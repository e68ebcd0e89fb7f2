"""The device generator draws FLIGHTS from the distributions of the
program's host generator (``repro.data.flights``)."""

import json

import numpy as np
import pytest

from bench.generators import flights as gen
from bench.tests.conftest import ROOT

ROWS = 400_000


@pytest.fixture(scope="module")
def tables(x64):
    from repro.data import flights

    cfg = json.loads((ROOT / "bench/configs/flights-151m.json").read_text())
    cfg["rows"] = ROWS
    cols, valid = gen.generate(cfg, seed=2**33 + 5)
    n = cfg["rows"]
    ours = {k: v.reshape(-1)[:n] for k, v in cols.items()}
    theirs = flights.generate(n_rows=n, seed=0).columns
    return cfg, ours, theirs, valid


def _ks(a, b):
    grid = np.linspace(0, 1, 201)
    return np.abs(np.quantile(a, grid) - np.quantile(b, grid)).max()


def test_dtypes_shape_and_padding(tables):
    cfg, ours, _, valid = tables
    for name, dt in gen.COLUMNS.items():
        assert ours[name].dtype == dt
    nb = -(-ROWS // cfg["block_rows"])
    assert valid.shape == (nb, cfg["block_rows"])
    assert valid.sum() == ROWS


def test_alias_tables_reproduce_the_distribution():
    p = np.random.default_rng(3).dirichlet(np.full(14, 3.0))
    accept, alias = gen.alias_table(p)
    q = accept / len(p)
    np.add.at(q, alias, (1.0 - accept) / len(p))
    np.testing.assert_allclose(q, p, atol=1e-12)


def test_categorical_marginals(tables):
    cfg, ours, theirs, _ = tables
    p = gen.params(cfg)
    for col, want in (("origin", p["p_airport"]), ("airline", p["p_airline"])):
        got = np.bincount(ours[col], minlength=len(want)) / ROWS
        assert 0.5 * np.abs(got - want).sum() < 0.01, col
    # the host generator's airports follow the same Zipf(1.1) shares
    zipf = np.bincount(theirs["origin"], minlength=200) / ROWS
    assert 0.5 * np.abs(zipf - p["p_airport"]).sum() < 0.01
    for day in (ours["day_of_week"], theirs["day_of_week"]):
        share = np.bincount(day, minlength=8)[1:] / ROWS
        np.testing.assert_allclose(share, 1 / 7, atol=0.005)


def test_continuous_marginals(tables):
    _, ours, theirs, _ = tables
    assert _ks(ours["dep_time"] / 1440, theirs["dep_time"] / 1440) < 0.01
    d, h = ours["dep_delay"], theirs["dep_delay"]
    assert d.min() >= gen.DELAY_RANGE[0] and d.max() <= gen.DELAY_RANGE[1]
    # locations are drawn per deployment, so compare the shape loosely
    assert abs(d.mean() - h.mean()) < 4.0
    assert abs(np.median(d) - np.median(h)) < 4.0
    assert 0.75 < d.std() / h.std() < 1.33
    assert abs((d > 60).mean() - (h > 60).mean()) < 0.02


def test_same_seed_same_rows(x64):
    cfg = json.loads((ROOT / "bench/configs/flights-151m.json").read_text())
    cfg["rows"] = 5000
    a, _ = gen.generate(cfg, seed=2**31 + 1)
    b, _ = gen.generate(cfg, seed=2**31 + 1)
    c, _ = gen.generate(cfg, seed=2**31 + 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["dep_delay"], c["dep_delay"])
