"""FastFrame engine integration tests: correctness of answers vs exact,
early stopping, active scanning, COUNT/SUM, bitmaps, scramble."""

import numpy as np
import pytest

from repro.aqp import (AggQuery, EngineConfig, Expression, FastFrame, Filter,
                       build_scramble)
from repro.aqp.bitmap import build_bitmap, pack_mask, unpack_words
from repro.aqp.flights_queries import f_q1, f_q2, f_q5, f_q8, f_q9
from repro.aqp.scramble import build_scramble
from repro.core.optstop import (AbsoluteWidth, GroupsOrdered, ThresholdSide,
                                TopKSeparated)
from repro.data import flights


@pytest.fixture(scope="module")
def ds():
    return flights.generate(n_rows=400_000, n_airports=40, n_airlines=8,
                            seed=0)


@pytest.fixture(scope="module")
def frame(ds):
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=512,
                        seed=1)
    return FastFrame(sc, EngineConfig(round_blocks=32, lookahead_blocks=256))


def exact_group_avg(ds, value_col, group_col, mask=None):
    v = ds.columns[value_col].astype(np.float64)
    g = ds.columns[group_col]
    if mask is None:
        mask = np.ones_like(v, dtype=bool)
    out = {}
    for code in np.unique(g[mask]):
        rows = v[(g == code) & mask]
        out[int(code)] = rows.mean()
    return out


# -- scramble / bitmap units ---------------------------------------------------


def test_scramble_preserves_multiset(ds):
    sc = build_scramble(ds.columns, block_rows=512, seed=3)
    orig = np.sort(ds.columns["dep_delay"])
    got = np.sort(sc.columns["dep_delay"][sc.valid])
    np.testing.assert_allclose(got, orig)
    assert sc.n_rows == ds.n_rows
    assert sc.catalog["dep_delay"][0] <= orig[0]
    assert sc.catalog["dep_delay"][1] >= orig[-1]


def test_scramble_prefix_is_unbiased(ds):
    """Scan prefix mean ~ population mean (without-replacement sample)."""
    sc = build_scramble(ds.columns, block_rows=512, seed=4)
    prefix = sc.columns["dep_delay"][:64][sc.valid[:64]]
    mu = ds.columns["dep_delay"].mean()
    sd = ds.columns["dep_delay"].std() / np.sqrt(prefix.size)
    assert abs(prefix.mean() - mu) < 6 * sd


def test_bitmap_presence_exact(ds):
    sc = build_scramble(ds.columns, block_rows=512, seed=5)
    bm = build_bitmap(sc, "airline")
    # brute-force presence for 20 random blocks
    rng = np.random.default_rng(0)
    for blk in rng.integers(0, sc.n_blocks, 20):
        codes = sc.columns["airline"][blk][sc.valid[blk]]
        for c in range(sc.categorical["airline"]):
            bit = (bm.words[blk, c // 32] >> (c % 32)) & 1
            assert bool(bit) == bool((codes == c).any())


def test_pack_mask_roundtrip():
    rng = np.random.default_rng(0)
    mask = rng.random(77) < 0.3
    words = pack_mask(mask)
    for c in range(77):
        assert bool((words[c // 32] >> (c % 32)) & 1) == bool(mask[c])


@pytest.mark.parametrize("cardinality", [1, 32, 77, 200])
def test_unpack_words_inverts_the_packing(cardinality):
    rng = np.random.default_rng(cardinality)
    masks = rng.random((9, cardinality)) < 0.3
    words = np.stack([pack_mask(m) for m in masks])
    got = unpack_words(words, cardinality)
    assert got.dtype == bool and got.shape == masks.shape
    np.testing.assert_array_equal(got, masks)


@pytest.mark.parametrize("group_by", [None, "airline", ("origin",
                                                        "airline")])
def test_group_presence_is_cached_and_read_only(frame, group_by):
    """A query's presence matrix is the group column's bitmap unpacked,
    with its per-group block counts; one frame unpacks it once and every
    later query of the column reads the same read-only arrays."""
    from repro.aqp import engine

    q = AggQuery(agg="avg", column="dep_delay", group_by=group_by,
                 stop=AbsoluteWidth(eps=1.0))
    first, again = (engine._ScanViews(frame, q) for _ in range(2))
    assert again.presence is first.presence
    assert again.presence_total is first.presence_total
    assert not first.presence.flags.writeable
    assert not first.presence_total.flags.writeable
    if group_by is None:
        want = np.ones((frame.scramble.n_blocks, 1), bool)
    else:
        want = unpack_words(frame.bitmap(first.gcol).words, first.G)
    np.testing.assert_array_equal(first.presence, want)
    np.testing.assert_array_equal(first.presence_total,
                                  want.sum(axis=0, dtype=np.int32))


# -- engine: exact mode --------------------------------------------------------


def test_exact_mode_matches_numpy(ds, frame):
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 stop=None)
    res = frame.run(q, sampling="exact")
    want = exact_group_avg(ds, "dep_delay", "airline")
    for code, mu in want.items():
        assert res.nonempty[code]
        assert np.isclose(res.estimate[code], mu, rtol=5e-4), code  # f32 states
        assert res.lo[code] == res.hi[code] == res.estimate[code]


def test_exact_mode_with_filter(ds, frame):
    mask = ds.columns["dep_time"] > 600
    q = AggQuery(agg="avg", column="dep_delay",
                 filters=(Filter("dep_time", "gt", 600),), stop=None)
    res = frame.run(q, sampling="exact")
    want = ds.columns["dep_delay"][mask].astype(np.float64).mean()
    assert np.isclose(res.estimate[0], want, rtol=5e-4)  # f32 states


# -- engine: approximate paths ------------------------------------------------


@pytest.mark.parametrize("sampling", ["scan", "active_sync", "active_peek"])
def test_avg_group_threshold_correct(ds, frame, sampling):
    """F-q2 analogue: HAVING side must match exact, any sampling strategy."""
    thresh = float(np.median([m for m in exact_group_avg(
        ds, "dep_delay", "airline").values()]))
    q = f_q2(thresh=thresh, delta=1e-9)
    res = frame.run(q, sampling=sampling, seed=2)
    want = exact_group_avg(ds, "dep_delay", "airline")
    got_above = set(res.having("gt", thresh).tolist())
    want_above = {c for c, m in want.items() if m > thresh}
    assert got_above == want_above
    # intervals must cover the truth
    for c, m in want.items():
        assert res.lo[c] - 1e-3 <= m <= res.hi[c] + 1e-3, c  # f32 data


def test_avg_single_filter_early_stop(ds, frame):
    """F-q1 analogue: relative-accuracy stop, early termination, coverage."""
    q = f_q1(airport=0, eps=0.5, delta=1e-9)
    res = frame.run(q, sampling="active_peek", seed=3)
    mask = ds.columns["origin"] == 0
    truth = ds.columns["dep_delay"][mask].astype(np.float64).mean()
    assert res.lo[0] <= truth <= res.hi[0]
    assert res.stopped_early
    assert res.blocks_fetched < frame.scramble.n_blocks // 2


def test_topk_query_correct(ds, frame):
    q = f_q9(delta=1e-9)
    res = frame.run(q, sampling="active_peek", seed=4)
    want = exact_group_avg(ds, "dep_delay", "airline")
    true_top = max(want, key=want.get)
    assert res.topk(1)[0] == true_top


def test_count_query(ds, frame):
    q = AggQuery(agg="count", filters=(Filter("airline", "eq", 2),),
                 stop=AbsoluteWidth(eps=20_000.0), delta=1e-9)
    res = frame.run(q, sampling="scan", seed=5)
    truth = int((ds.columns["airline"] == 2).sum())
    assert res.lo[0] <= truth <= res.hi[0]
    assert res.hi[0] - res.lo[0] <= 20_000.0 or not res.stopped_early


def test_sum_query(ds, frame):
    truth = ds.columns["dep_delay"][ds.columns["airline"] == 2]\
        .astype(np.float64).sum()
    q = AggQuery(agg="sum", column="dep_delay",
                 filters=(Filter("airline", "eq", 2),),
                 stop=AbsoluteWidth(eps=abs(truth) * 2.0), delta=1e-9)
    res = frame.run(q, sampling="scan", seed=6)
    tol = 1e-5 * abs(truth)  # f32 data path on exact points
    assert res.lo[0] - tol <= truth <= res.hi[0] + tol


def test_expression_aggregate(ds, frame):
    expr = Expression(
        fn=lambda c: (c["dep_delay"] / 60.0) ** 2,
        columns=("dep_delay",), convex=True)
    q = AggQuery(agg="avg", column=expr, stop=AbsoluteWidth(eps=5.0),
                 delta=1e-9)
    res = frame.run(q, sampling="scan", seed=7)
    truth = ((ds.columns["dep_delay"].astype(np.float64) / 60.0) ** 2).mean()
    assert res.lo[0] <= truth <= res.hi[0]


def test_active_scanning_skips_blocks(ds, frame):
    """Sparse-group query: active_peek must fetch fewer blocks than scan."""
    q = f_q5(delta=1e-9)
    r_scan = frame.run(q, sampling="scan", seed=8, start_block=0)
    r_peek = frame.run(q, sampling="active_peek", seed=8, start_block=0)
    want = exact_group_avg(ds, "dep_delay", "origin")
    for res in (r_scan, r_peek):
        got_neg = set(res.having("lt", 0.0).tolist())
        want_neg = {c for c, m in want.items() if m < 0.0}
        assert got_neg == want_neg
    assert r_peek.blocks_fetched <= r_scan.blocks_fetched


def test_groups_ordered_stop(ds, frame):
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 stop=GroupsOrdered(), delta=1e-9)
    res = frame.run(q, sampling="active_peek", seed=9)
    want = exact_group_avg(ds, "dep_delay", "airline")
    want_order = [c for c, _ in sorted(want.items(), key=lambda kv: kv[1])]
    got_order = res.order(ascending=True).tolist()
    assert got_order == want_order


def test_anderson_dkw_end_to_end(ds, frame):
    q = AggQuery(agg="avg", column="dep_delay", bounder="anderson_dkw",
                 rangetrim=False, stop=AbsoluteWidth(eps=40.0), delta=1e-9)
    res = frame.run(q, sampling="scan", seed=10)
    truth = ds.columns["dep_delay"].astype(np.float64).mean()
    assert res.lo[0] <= truth <= res.hi[0]


def test_rangetrim_beats_plain_on_sparse_filter(ds):
    """The paper's headline: Bernstein+RT needs <= blocks of Bernstein for
    sparse views whose local range is far from the catalog range."""
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=512,
                        seed=11)
    frame = FastFrame(sc, EngineConfig(round_blocks=16,
                                       lookahead_blocks=256))
    # sparse airport (high code = rare under the Zipf law)
    sparse = 35
    n_rows = int((ds.columns["origin"] == sparse).sum())
    assert 0 < n_rows < 6_000
    kw = dict(eps=0.5, delta=1e-9)
    rt = frame.run(f_q1(airport=sparse, rangetrim=True, **kw),
                   sampling="scan", start_block=0)
    plain = frame.run(f_q1(airport=sparse, rangetrim=False, **kw),
                      sampling="scan", start_block=0)
    assert rt.blocks_fetched <= plain.blocks_fetched
