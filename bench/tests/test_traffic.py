"""The general traffic generator: the same seed gives the same questions,
traced questions go first, and drawn values, picks and arrivals follow
the mix's data."""

import copy
import json

import numpy as np
import pytest

from bench import traffic
from bench.tests.conftest import ROOT

SEED = 2**31 + 33
N_BLOCKS = 147_950


def _mix(name="suite-solo"):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_mix_files_validate(name):
    traffic.validate(_mix(name))


def test_a_cycle_asks_each_template_once_from_the_seed():
    mix = _mix()
    a = traffic.cycle(mix, SEED, 3, N_BLOCKS)
    assert a == traffic.cycle(mix, SEED, 3, N_BLOCKS)
    assert sorted(r.template for r in a) == sorted(mix["templates"])
    assert all(0 <= r.start < N_BLOCKS and r.due_s is None for r in a)
    assert all(r.spec is mix["templates"][r.template] for r in a)
    b = traffic.cycle(mix, SEED, 4, N_BLOCKS)
    assert [r.start for r in a] != [r.start for r in b]


def test_traced_requests_go_first():
    mix = _mix("wholetable-solo")
    plain = traffic.cycle(mix, SEED, 1, N_BLOCKS)
    traced = traffic.cycle(mix, SEED, 1, N_BLOCKS, traced=True)
    k = len(mix["trace"])
    assert [r.traced for r in traced] == [True] * k + [False] * (
        len(traced) - k)
    assert sorted(r.template for r in traced[:k]) == sorted(mix["trace"])
    # the same requests, each group in the cycle's own order
    untraced = [r for r in plain if r.template not in mix["trace"]]
    assert [(r.template, r.start) for r in traced[k:]] == [
        (r.template, r.start) for r in untraced]
    assert [r.template for r in traced[:k]] == [
        r.template for r in plain if r.template in mix["trace"]]


def test_filter_values_drawn_per_question():
    mix = _mix()
    mix["templates"] = {"F-q1": copy.deepcopy(mix["templates"]["F-q1"]),
                        "F-q6": copy.deepcopy(mix["templates"]["F-q6"])}
    mix["templates"]["F-q1"]["filters"][0][2] = {"zipf": 1.1, "n": 200}
    mix["templates"]["F-q6"]["filters"][0][2] = {"uniform": [0, 1440],
                                                 "step": 10}
    mix["trace"] = ["F-q1"]
    traffic.validate(mix)
    airports, times = [], []
    for c in range(2000):
        for r in traffic.cycle(mix, SEED, c, N_BLOCKS):
            value = r.spec["filters"][0][2]
            (airports if r.template == "F-q1" else times).append(value)
    assert all(isinstance(v, int) for v in airports + times)
    assert min(airports) >= 0 and max(airports) < 200
    counts = np.bincount(airports, minlength=200)
    w = np.arange(1, 201) ** -1.1
    np.testing.assert_allclose(counts[:5] / counts.sum(), (w / w.sum())[:5],
                               atol=0.03)
    assert set(times) <= set(range(0, 1441, 10)) and len(set(times)) > 100
    # the mix itself keeps its distributions
    assert isinstance(mix["templates"]["F-q1"]["filters"][0][2], dict)


def test_weighted_pick_and_arrivals():
    mix = _mix()
    mix["pick"] = {"kind": "weighted", "weights": {"F-q1": 3, "F-q8": 1},
                   "per_cycle": 40}
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 2.0}
    traffic.validate(mix)
    reqs = traffic.cycle(mix, SEED, 0, N_BLOCKS)
    assert len(reqs) == 40
    assert {r.template for r in reqs} == {"F-q1", "F-q8"}
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] > 0
    assert 5 < due[-1] < 60
    mix["arrivals"] = {"kind": "burst"}
    assert all(r.due_s == 0.0
               for r in traffic.cycle(mix, SEED, 0, N_BLOCKS))


@pytest.mark.parametrize("change", [
    {"client": "no_such_client"},
    {"client": "../run"},
    {"trace": ["F-q42"]},
    {"trace": []},
    {"pick": {"kind": "weighted", "weights": {"F-q42": 1},
              "per_cycle": 1}},
    {"arrivals": {"kind": "open"}},
])
def test_validate_refuses(change):
    mix = dict(_mix(), **change)
    with pytest.raises(ValueError):
        traffic.validate(mix)


def test_validate_refuses_an_unknown_draw():
    mix = _mix()
    mix["templates"]["F-q1"]["filters"][0][2] = {"normal": 3}
    with pytest.raises(ValueError):
        traffic.validate(mix)
