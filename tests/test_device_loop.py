"""Device-resident round loop equivalence suite.

``EngineConfig(device_loop=True)`` fuses the WHOLE OptStop loop — scan,
fold, f64 state merge, accounting, CI refresh and stop test — into
``lax.while_loop`` dispatches. This suite pins it to the per-round host
loop (``device_loop=False``, the oracle, same pattern as ``fused``):

  * folds, coverage, soundness flags (exact / tainted) and scan metrics
    must match EXACTLY (same decisions, same arithmetic: the device f64
    merge is the same formula as ``merge_moments_host``);
  * CI endpoints / estimates must agree to <= 1e-9 (numpy libm vs XLA
    transcendentals differ in the last ulp);
  * ``sync_every`` chunking is a dispatch-granularity knob only — any
    chunk size must produce results identical to the unchunked loop;
  * the x64 guard fires a clear error instead of silently demoting the
    float64 bound math.
"""

import jax
import numpy as np
import pytest

from repro.aqp import (AggQuery, EngineConfig, Expression, FastFrame,
                       Filter, build_scramble)
from repro.core.optstop import (AbsoluteWidth, FixedSamples, GroupsOrdered,
                                RelativeWidth, ThresholdSide,
                                TopKSeparated)
from repro.data import flights
from repro.serve import FrameServer

EXACT_FIELDS = [
    "group_codes", "count_seen", "nonempty", "exact", "tainted",
    "rows_covered", "blocks_fetched", "blocks_skipped_active",
    "blocks_skipped_static", "bitmap_probes", "rounds", "stopped_early",
]
CI_FIELDS = ["estimate", "lo", "hi"]
ALL_FIELDS = EXACT_FIELDS + CI_FIELDS


@pytest.fixture(scope="module", autouse=True)
def _x64(x64_module):
    yield


def assert_device_matches_host(r_dev, r_host, atol=1e-9):
    for f in EXACT_FIELDS:
        a, b = getattr(r_dev, f), getattr(r_host, f)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            assert a == b, (f, a, b)
    for f in CI_FIELDS:
        a, b = getattr(r_dev, f), getattr(r_host, f)
        np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b),
                                      err_msg=f)
        fin = np.isfinite(a)
        # atol covers data-scale endpoints; the tiny rtol covers SUM
        # endpoints scaled by R (last-ulp libm-vs-XLA differences)
        np.testing.assert_allclose(a[fin], b[fin], rtol=1e-12, atol=atol,
                                   err_msg=f)


def run_both(sc, q, sampling, seed=1, start=0, **cfg_kw):
    r_d = FastFrame(sc, EngineConfig(device_loop=True, **cfg_kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    r_h = FastFrame(sc, EngineConfig(device_loop=False, **cfg_kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    return r_d, r_h


@pytest.fixture(scope="module")
def sc():
    ds = flights.generate(n_rows=80_000, n_airports=60, n_airlines=6,
                          seed=3)
    return build_scramble(ds.columns, catalog=ds.catalog, block_rows=256,
                          seed=4)


SCENARIOS = [
    ("avg-group-topk-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=TopKSeparated(k=2, largest=True), delta=1e-9),
     "active_peek"),
    ("avg-group-bottomk-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=TopKSeparated(k=3, largest=False), delta=1e-9),
     "active_peek"),
    ("avg-group-thresh-sync",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=ThresholdSide(threshold=0.0), delta=1e-9),
     "active_sync"),
    ("avg-group-relwidth-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="airline",
              stop=RelativeWidth(eps=0.5), delta=1e-6),
     "active_peek"),
    ("avg-group-fixedsamples-scan",
     AggQuery(agg="avg", column="dep_delay", group_by="airline",
              stop=FixedSamples(m=4000), delta=1e-9),
     "scan"),
    ("sum-filter-scan",
     AggQuery(agg="sum", column="dep_delay",
              filters=(Filter("airline", "eq", 2),),
              stop=AbsoluteWidth(eps=1e6), delta=1e-9),
     "scan"),
    ("count-filter-peek",
     AggQuery(agg="count", filters=(Filter("origin", "eq", 3),),
              stop=AbsoluteWidth(eps=5e3), delta=1e-9),
     "active_peek"),
    ("avg-anderson-dkw-scan",
     AggQuery(agg="avg", column="dep_delay", bounder="anderson_dkw",
              rangetrim=False, stop=AbsoluteWidth(eps=30.0), delta=1e-9),
     "scan"),
    ("avg-hoeffding-serfling-rt-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="airline",
              bounder="hoeffding_serfling", rangetrim=True,
              stop=AbsoluteWidth(eps=15.0), delta=1e-9),
     "active_peek"),
    ("expr-composite-ordered-peek",
     AggQuery(agg="avg",
              column=Expression(fn=lambda c: (c["dep_delay"] / 60.0) ** 2,
                                columns=("dep_delay",), convex=True),
              group_by=("airline", "day_of_week"),
              stop=GroupsOrdered(), delta=1e-6),
     "active_peek"),
    # eps too tight to ever satisfy -> full-sweep exhaustion, exact views
    ("avg-exhaust-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=AbsoluteWidth(eps=1e-7), delta=1e-9),
     "active_peek"),
]


@pytest.mark.parametrize("name,q,sampling",
                         SCENARIOS, ids=[s[0] for s in SCENARIOS])
def test_device_loop_matches_host_loop(sc, name, q, sampling):
    r_d, r_h = run_both(sc, q, sampling, seed=1, start=0,
                        round_blocks=16, lookahead_blocks=64,
                        sync_lookahead_blocks=16, hist_bins=256)
    assert_device_matches_host(r_d, r_h)
    if name == "avg-exhaust-peek":
        assert r_d.exact.all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_loop_randomized_starts(sc, seed):
    """Random scan starts (wrap-around windows) and unknown-N filters."""
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 filters=(Filter("dep_time", "gt", 400.0),),
                 stop=ThresholdSide(threshold=10.0), delta=1e-9)
    r_d, r_h = run_both(sc, q, "active_peek", seed=seed, start=None,
                        round_blocks=8, lookahead_blocks=64)
    assert_device_matches_host(r_d, r_h)


def _taint_scramble():
    rng = np.random.default_rng(0)
    n = 40_000
    g = (rng.random(n) < 0.02).astype(np.int32)  # rare group 1
    v = np.where(g == 1, rng.normal(50.0, 30.0, n),
                 rng.normal(100.0, 1.0, n)).astype(np.float32)
    return build_scramble({"g": g, "v": v}, catalog={"v": (-100.0, 250.0)},
                          block_rows=64, seed=1)


@pytest.mark.parametrize("sampling", ["active_peek", "active_sync"])
def test_device_loop_taint_propagates_out_of_while_loop(sampling):
    """Taint accrued inside the while_loop carry must surface identically
    to the host loop's accounting (and the recovery pass must see it)."""
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=ThresholdSide(threshold=50.0), delta=1e-6)
    r_d, r_h = run_both(_taint_scramble(), q, sampling, seed=1, start=0,
                        round_blocks=8, lookahead_blocks=64,
                        sync_lookahead_blocks=16)
    assert_device_matches_host(r_d, r_h)
    assert r_d.blocks_skipped_active > 0
    assert r_d.tainted[0] and not r_d.tainted[1]


# A slot without the probe holding two ungrouped queries that stop on
# different rounds: the slot scans on after the first one finishes, so
# its intervals and snapshots must stay as they were when it finished.
UNGROUPED_SLOT = [
    AggQuery(agg="avg", column="dep_delay", stop=AbsoluteWidth(eps=30.0),
             delta=1e-9),
    AggQuery(agg="avg", column="dep_delay", stop=AbsoluteWidth(eps=8.0),
             delta=1e-9),
]

SERVED_BATCHES = {
    "mixed": [
        AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=TopKSeparated(k=2), delta=1e-9),
        AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=ThresholdSide(threshold=0.0), delta=1e-6),
        AggQuery(agg="sum", column="dep_delay", group_by="airline",
                 stop=AbsoluteWidth(eps=1e6), delta=1e-9),
        AggQuery(agg="count", group_by="airline",
                 stop=AbsoluteWidth(eps=5e3), delta=1e-9),
        AggQuery(agg="avg", column="dep_delay", bounder="anderson_dkw",
                 rangetrim=False, stop=AbsoluteWidth(eps=30.0),
                 delta=1e-9),
    ],
    "ungrouped-slot": UNGROUPED_SLOT,
}


@pytest.mark.parametrize("batch", sorted(SERVED_BATCHES))
def test_device_loop_serve_pass_matches_host_pass(sc, batch):
    """The multi-query pass loop (per-slot cursors and folds,
    finish-time snapshots recorded in the carry) must reproduce the host
    pass for every query of a mixed batch, and of one slot whose queries
    finish on different rounds."""
    queries = SERVED_BATCHES[batch]
    kw = dict(round_blocks=16, lookahead_blocks=64, hist_bins=256)
    res_d = FrameServer(FastFrame(
        sc, EngineConfig(device_loop=True, **kw))).run_batch(
        queries, start_block=0, seed=1)
    res_h = FrameServer(FastFrame(
        sc, EngineConfig(device_loop=False, **kw))).run_batch(
        queries, start_block=0, seed=1)
    for r_d, r_h in zip(res_d, res_h):
        assert_device_matches_host(r_d, r_h)
    if batch == "ungrouped-slot":
        assert all(r.stopped_early for r in res_d)
        assert res_d[0].rounds < res_d[1].rounds


def test_run_drives_a_one_slot_pass(sc, monkeypatch):
    """``FastFrame.run``'s device path is the pass loop of one slot
    holding one query."""
    from repro.kernels import fused_scan as kfused

    built = []
    build = kfused.build_pass_loop

    def spied(**kw):
        built.append(kw)
        return build(**kw)

    monkeypatch.setattr(kfused, "build_pass_loop", spied)
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=ThresholdSide(threshold=0.0), delta=1e-9)
    FastFrame(sc, EngineConfig(device_loop=True, round_blocks=16,
                               lookahead_blocks=64)).run(
        q, seed=1, start_block=0)
    assert len(built) == 1
    assert len(built[0]["slot_specs"]) == 1
    assert [len(fns) for fns in built[0]["refresh_fns"]] == [1]
    assert built[0]["slot_specs"][0].probe


def test_device_loop_served_singleton_matches_run(sc):
    """A served singleton through the device pass loop stays identical to
    ``FastFrame.run``, the same loop driven as a one-slot pass."""
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=ThresholdSide(threshold=0.0), delta=1e-9)
    cfg = dict(device_loop=True, round_blocks=16, lookahead_blocks=64)
    r_run = FastFrame(sc, EngineConfig(**cfg)).run(q, seed=1,
                                                   start_block=0)
    r_srv = FrameServer(FastFrame(sc, EngineConfig(**cfg))).run_batch(
        [q], seed=1, start_block=0)[0]
    for f in ALL_FIELDS:
        a, b = getattr(r_run, f), getattr(r_srv, f)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            assert a == b, (f, a, b)


def test_on_sync_streams_snapshots(sc):
    """sync_every chunks the loop into dispatches and surfaces a
    monotone stream of interval snapshots."""
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=ThresholdSide(threshold=0.0), delta=1e-9)
    snaps = []
    FastFrame(sc, EngineConfig(device_loop=True, sync_every=2,
                               round_blocks=16, lookahead_blocks=64)).run(
        q, seed=1, start_block=0, on_sync=snaps.append)
    assert len(snaps) >= 2
    rounds = [s["rounds"] for s in snaps]
    assert rounds == sorted(rounds)
    assert all(r2 - r1 <= 2 for r1, r2 in zip(rounds, rounds[1:]))
    assert snaps[-1]["live"] is False
    # running intervals only tighten across syncs
    for s1, s2 in zip(snaps, snaps[1:]):
        assert (s2["lo"] >= s1["lo"] - 1e-12).all()
        assert (s2["hi"] <= s1["hi"] + 1e-12).all()


def test_device_loop_x64_guard():
    """Explicit device_loop=True without x64 must raise the clear guard
    error (silent f32 demotion would invalidate guarantees); the auto
    default (None) silently falls back to the host loop instead."""
    ds = flights.generate(n_rows=10_000, n_airports=8, n_airlines=4,
                          seed=9)
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=256,
                        seed=10)
    q = AggQuery(agg="avg", column="dep_delay",
                 stop=AbsoluteWidth(eps=20.0), delta=1e-6)
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(RuntimeError, match="jax_enable_x64"):
            FastFrame(sc, EngineConfig(device_loop=True)).run(
                q, seed=0, start_block=0)
        assert EngineConfig(device_loop=None).resolve_device_loop() is False
        r = FastFrame(sc, EngineConfig(device_loop=None)).run(
            q, seed=0, start_block=0)  # host loop, no error
        assert r.rounds >= 1
    finally:
        jax.config.update("jax_enable_x64", True)
    assert EngineConfig(device_loop=None).resolve_device_loop() is True


def test_device_loop_requires_fused():
    with pytest.raises(ValueError, match="fused"):
        EngineConfig(device_loop=True, fused=False).resolve_device_loop()
