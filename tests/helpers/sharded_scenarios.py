"""Sharded-vs-oracle scenarios, shared by the in-process multi-device
suite (``tests/test_sharded_scan.py``, run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and the
subprocess worker (``tests/helpers/dist_aqp_worker.py``) that gives
tier-1 coverage on single-device machines.

Equivalence discipline (mirrors ``EngineConfig.shard_rows``):

  * scan decisions, coverage, taint, fold counts and every scan metric
    must match the single-device device loop EXACTLY — selection and
    accounting are replicated computations over replicated inputs, so
    any difference is a bug, not noise;
  * fold deltas are bitwise whenever the per-shard f32 partial sums are
    exactly representable (``scenario_exhaustion_bitwise`` constructs
    such data and asserts FULL bitwise equality, intervals included);
  * on general data the shard merge reorders the f32 row sum, so CI
    endpoints / estimates carry f32-reorder noise — asserted within
    ``CI_RTOL`` (relative ~1e-3 bound; observed ~1e-6..1e-4).

Callers must enable 64-bit JAX types and provide >= 2 devices before
invoking any scenario (the device-resident loop requires x64; the mesh
requires devices fixed before jax initializes).
"""

import numpy as np

from repro.aqp import (AggQuery, EngineConfig, FastFrame, Filter,
                       build_scramble)
from repro.core.optstop import (AbsoluteWidth, ThresholdSide,
                                TopKSeparated)
from repro.data import flights
from repro.serve import FrameServer

EXACT_FIELDS = [
    "group_codes", "count_seen", "nonempty", "exact", "tainted",
    "rows_covered", "blocks_fetched", "blocks_skipped_active",
    "blocks_skipped_static", "bitmap_probes", "rounds", "stopped_early",
]
CI_FIELDS = ["estimate", "lo", "hi"]
CI_RTOL = 1e-3     # f32-reorder noise bound on general data
CI_ATOL = 1e-6

CFG = dict(device_loop=True, round_blocks=16, lookahead_blocks=64,
           sync_lookahead_blocks=16, hist_bins=256)


def assert_sharded_matches_oracle(r_sh, r_or, bitwise_ci=False):
    """Exact fields equal; CI endpoints bitwise (``bitwise_ci``, for
    exactly-representable data) or within the f32-reorder bound."""
    for f in EXACT_FIELDS:
        a, b = getattr(r_sh, f), getattr(r_or, f)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            assert a == b, (f, a, b)
    for f in CI_FIELDS:
        a, b = getattr(r_sh, f), getattr(r_or, f)
        if bitwise_ci:
            np.testing.assert_array_equal(a, b, err_msg=f)
            continue
        np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b),
                                      err_msg=f)
        fin = np.isfinite(a)
        np.testing.assert_allclose(a[fin], b[fin], rtol=CI_RTOL,
                                   atol=CI_ATOL, err_msg=f)


def run_pair(sc, q, sampling="active_peek", mesh_shape=None, seed=1,
             start=0, **over):
    """Run one query sharded (``shard_rows=True``) and on the
    single-device oracle (``shard_rows=False``), fresh frames each."""
    kw = dict(CFG)
    kw.update(over)
    r_sh = FastFrame(sc, EngineConfig(shard_rows=True,
                                      mesh_shape=mesh_shape, **kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    r_or = FastFrame(sc, EngineConfig(shard_rows=False, **kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    return r_sh, r_or


def flights_scramble(n_rows=60_000, block_rows=256):
    ds = flights.generate(n_rows=n_rows, n_airports=30, n_airlines=5,
                          seed=3)
    return build_scramble(ds.columns, catalog=ds.catalog,
                          block_rows=block_rows, seed=4)


def scenario_groupby_topk():
    """GROUP BY + TopK early stop: activity skipping + probe metrics."""
    sc = flights_scramble()
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=TopKSeparated(k=2, largest=True), delta=1e-9)
    assert_sharded_matches_oracle(*run_pair(sc, q))


def scenario_groupby_threshold_2d_mesh():
    """Explicit 2-D mesh_shape (block axis sharded over the flattened
    axes). Needs >= 4 devices."""
    import jax
    n = jax.device_count()
    assert n >= 4, f"needs >= 4 devices, have {n}"
    sc = flights_scramble()
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=ThresholdSide(threshold=0.0), delta=1e-9)
    assert_sharded_matches_oracle(*run_pair(sc, q, mesh_shape=(2, n // 2)))


def scenario_filtered_sum():
    """Unknown-N SUM with a filter (static prefilter + N+ bound math)."""
    sc = flights_scramble()
    q = AggQuery(agg="sum", column="dep_delay",
                 filters=(Filter("airline", "eq", 2),),
                 stop=AbsoluteWidth(eps=1e6), delta=1e-9)
    assert_sharded_matches_oracle(*run_pair(sc, q, sampling="scan"))


def scenario_taint():
    """Taint accrued inside the sharded while_loop carry must surface
    identically (rare group goes inactive -> its blocks activity-skip)."""
    rng = np.random.default_rng(0)
    n = 40_000
    g = (rng.random(n) < 0.02).astype(np.int32)
    v = np.where(g == 1, rng.normal(50.0, 30.0, n),
                 rng.normal(100.0, 1.0, n)).astype(np.float32)
    sc = build_scramble({"g": g, "v": v}, catalog={"v": (-100.0, 250.0)},
                        block_rows=64, seed=1)
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=ThresholdSide(threshold=50.0), delta=1e-6)
    r_sh, r_or = run_pair(sc, q, round_blocks=8)
    assert_sharded_matches_oracle(r_sh, r_or)
    assert r_sh.blocks_skipped_active > 0
    assert r_sh.tainted[0] and not r_sh.tainted[1]


def _integer_scramble(n=50_000, groups=8):
    """Exactly-representable data: small-integer values, cyclic groups —
    every per-shard f32 partial sum is an exact integer, so the psum
    merge computes the same real numbers as the single-device fold (the
    ``dist_aqp_bitwise_worker`` methodology at engine level)."""
    g = (np.arange(n) % groups).astype(np.int32)
    v = (((np.arange(n) * 7) // 5 + g) % 5).astype(np.float32)
    return build_scramble({"g": g, "v": v}, catalog={"v": (0.0, 4.0)},
                          block_rows=256, seed=1)


def scenario_exhaustion_bitwise():
    """Scan exhaustion on exactly-representable data: the whole result —
    intervals included — must be BITWISE identical to the oracle."""
    sc = _integer_scramble()
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # never fires
    r_sh, r_or = run_pair(sc, q)
    assert_sharded_matches_oracle(r_sh, r_or, bitwise_ci=True)
    assert r_sh.exact.all()


def scenario_early_stop_bitwise():
    """Early stop on exactly-representable data: bitwise, and the stop
    decision itself (rounds / stopped_early) identical."""
    sc = _integer_scramble()
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=ThresholdSide(threshold=2.0), delta=1e-6)
    r_sh, r_or = run_pair(sc, q)
    assert_sharded_matches_oracle(r_sh, r_or, bitwise_ci=True)


def scenario_uneven_tail():
    """n_blocks not divisible by n_shards: the tail shard is zero-padded;
    no block may be dropped or double-counted (counts are exact)."""
    import jax
    n_dev = jax.device_count()
    # 61 blocks: indivisible by any device count >= 2
    sc = flights_scramble(n_rows=61 * 128, block_rows=128)
    assert sc.n_blocks % n_dev != 0, (sc.n_blocks, n_dev)
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # exhaustion
    r_sh, r_or = run_pair(sc, q, round_blocks=8)
    assert_sharded_matches_oracle(r_sh, r_or)
    assert r_sh.exact.all()


def scenario_last_block_start():
    """A scan that starts at the last block: every round's window wraps
    from block nb - 1 to block 0 at its first lane (probe on, exhaustion
    so every block is processed), bitwise on exactly-representable data,
    through ``FastFrame.run`` and through ``FrameServer``."""
    sc = _integer_scramble()
    nb = sc.n_blocks
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # never fires
    r_sh, r_or = run_pair(sc, q, start=nb - 1)
    assert_sharded_matches_oracle(r_sh, r_or, bitwise_ci=True)
    assert r_sh.exact.all() and r_sh.blocks_fetched == nb
    (r_pass,) = FrameServer(FastFrame(sc, EngineConfig(
        shard_rows=True, **CFG))).run_batch([q], start_block=nb - 1,
                                            seed=1)
    assert_sharded_matches_oracle(r_pass, r_or, bitwise_ci=True)


def scenario_server_pass():
    """A mixed FrameServer batch through the sharded pass loop (per-slot
    cursors, per-slot collective folds, finish-time snapshots)."""
    sc = flights_scramble()
    queries = [
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
    ]
    res_sh = FrameServer(FastFrame(sc, EngineConfig(
        shard_rows=True, **CFG))).run_batch(queries, start_block=0,
                                            seed=1)
    res_or = FrameServer(FastFrame(sc, EngineConfig(
        shard_rows=False, **CFG))).run_batch(queries, start_block=0,
                                             seed=1)
    for r_sh, r_or in zip(res_sh, res_or):
        assert_sharded_matches_oracle(r_sh, r_or)


def scenario_carousel_sharded_lap():
    """Carousel lap on a sharded merge_every=1 pass: a query admitted
    mid-scan advances its own slot cursor through the divided scan, wraps
    past the last block, and its full lap must be BITWISE identical to a
    single-device solo run rotated to its admission anchor — intervals
    included (exactly-representable data), probe slot included (the
    per-slot-cursor contract covers GROUP BY probes too)."""
    sc = _integer_scramble()          # nb = 196 at block_rows=256
    nb = sc.n_blocks
    frame = FastFrame(sc, EngineConfig(shard_rows=True, **CFG))
    p = FrameServer(frame).open_pass((), seed=1, start_block=0,
                                     chunk_rounds=2)
    q0 = AggQuery(agg="avg", column="v", group_by="g",
                  stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # probe slot
    q1 = AggQuery(agg="sum", column="v",
                  stop=AbsoluteWidth(eps=1e-9), delta=1e-9)
    (qc0,) = p.admit([q0])
    for _ in range(2):                # 2 chunks x 2 rounds
        p.step()
    (qc1,) = p.admit([q1])            # late joiner, mid-scan
    assert qc1.slot.anchor > 0 and p.wrap, (qc1.slot.anchor, p.wrap)
    p.run_to_completion()
    p.finish()
    r0 = p.result_of(q0)
    r1 = p.result_of(q1)
    oracle = FastFrame(sc, EngineConfig(shard_rows=False, **CFG))
    assert_sharded_matches_oracle(
        r0, oracle.run(q0, seed=1, start_block=0), bitwise_ci=True)
    assert_sharded_matches_oracle(
        r1, FastFrame(sc, EngineConfig(shard_rows=False, **CFG)).run(
            q1, seed=1, start_block=qc1.slot.anchor % nb),
        bitwise_ci=True)
    assert r0.exact.all() and r1.exact.all()


# -- collective cadence (merge_every > 1) ------------------------------------
#
# Equivalence discipline for the cadence path (vs the merge_every=1
# oracle, both sharded):
#
#   * selection/coverage/fold counts stay exact under ``sampling="scan"``
#     (the cursor never consults the active mask, so the block schedule
#     is cadence-independent);
#   * the two paths associate the same per-round fold deltas differently
#     (K=1 Chan-merges each round's delta; cadence pools K deltas in f64
#     and merges once), so even on exactly-representable data the CI
#     endpoints agree only to f64 association-order rounding (observed
#     ~6e-8; asserted within 1e-5) — and on general f32 data only within
#     the usual ``CI_RTOL`` f32-reorder class;
#   * staleness may only *delay* refreshes: every synced cadence CI must
#     be superset-or-equal of the oracle CI on the same prefix (up to
#     the noise class above), and termination must never consume
#     unmerged stats (merge-then-confirm).

CADENCE_TOL = 1e-5   # f64 association-order bound on exact-integer data


def run_cadence_pair(sc, q, merge_every=4, sampling="scan", seed=1,
                     start=0, on_sync=None, **over):
    """Run one query sharded at ``merge_every=K`` and at the per-round
    oracle ``merge_every=1`` (both ``shard_rows=True``), fresh frames."""
    kw = dict(CFG)
    kw.update(over)
    snaps_k, snaps_1 = [], []
    r_k = FastFrame(sc, EngineConfig(
        shard_rows=True, merge_every=merge_every, **kw)).run(
        q, sampling=sampling, seed=seed, start_block=start,
        on_sync=snaps_k.append if on_sync else None)
    r_1 = FastFrame(sc, EngineConfig(
        shard_rows=True, merge_every=1, **kw)).run(
        q, sampling=sampling, seed=seed, start_block=start,
        on_sync=snaps_1.append if on_sync else None)
    if on_sync:
        return (r_k, snaps_k), (r_1, snaps_1)
    return r_k, r_1


def scenario_cadence_superset_sync():
    """Staleness soundness at every host sync: the cadence CI must be a
    superset-or-equal of the oracle CI on the same scanned prefix —
    stale bounds may be looser, never tighter. Exact-integer data keeps
    the comparison at f64 association-order noise (``CADENCE_TOL``)
    instead of the much looser f32-reorder class."""
    sc = _integer_scramble()
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # never fires
    (r_k, snaps_k), (r_1, snaps_1) = run_cadence_pair(
        sc, q, merge_every=4, sync_every=3, on_sync=True)
    assert len(snaps_k) == len(snaps_1) > 1
    for a, b in zip(snaps_k, snaps_1):
        # scan-sampled prefixes are identical dispatch by dispatch
        assert a["rounds"] == b["rounds"]
        fin = np.isfinite(b["lo"]) & np.isfinite(b["hi"])
        np.testing.assert_array_equal(np.isfinite(a["lo"]), fin)
        tol = CADENCE_TOL * np.maximum(1.0, np.abs(b["est"][fin]))
        assert (a["lo"][fin] <= b["lo"][fin] + tol).all(), \
            ("cadence lo tighter than oracle",
             (a["lo"][fin] - b["lo"][fin]).max())
        assert (a["hi"][fin] >= b["hi"][fin] - tol).all(), \
            ("cadence hi tighter than oracle",
             (b["hi"][fin] - a["hi"][fin]).max())
    np.testing.assert_array_equal(r_k.count_seen, r_1.count_seen)
    assert r_k.rounds == r_1.rounds and r_k.exact.all()


def scenario_cadence_merge_confirm():
    """A query can never terminate on unmerged stats.

    Adversarial layout for the ROW-SLICE divided scan: within every
    block, the rows of shard d's slice are constant 49 (even d) or 51
    (odd d), so each shard's local fold only ever sees ONE of the two
    values no matter which blocks the cursor picks, while every block's
    true mean is exactly 50 — the threshold. Globally the CI straddles
    forever and the scan must run to exhaustion. Between merges the
    cadence loop runs ZERO collectives, so a shard's local partials are
    one-sided (all-49 or all-51 => CI clear of the threshold); a loop
    that consulted that local view would stop inside the very first
    cadence window with estimate ~49. Termination may only be evaluated
    at the deterministic merge boundary, AFTER the pooled deltas fold
    in."""
    import jax
    n_dev = jax.device_count()
    assert n_dev >= 2 and n_dev % 2 == 0, n_dev
    nb, block_rows = 16, 128
    assert block_rows % n_dev == 0, (block_rows, n_dev)
    slice_rows = block_rows // n_dev
    n = nb * block_rows
    g = np.zeros(n, np.int32)
    owner = (np.arange(n) % block_rows) // slice_rows
    v = np.where(owner % 2 == 0, np.float32(49.0), np.float32(51.0))
    sc = build_scramble({"g": g, "v": v}, catalog={"v": (49.0, 51.0)},
                        block_rows=block_rows, seed=1)
    # build_scramble shuffles blocks, but every block carries the same
    # row pattern — restore anyway so the layout is assignment-exact
    sc.columns["v"][:] = v.reshape(sc.columns["v"].shape)
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=ThresholdSide(threshold=50.0), delta=1e-6)
    r_k, r_1 = run_cadence_pair(sc, q, merge_every=4, round_blocks=2)
    for r in (r_k, r_1):
        assert not r.stopped_early, r.rounds
        assert r.exact.all()
        # center = catalog midpoint 50 => dsum is exactly 0 on the full
        # scan, so the mean is bitwise 50.0 on both paths
        np.testing.assert_array_equal(r.estimate, np.float64(50.0))
    assert r_k.rounds == r_1.rounds == nb // 2
    np.testing.assert_array_equal(r_k.count_seen, r_1.count_seen)


def scenario_cadence_exhaustion():
    """Full-scan cadence run on general data: every scan metric exact vs
    the merge_every=1 oracle, CI endpoints within the f32-reorder class
    (the cadence pools fold deltas in a different association order)."""
    sc = flights_scramble()
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9)  # never fires
    r_k, r_1 = run_cadence_pair(sc, q, merge_every=4)
    assert_sharded_matches_oracle(r_k, r_1)
    assert r_k.exact.all()


def scenario_cadence_early_stop():
    """Early stop under cadence: termination waits for a merge round, so
    the cadence path may scan extra rounds but never fewer, and the
    final (fully merged) answer matches the oracle's."""
    sc = flights_scramble()
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=TopKSeparated(k=2, largest=True), delta=1e-9)
    r_k, r_1 = run_cadence_pair(sc, q, merge_every=4)
    assert r_k.rounds >= r_1.rounds, (r_k.rounds, r_1.rounds)
    assert r_k.stopped_early == r_1.stopped_early
    np.testing.assert_array_equal(r_k.group_codes, r_1.group_codes)
    fin = np.isfinite(r_1.estimate)
    np.testing.assert_allclose(r_k.estimate[fin], r_1.estimate[fin],
                               rtol=CI_RTOL, atol=CI_ATOL)


def scenario_cadence_server_pass():
    """FrameServer batch through the cadence pass loop (replicated
    pend_rounds counter, per-slot pending folds, flush before the
    dispatch returns). Exhaustion queries keep every slot's cursor
    schedule identical to the merge_every=1 oracle."""
    sc = flights_scramble()
    queries = [
        AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9),
        AggQuery(agg="sum", column="dep_delay",
                 filters=(Filter("airline", "eq", 2),),
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9),
        AggQuery(agg="count", group_by="airline",
                 stop=AbsoluteWidth(eps=1e-9), delta=1e-9),
        AggQuery(agg="avg", column="dep_delay", bounder="anderson_dkw",
                 rangetrim=False, stop=AbsoluteWidth(eps=1e-9),
                 delta=1e-9),
    ]
    res = []
    for k in (4, 1):
        res.append(FrameServer(FastFrame(sc, EngineConfig(
            shard_rows=True, merge_every=k, **CFG))).run_batch(
            queries, start_block=0, seed=1))
    for r_k, r_1 in zip(*res):
        assert_sharded_matches_oracle(r_k, r_1)


def scenario_cadence_slot_queries_finish_apart():
    """One slot without the probe holding two ungrouped queries that stop
    on different rounds, through the cadence pass loop (merge_every 2):
    the slot scans on after the first query stops. Each query's answer
    is bitwise its solo run at the same cadence: the slot selects on its
    static verdicts whoever is in it, and a query answers from the merge
    that confirmed its stop."""
    sc = flights_scramble()
    queries = [
        AggQuery(agg="avg", column="dep_delay",
                 stop=AbsoluteWidth(eps=30.0), delta=1e-9),
        AggQuery(agg="avg", column="dep_delay",
                 stop=AbsoluteWidth(eps=8.0), delta=1e-9),
    ]

    def frame():
        return FastFrame(sc, EngineConfig(shard_rows=True, merge_every=2,
                                          **CFG))

    served = FrameServer(frame()).run_batch(queries, start_block=0, seed=1)
    for q, r in zip(queries, served):
        assert_sharded_matches_oracle(
            r, frame().run(q, start_block=0, seed=1), bitwise_ci=True)
    assert all(r.stopped_early for r in served)
    assert served[0].rounds < served[1].rounds


ALL = [
    scenario_groupby_topk,
    scenario_groupby_threshold_2d_mesh,
    scenario_filtered_sum,
    scenario_taint,
    scenario_exhaustion_bitwise,
    scenario_early_stop_bitwise,
    scenario_uneven_tail,
    scenario_last_block_start,
    scenario_server_pass,
    scenario_carousel_sharded_lap,
    scenario_cadence_superset_sync,
    scenario_cadence_merge_confirm,
    scenario_cadence_exhaustion,
    scenario_cadence_early_stop,
    scenario_cadence_server_pass,
    scenario_cadence_slot_queries_finish_apart,
]
