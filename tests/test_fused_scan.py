"""Fused-scan equivalence suite: the fused superkernel path must produce
BITWISE-identical query results to the per-block reference path
(``EngineConfig(fused=False)``) — estimates, intervals, soundness
bookkeeping (tainted / exact) and scan metrics — across randomized query
shapes, including activity-skipped (tainted) and exhausted (exact) views.
"""

from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.aqp import (AggQuery, EngineConfig, Expression, FastFrame,
                       Filter, build_scramble)
from repro.core.optstop import (AbsoluteWidth, GroupsOrdered, ThresholdSide,
                                TopKSeparated)
from repro.data import flights

RESULT_FIELDS = [
    "group_codes", "estimate", "lo", "hi", "count_seen", "nonempty",
    "exact", "tainted", "rows_covered", "blocks_fetched",
    "blocks_skipped_active", "blocks_skipped_static", "bitmap_probes",
    "rounds", "stopped_early",
]


def assert_bitwise_equal(r_fused, r_ref):
    for f in RESULT_FIELDS:
        a, b = getattr(r_fused, f), getattr(r_ref, f)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            assert a == b, (f, a, b)


def run_both(sc, q, sampling, seed=1, start=0, **cfg_kw):
    r_f = FastFrame(sc, EngineConfig(fused=True, **cfg_kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    r_r = FastFrame(sc, EngineConfig(fused=False, **cfg_kw)).run(
        q, sampling=sampling, seed=seed, start_block=start)
    return r_f, r_r


@pytest.fixture(scope="module")
def sc():
    ds = flights.generate(n_rows=100_000, n_airports=80, n_airlines=6,
                          seed=3)
    return build_scramble(ds.columns, catalog=ds.catalog, block_rows=256,
                          seed=4)


SCENARIOS = [
    ("avg-group-topk-peek",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=TopKSeparated(k=2, largest=True), delta=1e-9),
     "active_peek"),
    ("avg-group-thresh-sync",
     AggQuery(agg="avg", column="dep_delay", group_by="origin",
              stop=ThresholdSide(threshold=0.0), delta=1e-9),
     "active_sync"),
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
def test_fused_bitwise_equals_reference(sc, name, q, sampling):
    r_f, r_r = run_both(sc, q, sampling, seed=1, start=0,
                        round_blocks=16, lookahead_blocks=64,
                        sync_lookahead_blocks=16, hist_bins=256)
    assert_bitwise_equal(r_f, r_r)
    if name == "avg-exhaust-peek":
        assert r_f.exact.all()  # exhaustion collapsed every view


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fused_bitwise_randomized_starts(sc, seed):
    """Random scan starts (wrap-around windows) and seeds."""
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 filters=(Filter("dep_time", "gt", 400.0),),
                 stop=ThresholdSide(threshold=10.0), delta=1e-9)
    r_f, r_r = run_both(sc, q, "active_peek", seed=seed, start=None,
                        round_blocks=8, lookahead_blocks=64)
    assert_bitwise_equal(r_f, r_r)


@pytest.mark.parametrize("sampling", ["active_peek", "active_sync"])
def test_fused_bitwise_with_tainted_views(sampling):
    """Activity skips must taint (and freeze) identically on both paths:
    a dominant group resolves instantly, so blocks without the rare
    straddling group get skipped and the dominant group loses its clean
    prefix; the recovery pass then finishes it exactly."""
    rng = np.random.default_rng(0)
    n = 40_000
    g = (rng.random(n) < 0.02).astype(np.int32)  # rare group 1
    v = np.where(g == 1, rng.normal(50.0, 30.0, n),
                 rng.normal(100.0, 1.0, n)).astype(np.float32)
    sc = build_scramble({"g": g, "v": v}, catalog={"v": (-100.0, 250.0)},
                        block_rows=64, seed=1)
    q = AggQuery(agg="avg", column="v", group_by="g",
                 stop=ThresholdSide(threshold=50.0), delta=1e-6)
    r_f, r_r = run_both(sc, q, sampling, seed=1, start=0,
                        round_blocks=8, lookahead_blocks=64,
                        sync_lookahead_blocks=16)
    assert_bitwise_equal(r_f, r_r)
    assert r_f.blocks_skipped_active > 0   # scenario exercised skipping
    assert r_f.tainted[0] and not r_f.tainted[1]
    # the skipped-prefix view still carries a valid interval
    truth0 = v[g == 0].astype(np.float64).mean()
    assert r_f.lo[0] - 1e-3 <= truth0 <= r_f.hi[0] + 1e-3


def test_fused_exact_mode_unaffected():
    """sampling='exact' (and stop=None) bypasses the fused path; results
    must be identical regardless of the flag."""
    ds = flights.generate(n_rows=30_000, n_airports=16, n_airlines=4,
                          seed=9)
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=256,
                        seed=10)
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 stop=None)
    r_f, r_r = run_both(sc, q, "exact", seed=0, start=0)
    assert_bitwise_equal(r_f, r_r)
    assert r_f.exact.all()


# -- kernel level: the fused fold superkernel vs the oracles ------------------


def test_fused_fold_matches_oracles():
    """fused_fold (interpret) == grouped_moments + grouped_hist oracles."""
    from repro.kernels import fused_scan, ops

    rng = np.random.default_rng(0)
    n, g, k = 4096, 120, 256
    v = jnp.asarray(rng.normal(50.0, 10.0, n).astype(np.float32))
    gid = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    m = jnp.asarray((rng.random(n) < 0.8).astype(np.float32))
    a, b = 0.0, 100.0

    gpad, kpad = 128, 256
    sums, vmin, vmax, hist = fused_scan.fused_fold(
        v, gid, m, jnp.float32(50.0), a=a, b=b, num_groups=gpad,
        nbins=kpad, interpret=True)
    state = ops.moments_from_sums(sums[:, :g], vmin[:, :g], vmax[:, :g],
                                  50.0)
    want = ops.grouped_moments(v, gid, m, g, 50.0, impl="ref")
    for got_f, want_f, tol in zip(state, want, [1e-6, 1e-4, 5e-2, 1e-6,
                                                1e-6]):
        np.testing.assert_allclose(np.asarray(got_f), np.asarray(want_f),
                                   rtol=tol, atol=tol)
    want_h = ops.grouped_hist(v, gid, m, g, a, b, nbins=k, impl="ref")
    np.testing.assert_allclose(np.asarray(hist[:g, :k]),
                               np.asarray(want_h.hist))


@pytest.mark.parametrize("n_flagged", ["none", "one", "budget-1", "budget",
                                       "all"])
@pytest.mark.parametrize("budget", [1, 64])
@pytest.mark.parametrize("window", [64, 1024, 4096])
def test_gather_compaction_equals_nonzero(window, budget, n_flagged):
    """The round's compaction of the budgeted take into padded window
    positions is ``jnp.nonzero(take, size=budget, fill_value=window)``
    exactly, including a window whose flags all set (take cut at the
    budget) and one with none."""
    from repro.kernels import fused_scan

    n = {"none": 0, "one": 1, "budget-1": budget - 1, "budget": budget,
         "all": window}[n_flagged]
    rng = np.random.default_rng(window + budget + n)
    flags = np.zeros(window, bool)
    flags[rng.choice(window, size=n, replace=False)] = True
    win = jnp.asarray(rng.permutation(window).astype(np.int32))
    take, csum, _ = fused_scan._budget_select(
        jnp.asarray(flags), jnp.int32(0), window, window, budget)
    blk, tvalid, take_idx = fused_scan._gather_blocks(csum, win, window,
                                                      budget)
    want = np.asarray(jnp.nonzero(take, size=budget, fill_value=window)[0])
    assert take_idx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(take_idx), want)
    np.testing.assert_array_equal(np.asarray(tvalid), want < window)
    np.testing.assert_array_equal(
        np.asarray(blk),
        np.where(want < window, np.asarray(win)[np.minimum(want, window - 1)],
                 0))


# -- the round's window as slices ---------------------------------------------


class _Acct(NamedTuple):
    """The fields of a query or slot carry that ``_account`` reads."""

    pos: jnp.ndarray
    tainted: jnp.ndarray
    seen_presence: jnp.ndarray
    exact: jnp.ndarray
    processed: jnp.ndarray
    blocks_fetched: jnp.ndarray
    skipped_static: jnp.ndarray
    skipped_active: jnp.ndarray
    probes: jnp.ndarray


def _gather_round(order_pad, static_ok, words, presence, presence_total, c,
                  aw, *, nb, window, budget, lookahead, cover_cap, probe,
                  lim, wrap):
    """The gather/scatter form of ``_round_scan`` + ``_account``: every
    window read indexes the block-id buffers with the window's block ids,
    and ``processed`` is updated by a scatter."""
    from repro.kernels import fused_scan
    from repro.kernels import ops

    i32, i64 = jnp.int32, jnp.int64
    offs = jnp.arange(window, dtype=i32)
    in_range = (c.pos + offs) < lim
    start = jax.lax.rem(c.pos, i32(nb)) if wrap else c.pos
    win = jax.lax.dynamic_slice(order_pad, (start,), (window,))
    ok = static_ok[win] & in_range
    flags = ok
    if probe:
        flags = ok & (ops.active_blocks(words[win], aw, impl="ref") > 0)
    take, _, new_pos = fused_scan._budget_select(flags, c.pos, lim, window,
                                                 budget)
    covmask = offs < (new_pos - c.pos)
    act_skip = ok & covmask & ~(flags & covmask)
    pres_win = presence[win]
    tainted = c.tainted | (pres_win & act_skip[:, None]).any(axis=0)
    seen = c.seen_presence + (pres_win & take[:, None]).sum(axis=0,
                                                            dtype=i32)
    cov = (seen >= presence_total) | ((new_pos >= lim) & ~tainted)
    probes = c.probes
    if probe:
        win_len = jnp.minimum(i32(window), i32(lim) - c.pos)
        csum = jnp.cumsum(flags.astype(i32))
        csum_excl = jnp.concatenate([jnp.zeros(1, i32), csum[:-1]])
        starts = jnp.arange(-(-window // lookahead), dtype=i32) * lookahead
        probed = ((csum_excl[starts] < budget) & (starts < win_len)
                  & (starts < cover_cap))
        ends = jnp.minimum(starts + lookahead, win_len)
        probes = probes + jnp.where(probed, ends - starts, 0).sum().astype(
            i64)
    return c._replace(
        pos=new_pos, tainted=tainted, seen_presence=seen,
        exact=c.exact | cov, processed=c.processed.at[win].max(take),
        blocks_fetched=c.blocks_fetched + take.sum(dtype=i64),
        skipped_static=c.skipped_static + (~ok & covmask).sum(dtype=i64),
        skipped_active=c.skipped_active + act_skip.sum(dtype=i64),
        probes=probes)


def _slice_round(bufs, presence, presence_total, c, aw, *, nb, window,
                 budget, lookahead, cover_cap, probe, lim, wrap):
    """``_round_scan`` + ``_account`` as the loops call them, on padded
    buffers, with the loops' activity test."""
    from repro.kernels import fused_scan
    from repro.kernels import ops

    def flags_src(ok, b0):
        if not probe:
            return ok
        rows = fused_scan._window_rows(bufs.words, b0, window)
        return ok & (ops.active_blocks(rows, aw, impl="ref") > 0)

    scan = fused_scan._round_scan(
        bufs, c.pos, flags_src, nb=nb, window=window, budget=budget,
        bound=None if lim == nb else lim, wrap=wrap)
    acct = fused_scan._account(
        c, presence, presence_total, scan, lim=lim, probe=probe,
        window=window, budget=budget, lookahead=lookahead,
        cover_cap=cover_cap)
    return c._replace(pos=scan[5], **acct)


# (nb, window, start, pos, anchor): anchor None is the query loop (cursor
# limit nb), else a pass-loop slot whose lap is [anchor, anchor + nb)
_WINDOWS = {
    "start-0": (300, 256, 0, 0, None),
    "start-last": (300, 256, 299, 0, None),
    "crosses-last-block": (300, 256, 200, 0, None),
    "last-partial-window": (300, 256, 37, 200, None),
    "window-past-nb": (40, 128, 13, 0, None),
    "slot-lap-past-nb": (300, 256, 37, 250, 170),
}


@pytest.mark.parametrize("G", [1, 14, 1600])
@pytest.mark.parametrize("probe", [False, True])
@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_window_slices_equal_gathers(x64, case, probe, G):
    """Rounds read through ``dynamic_slice`` at the window's first block,
    on the padded block-id buffers, and ``processed`` kept padded and
    folded back to ``(nb,)``, give bitwise what the gather/scatter form
    gives: every carry field, round after round, until the cursor limit.
    The cases cover a rotation that starts at block 0, at the last block,
    one whose windows cross block nb - 1 to 0, the last partial window, a
    window longer than the scramble, and a served slot whose lap runs
    past nb."""
    from repro.kernels import fused_scan

    nb, window, start, pos, anchor = _WINDOWS[case]
    wrap = anchor is not None
    lim = nb if anchor is None else anchor + nb
    budget, lookahead, cover_cap = 16, 64, window
    rng = np.random.default_rng([nb, start, pos, G, probe])
    n_words = -(-G // 32)
    static_ok = rng.random(nb) < 0.8
    words = rng.integers(0, 2 ** 32, (nb, n_words), dtype=np.uint32)
    presence = rng.random((nb, G)) < min(0.5, 4.0 / G + 0.05)
    presence_total = jnp.asarray(presence.sum(axis=0, dtype=np.int32))
    active = rng.random(G) < 0.3
    active[0] = True
    aw = fused_scan.pack_active_device(jnp.asarray(active), n_words)
    order_pad = fused_scan.rotation_order_pad(start, nb, window)
    np.testing.assert_array_equal(order_pad[:nb],
                                  (start + np.arange(nb)) % nb)
    bufs = fused_scan.PassLoopBuffers(
        mask=None, order_pad=jnp.asarray(order_pad),
        static_ok=jnp.asarray(fused_scan.pad_blocks(static_ok, window)),
        cum_rows=None, values=None, gids=None,
        words=jnp.asarray(fused_scan.pad_blocks(words, window)),
        presence=None, presence_total=None)
    presence_pad = jnp.asarray(fused_scan.pad_blocks(presence, window))
    i64 = lambda v: jnp.asarray(v, jnp.int64)
    c_ref = _Acct(
        pos=jnp.asarray(pos, jnp.int32),
        tainted=jnp.asarray(rng.random(G) < 0.1),
        seen_presence=jnp.zeros(G, jnp.int32),
        exact=jnp.asarray(rng.random(G) < 0.1),
        processed=jnp.asarray(rng.random(nb) < 0.05),
        blocks_fetched=i64(0), skipped_static=i64(0), skipped_active=i64(0),
        probes=i64(0))
    c = c_ref._replace(
        processed=fused_scan._pad_processed(c_ref.processed, window))
    kw = dict(nb=nb, window=window, budget=budget, lookahead=lookahead,
              cover_cap=cover_cap, probe=probe, lim=lim, wrap=wrap)
    rounds = 0
    while int(c_ref.pos) < lim:
        c_ref = _gather_round(jnp.asarray(order_pad),
                              jnp.asarray(static_ok), jnp.asarray(words),
                              jnp.asarray(presence), presence_total, c_ref,
                              aw, **kw)
        c = _slice_round(bufs, presence_pad, presence_total, c, aw, **kw)
        got = c._replace(
            processed=fused_scan._fold_processed(c.processed, nb))
        for f in _Acct._fields:
            a, b = np.asarray(getattr(got, f)), np.asarray(getattr(c_ref, f))
            assert a.dtype == b.dtype and a.shape == b.shape, f
            np.testing.assert_array_equal(a, b, err_msg=f"{f}, round "
                                          f"{rounds}")
        rounds += 1
    assert int(c_ref.blocks_fetched) > 0


def test_fused_round_interpret_engine_close_to_ref():
    """The engine driven through the fused superkernel (interpret) agrees
    with the ref backend within f32 tile-order tolerance."""
    ds = flights.generate(n_rows=20_000, n_airports=12, n_airlines=4,
                          seed=5)
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=256,
                        seed=6)
    q = AggQuery(agg="avg", column="dep_delay", group_by="airline",
                 bounder="anderson_dkw", rangetrim=False,
                 stop=AbsoluteWidth(eps=25.0), delta=1e-6)
    r_int = FastFrame(sc, EngineConfig(fused=True, impl="interpret",
                                       round_blocks=8,
                                       lookahead_blocks=32,
                                       hist_bins=256)).run(
        q, sampling="scan", seed=2, start_block=0)
    r_ref = FastFrame(sc, EngineConfig(fused=True, impl="ref",
                                       round_blocks=8,
                                       lookahead_blocks=32,
                                       hist_bins=256)).run(
        q, sampling="scan", seed=2, start_block=0)
    np.testing.assert_allclose(r_int.estimate, r_ref.estimate,
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(r_int.lo, r_ref.lo, rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(r_int.hi, r_ref.hi, rtol=1e-3, atol=1e-2)


# -- device-resident loop: dispatch-boundary semantics ------------------------
# (the deep equivalence suite is tests/test_device_loop.py; these pin the
# loop-boundary invariants of the lax.while_loop chunking specifically;
# the x64 fixture lives in tests/conftest.py)


def _run_device(sc, q, **cfg_kw):
    return FastFrame(sc, EngineConfig(device_loop=True, round_blocks=16,
                                      lookahead_blocks=64,
                                      **cfg_kw)).run(
        q, sampling="active_peek", seed=1, start_block=0)


def test_device_chunking_is_result_invariant(sc, x64):
    """``sync_every`` / ``chunk_rounds`` change dispatch granularity
    only: any chunk size must produce results identical to the unchunked
    single-dispatch loop — including when the chunk boundary lands
    exactly on, just before and just after the stopping round."""
    q = AggQuery(agg="count", filters=(Filter("origin", "eq", 3),),
                 stop=AbsoluteWidth(eps=5e3), delta=1e-9)
    base = _run_device(sc, q)
    assert base.stopped_early  # the boundary cases below are meaningful
    for cfg_kw in (dict(sync_every=1), dict(sync_every=3),
                   dict(sync_every=base.rounds),
                   dict(sync_every=base.rounds - 1),
                   dict(sync_every=base.rounds + 1),
                   dict(chunk_rounds=2),
                   dict(sync_every=2, chunk_rounds=1000)):
        got = _run_device(sc, q, **cfg_kw)
        assert_bitwise_equal(got, base)


def test_device_early_stop_inside_chunk_no_overscan(sc, x64):
    """A stop firing mid-chunk must end the while_loop immediately: the
    coverage accounting (rows_covered / blocks_fetched / rounds) must
    equal the host loop's, which checks the stop test every round —
    a chunk far larger than the stopping round must not over-scan."""
    q = AggQuery(agg="count", filters=(Filter("origin", "eq", 3),),
                 stop=AbsoluteWidth(eps=5e3), delta=1e-9)
    r_host = FastFrame(sc, EngineConfig(device_loop=False,
                                        round_blocks=16,
                                        lookahead_blocks=64)).run(
        q, sampling="active_peek", seed=1, start_block=0)
    r_dev = _run_device(sc, q, sync_every=10_000)
    assert r_dev.stopped_early and r_host.stopped_early
    assert r_dev.rounds == r_host.rounds
    assert r_dev.rows_covered == r_host.rows_covered
    assert r_dev.blocks_fetched == r_host.blocks_fetched
    assert r_dev.bitmap_probes == r_host.bitmap_probes


# -- retrace budgets (dynamic half of the aqplint AQP5xx pass) -----------------
#
# The static-shape padding (PR 3) makes every steady-state re-dispatch
# hit the jit cache; a shape signature varying per call would keep the
# results bitwise identical while recompiling every round, which no
# value-comparing test can see. Budgets live in
# tools/aqplint/retrace_budgets.json and are exact ceilings.

def test_fused_rerun_stays_within_retrace_budget(sc):
    from aqplint.retrace import assert_within_budget, count_compiles
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=AbsoluteWidth(eps=8.0), delta=0.05)
    frame = FastFrame(sc, EngineConfig(fused=True))
    frame.run(q, sampling="sample", seed=1)          # warm-up
    with count_compiles() as counter:
        frame.run(q, sampling="sample", seed=2)      # same shapes
    assert_within_budget("fused_scan::rerun_same_shapes", counter)


def test_fresh_frame_same_scramble_hits_jit_cache(sc):
    from aqplint.retrace import assert_within_budget, count_compiles
    q = AggQuery(agg="avg", column="dep_delay", group_by="origin",
                 stop=AbsoluteWidth(eps=8.0), delta=0.05)
    FastFrame(sc, EngineConfig(fused=True)).run(q, sampling="sample",
                                                seed=1)
    with count_compiles() as counter:
        FastFrame(sc, EngineConfig(fused=True)).run(q, sampling="sample",
                                                    seed=1)
    assert_within_budget("fused_scan::fresh_frame_same_scramble", counter)
