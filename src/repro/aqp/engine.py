"""FastFrame query engine: OptStop rounds + active scanning over a scramble.

Per round (Algorithm 5 at block granularity, §4.2/§4.3):
  1. advance the scan cursor through the shuffled block order, using the
     static predicate bitmap and the (group-bitmap AND active-mask) lookahead
     kernel to *skip* blocks that cannot help any active view;
  2. fold the selected blocks into the per-group mergeable moment states
     (+ the DKW histogram when the Anderson/DKW bounder is in play);
  3. re-evaluate per-view CIs at delta_k = (6/pi^2) delta_view / k^2 with the
     Theorem-3 ``N+`` upper bound standing in for the unknown view size;
  4. intersect with the running interval, update the active mask from the
     query's stopping condition, and stop when no view is active.

Steps 1–2 have two implementations sharing the same semantics (bitwise
identical on the shared fold backends — see ``EngineConfig.fused``):

  * **fused** (default, ``EngineConfig.fused=True``): the query's value
    column, predicate mask and group codes are materialized once and kept
    device-resident; each round is ONE dispatch of the
    :func:`repro.kernels.fused_scan.fused_round` superkernel (activity
    test -> budgeted selection -> gather -> moment/histogram fold), and
    the host syncs once per round to merge the emitted
    ``StatsBatch``-compatible deltas and run the soundness bookkeeping;
  * **per-block reference** (``fused=False``): the original path — a
    Python cursor loop issuing separate bitmap-probe and fold dispatches
    per lookahead batch with host materialization in between. It is kept
    as the oracle the fused path is tested bitwise against
    (``tests/test_fused_scan.py``) and as the baseline for
    ``benchmarks/bench_fused_scan.py``. Probe batches and fold inputs are
    padded to static shapes so the tail of the scramble does not retrace
    the XLA computations (padding rows carry ``mask == 0`` and contribute
    exact zeros).

The per-query execution state is split into two composable pieces so
:class:`repro.serve.FrameServer` can serve many concurrent queries off
one shared scan:

  * :class:`_ScanViews` — everything determined by the *scan signature*
    ``(filters, column, group-by)`` alone: device materialization,
    per-view fold states, coverage, and taint bookkeeping. Several
    queries (different stopping conditions / bounders / deltas) can share
    one instance.
  * :class:`_QueryIntervals` — one query's OptStop state: running
    intervals, delta schedule, CI refresh and the active mask from its
    stopping condition.

Soundness bookkeeping beyond the paper's prose:
  * ``tainted`` views: a view that occurred in an *activity-skipped* block
    no longer sees a clean scan prefix, so its CI is frozen at the last
    clean value (always valid — Theorem 4's intersection is anytime). Only
    inactive views can be tainted (a block is skipped iff it contains no
    active view), so the freeze coincides with the deactivation freeze.
  * ``exact`` views: once every block containing a view has been processed
    the aggregate is exact regardless of sampling history; the interval
    collapses to a point. This also guarantees termination for any
    stopping condition.
  * The Exact baseline intentionally performs a full sequential sweep with
    no bitmap skipping (the paper's strawman).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.aqp import distributed as adist
from repro.aqp.bitmap import (BlockBitmap, build_bitmap, pack_mask,
                              unpack_words)
from repro.aqp.query import AggQuery, Expression, QueryResult
from repro.aqp.scramble import Scramble
from repro.core import count_sum
from repro.core.lru import LRUCache
from repro.core.bounders import get_bounder
from repro.core.optstop import delta_schedule, delta_schedule_device
from repro.core.state import (DevStatsBatch, MomentState, StatsBatch,
                              init_moments_host, merge_hist_host,
                              merge_moments_host, require_x64, to_host,
                              x64_enabled)
from repro.kernels import fused_scan as kfused
from repro.kernels import ops as kops

_ALPHA = count_sum.ALPHA_DEFAULT
_INT32_MAX = np.iinfo(np.int32).max


def _batched_view_ci(q: AggQuery, sb: StatsBatch, a, b, r, R, dk,
                     known_n, bounder, alpha):
    """One round's CI refresh for a batch of views (module-level so tests
    can swap in a scalar-loop oracle). Returns ``(lo, hi, est)`` arrays of
    the batch length. ``r`` is the scalar clean-prefix row count; N+ and
    all bounder math are evaluated elementwise over the batch."""
    if q.agg == "count":
        clo, chi = count_sum.count_ci(sb.count, r, R, dk)
        return clo, chi, sb.count / max(r, 1) * R
    if known_n:
        alo, ahi = bounder.interval_batch(sb, a, b, R, dk)
    else:
        budget = dk if q.agg == "avg" else dk / 2.0
        npl = count_sum.n_plus(sb.count, r, R, (1 - alpha) * budget)
        alo, ahi = bounder.interval_batch(sb, a, b, npl, alpha * budget)
    if q.agg == "avg":
        return alo, ahi, sb.mean.copy()
    # SUM = COUNT x AVG (paper §4.1)
    cci = count_sum.count_ci(sb.count, r, R, dk / 2.0)
    slo, shi = count_sum.sum_ci(cci, (alo, ahi))
    return slo, shi, sb.mean * (sb.count / max(r, 1)) * R


def _view_ci_device(q: AggQuery, sb: DevStatsBatch, a, b, r, R, dk,
                    known_n, bounder, alpha):
    """Jittable twin of :func:`_batched_view_ci`: the same CI refresh in
    device float64, with ``r`` (clean-prefix rows) and ``dk`` (the round's
    delta) as traced scalars — the per-round bound evaluation of the
    device-resident loop."""
    if q.agg == "count":
        clo, chi = count_sum.count_ci_device(sb.count, r, R, dk)
        return clo, chi, sb.count / jnp.maximum(r, 1.0) * R
    if known_n:
        alo, ahi = bounder.interval_batch_device(sb, a, b, R, dk)
    else:
        budget = dk if q.agg == "avg" else dk / 2.0
        npl = count_sum.n_plus_device(sb.count, r, R,
                                      (1 - alpha) * budget)
        alo, ahi = bounder.interval_batch_device(sb, a, b, npl,
                                                alpha * budget)
    if q.agg == "avg":
        return alo, ahi, sb.mean
    # SUM = COUNT x AVG (paper §4.1)
    cci = count_sum.count_ci_device(sb.count, r, R, dk / 2.0)
    slo, shi = count_sum.sum_ci_device(cci, (alo, ahi))
    return slo, shi, sb.mean * (sb.count / jnp.maximum(r, 1.0)) * R


def _exact_estimate(q: AggQuery, counts, means, R):
    """Vectorized point estimate over fully-covered views (elementwise —
    works for both numpy and traced jnp inputs)."""
    if q.agg == "avg":
        return means
    if q.agg == "count":
        return counts
    return means * counts  # sum


def _round_window(nb: int, lookahead: int, cover_cap: int) -> int:
    """Maximum cursor coverage per fused round: the reference path
    accumulates whole lookahead batches until the cover cap (then clamps
    to ``nb``)."""
    window = lookahead * (-(-cover_cap // lookahead))
    return min(window, lookahead * (-(-nb // lookahead)))


@dataclasses.dataclass
class EngineConfig:
    """Engine tuning knobs (defaults follow the paper's §4.3 settings).

    Attributes:
        round_blocks: processed-block budget per OptStop round — the number
            of blocks folded into the states between two CI refreshes.
        lookahead_blocks: ActivePeek bitmap-probe batch (paper §4.3).
        sync_lookahead_blocks: ActiveSync probe batch (the paper's
            cache-unfriendly synchronous variant).
        cover_cap_factor: cap on cursor positions covered per round, as a
            multiple of ``round_blocks`` (bounds per-round skip scanning).
        hist_bins: DKW histogram resolution (Anderson/DKW bounder only).
        alpha: COUNT/AVG delta split for unknown-``N`` SUM/AVG queries.
        impl: kernel backend — ``'pallas'`` (compiled, TPU),
            ``'interpret'`` (Pallas interpreter), ``'ref'`` (pure-jnp
            oracle) or ``None`` = auto (pallas on TPU, ref elsewhere).
        fused: drive scan rounds through the fused superkernel
            (:mod:`repro.kernels.fused_scan`, one dispatch + one host sync
            per round). ``False`` falls back to the per-block reference
            path. Results are bitwise identical either way on the shared
            fold backends (``impl='ref'``, the off-TPU default, and any
            backend when no histogram is required); the Anderson/DKW
            histogram fold under ``impl='pallas'|'interpret'`` uses the
            combined superkernel's smaller tiles, so it agrees only to
            f32 tile-order rounding.
        device_loop: keep the *whole* round loop device-resident — fold,
            float64 state merge, CI refresh (the ``*_device`` bounder
            twins) and stop test all run inside one ``lax.while_loop``
            dispatch (:func:`repro.kernels.fused_scan.build_pass_loop`,
            run as a pass of one slot holding one query), syncing to
            host only at termination or every
            ``sync_every`` rounds. Requires ``fused=True`` and 64-bit
            JAX types (:func:`repro.core.state.require_x64`; a clear
            error is raised otherwise — silent float32 demotion would
            invalidate the guarantees). ``None`` (default) auto-enables
            when x64 is on; ``False`` forces the per-round host loop
            (the tolerance oracle, same pattern as ``fused``). Scan
            decisions, fold counts, coverage, soundness flags and scan
            metrics match the host loop exactly; CI endpoints and
            estimates agree to <= 1e-9 (libm-vs-XLA transcendentals and
            FMA contraction differ in the final ulp).
        chunk_rounds: max OptStop rounds fused into one device-loop
            dispatch (``None`` = run until stop/exhaustion in a single
            dispatch). Chunking changes dispatch granularity only, never
            results.
        sync_every: host-sync (and ``on_sync`` result-streaming
            callback) cadence in rounds for the device loop; takes
            precedence over ``chunk_rounds`` as the dispatch size.
        mat_cache_entries: LRU capacity of EACH of the frame's three
            device materialization caches (value columns, predicate
            masks, group-code columns), keyed by the components of the
            ``(filters, column, group-by)`` scan signature. Every entry
            pins one full ``(n_blocks, block_rows)`` device buffer, so
            this bounds device memory of a long-lived server receiving
            ad-hoc filter values; eviction drops only the cache's pin —
            in-flight scans hold direct references and are never
            invalidated. Shared by ``FastFrame.run`` and
            :class:`repro.serve.FrameServer` (repeat signatures across
            batches reuse the same buffers). All four frame caches
            (materialization + compiled loops) are
            :class:`repro.core.lru.LRUCache` instances.
        shard_rows: run the device-resident round loop with the scan
            DIVIDED over a device mesh: the within-block row axis of the
            value/mask/group-code slabs is sliced into ``n_shards``
            equal pieces (block axis whole on every device, rows
            zero-padded to divide evenly), so each shard gathers and
            folds only ``1/n_shards`` of every selected block's rows;
            selection / accounting / bound eval stay replicated, and
            each round's fold delta merges across the mesh with one
            ``psum``/``pmin``/``pmax`` set inside the ``lax.while_loop``
            carry (no host sync; see :mod:`repro.aqp.distributed` and
            ``docs/architecture.md``). ``None`` (default) auto-enables
            when the device loop is in effect AND more than one device
            is visible — i.e. automatically off on a single device.
            ``True`` requires a >=2-device mesh and the device loop (a
            clear error otherwise). Equivalence vs the single-device
            loop (``tests/test_sharded_scan.py``): scan decisions,
            coverage, taint and scan metrics match exactly; fold deltas
            are bitwise-equal whenever the per-shard f32 partial sums
            are exactly representable (then CI endpoints match to the
            f64 last ulp, <= 1e-9); on general data the shard merge
            reorders the f32 row sum, so CI endpoints carry f32-reorder
            noise (~1e-6 relative — the same class of caveat as the
            fused histogram's tile-order rounding under ``fused``).
        mesh_shape: explicit device-mesh shape for ``shard_rows`` (e.g.
            ``(8,)`` or ``(2, 4)``; the within-block row axis is sharded
            over every axis, flattened). ``None`` uses all visible
            devices as a 1-D mesh.
        merge_every: collective cadence K of the sharded round loop:
            the cross-shard ``psum``/``pmin``/``pmax`` fold merge fires
            every K rounds on a deterministic replicated round counter —
            between merges there is zero cross-shard communication of
            any kind. Termination is merge-then-confirm (it always
            reads fully-merged stats): never earlier than the K=1
            loop, but possibly more than K-1 rounds later, since the
            intervals are intersected only at merges and the K=1 loop
            may stop on a look the cadence skips. Between merges each shard accumulates its raw
            additive fold delta locally and the reported intervals stay
            frozen at their last merged values — stale by at most K
            rounds but still anytime-valid (the ``sync_every`` trick,
            one level down). 1 (default) is the per-round-merge path,
            bitwise-identical to not setting this at all; K > 1 only
            affects sharded loops (no-op when ``shard_rows`` resolves
            False). Host syncs (``sync_every`` dispatch boundaries,
            ``on_sync`` snapshots, termination) always flush pending
            deltas first, so they never observe stale stats. See
            ``docs/architecture.md`` ("Collective cadence").
    """

    round_blocks: int = 64          # processed-block budget per round
    lookahead_blocks: int = 1024    # ActivePeek batch (paper §4.3)
    sync_lookahead_blocks: int = 32 # ActiveSync batch (cache-unfriendly)
    cover_cap_factor: int = 64      # max covered positions per round
    hist_bins: int = 1024
    alpha: float = _ALPHA
    impl: Optional[str] = None      # kernel impl: pallas | interpret | ref
    fused: bool = True              # fused scan superkernel (vs per-block)
    device_loop: Optional[bool] = None  # lax.while_loop round loop
                                    # (None = auto: on iff x64 enabled)
    chunk_rounds: Optional[int] = None  # rounds per device-loop dispatch
    sync_every: Optional[int] = None    # host-sync / streaming cadence
    mat_cache_entries: int = 32     # LRU cap per device materialization
                                    # cache (each entry pins one full
                                    # (n_blocks, block_rows) buffer)
    shard_rows: Optional[bool] = None   # mesh-sharded device loop
                                    # (None = auto: on iff device loop
                                    # active and >1 device visible)
    mesh_shape: Optional[Tuple[int, ...]] = None  # explicit mesh shape
                                    # (None = all visible devices, 1-D)
    merge_every: int = 1            # collective cadence K of the sharded
                                    # loop (1 = merge folds every round)

    def __post_init__(self):
        if self.merge_every < 1:
            raise ValueError(
                f"EngineConfig(merge_every={self.merge_every}) must be "
                ">= 1 (1 merges the shard folds every round; K > 1 "
                "amortizes the collective set over K rounds)")

    def resolve_shard_rows(self) -> bool:
        """Whether the device-resident round loop runs sharded over a
        device mesh, with the guards applied for an explicit
        ``shard_rows=True`` (auto is off on a single device)."""
        n_dev = (math.prod(self.mesh_shape) if self.mesh_shape
                 else jax.device_count())
        if self.shard_rows is None:
            return n_dev > 1 and self.resolve_device_loop()
        if self.shard_rows:
            if n_dev < 2:
                raise ValueError(
                    "EngineConfig(shard_rows=True) needs a mesh of >= 2 "
                    f"devices, but the resolved mesh has {n_dev} (on CPU "
                    "hosts set XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=N before jax initializes, or pass "
                    "mesh_shape). Sharding on one device is pure "
                    "overhead, so it is never enabled implicitly.")
            if not self.resolve_device_loop():
                raise ValueError(
                    "EngineConfig(shard_rows=True) requires the device-"
                    "resident round loop (device_loop=True, which needs "
                    "fused=True and 64-bit JAX types): the sharded scan "
                    "is the fused lax.while_loop running under "
                    "shard_map.")
        return bool(self.shard_rows)

    def resolve_device_loop(self) -> bool:
        """Whether the device-resident round loop is in effect, with the
        x64 guard applied for an explicit ``device_loop=True``."""
        if self.device_loop is None:
            return self.fused and x64_enabled()
        if self.device_loop:
            if not self.fused:
                raise ValueError(
                    "EngineConfig(device_loop=True) requires fused=True: "
                    "the device-resident loop is built on the fused scan "
                    "superkernel")
            require_x64("EngineConfig(device_loop=True)")
        return bool(self.device_loop)


class _ScanViews:
    """State determined by one scan signature ``(filters, column,
    group-by)``: the aggregate views' fold / coverage / soundness
    bookkeeping, independent of any one query's stopping condition.

    One instance can back several concurrent queries
    (:class:`repro.serve.FrameServer`): the moment/histogram states,
    coverage, exactness and taint are functions of the scan alone, so
    queries that differ only in aggregate, bounder, delta or stopping
    condition share them.
    """

    def __init__(self, frame: "FastFrame", q: AggQuery,
                 use_hist: Optional[bool] = None, anchor: int = 0):
        self.frame = frame
        self.rep_q = q
        sc = frame.scramble
        # Carousel anchor: the pass cursor position where this slot
        # joined a shared walk. Its lap is [anchor, anchor + n_blocks) in
        # pass-cursor coordinates — one full rotation of the scan order,
        # so the skipped prefix is covered at the end of the lap. A solo
        # run is the anchor=0 case.
        self.anchor = anchor
        self.lap_end = anchor + sc.n_blocks
        self.gcol, self.G = (None, 1)
        if q.group_by is not None:
            self.gcol, self.G = frame._composite_group(q.group_cols)
        self.value_src, (self.a, self.b) = frame._values_and_bounds(q)
        self.center = 0.5 * (self.a + self.b)
        self.use_hist = use_hist if use_hist is not None else q.needs_hist
        self.static_ok, self.probes0 = frame._static_ok(q)
        self.group_bm = (frame.bitmap(self.gcol) if self.gcol is not None
                         else None)
        self.presence, self.presence_total = frame._group_presence(
            self.gcol, self.G)
        self.valid = self.presence_total > 0
        self.state = init_moments_host((self.G,))
        self.hist = (np.zeros((self.G, frame.config.hist_bins), np.float64)
                     if self.use_hist else None)
        self.seen_presence = np.zeros(self.G, dtype=np.int64)
        self.processed = np.zeros(sc.n_blocks, dtype=bool)
        self.exact = self.presence_total == 0   # group code never occurs
        self.tainted = np.zeros(self.G, dtype=bool)
        self.blocks_fetched = 0

    @property
    def counts(self) -> np.ndarray:
        return self.state.count

    def ingest_delta(self, idx: np.ndarray, upd, hupd) -> None:
        """Merge one fused round's device-side mergeable deltas for the
        selected blocks ``idx``."""
        self.processed[idx] = True
        self.blocks_fetched += len(idx)
        self.state = merge_moments_host(self.state, to_host(upd))
        if self.use_hist:
            self.hist = merge_hist_host(self.hist, hupd)
        self.seen_presence += self.presence[idx].sum(axis=0)

    def ingest_blocks(self, idx: np.ndarray,
                      pad_to: Optional[int] = None) -> None:
        """Host materialize-and-fold path (per-block reference, exact
        sweep and the recovery pass)."""
        self.processed[idx] = True
        self.blocks_fetched += len(idx)
        self.state, self.hist = self.frame._fold_blocks(
            self.rep_q, idx, self.value_src, self.gcol, self.G, self.center,
            self.a, self.b, self.state, self.hist, self.use_hist,
            pad_to=pad_to)
        self.seen_presence += self.presence[idx].sum(axis=0)

    def export_state(self) -> Dict[str, object]:
        """Deep-copy the mutable fold/coverage/soundness state (the scan
        signature's derived arrays — presence, static_ok, bounds — are
        pure functions of the frame and are NOT exported; a restored
        slot recomputes them). Consumed by
        :class:`repro.serve.checkpoint.PassCheckpoint`."""
        return dict(
            use_hist=self.use_hist, anchor=self.anchor,
            state=MomentState(*(np.array(x) for x in self.state)),
            hist=None if self.hist is None else np.array(self.hist),
            seen_presence=np.array(self.seen_presence),
            processed=np.array(self.processed),
            exact=np.array(self.exact),
            tainted=np.array(self.tainted),
            blocks_fetched=int(self.blocks_fetched))

    def import_state(self, snap: Dict[str, object]) -> None:
        """Overwrite the mutable state from an :meth:`export_state`
        snapshot (bitwise: the arrays are copied back verbatim, so a
        restored scan continues exactly where the snapshot was taken)."""
        if snap["use_hist"] != self.use_hist or \
                snap["anchor"] != self.anchor:
            raise ValueError("checkpoint does not match this slot's "
                             "scan configuration")
        self.state = MomentState(*(np.array(x) for x in snap["state"]))
        self.hist = (None if snap["hist"] is None
                     else np.array(snap["hist"]))
        self.seen_presence = np.array(snap["seen_presence"])
        self.processed = np.array(snap["processed"])
        self.exact = np.array(snap["exact"])
        self.tainted = np.array(snap["tainted"])
        self.blocks_fetched = int(snap["blocks_fetched"])

    def update_exact(self, pos: Optional[int] = None) -> None:
        """Mark fully-covered views exact; on lap exhaustion
        (``pos >= lap_end``, i.e. the cursor walked one full rotation
        from this slot's anchor) also untainted views — an untainted
        view's unprocessed blocks were all static-skipped (zero view
        rows), whereas a tainted view lost member rows to activity skips
        and must finish via the recovery pass (collapsing it early would
        overwrite a valid frozen CI with a biased point estimate)."""
        cov = self.seen_presence >= self.presence_total
        if pos is not None and pos >= self.lap_end:
            cov = cov | ~self.tainted
        self.exact |= cov


class _QueryIntervals:
    """One query's OptStop / interval state over a :class:`_ScanViews`
    slot: running intervals, delta schedule, batched CI refresh and the
    active mask from the query's stopping condition."""

    def __init__(self, frame: "FastFrame", q: AggQuery, slot: _ScanViews):
        self.q = q
        self.slot = slot
        self.cfg = frame.config
        self.R = frame.scramble.n_rows
        self.bounder = (get_bounder(q.bounder, rangetrim=q.rangetrim)
                        if q.agg != "count" else None)
        self.use_hist = q.needs_hist
        # The per-view delta budget is split over views that can ever emit
        # an interval (presence_total > 0, known a priori from the group
        # bitmap). Phantom composite codes never refresh (their counts
        # stay 0), so excluding them keeps the union bound sound while
        # tightening every real view's CI for free.
        self.delta_view = q.delta / max(int(slot.valid.sum()), 1)
        self.known_n = (not q.filters) and (q.group_by is None)
        G = slot.G
        # trivial a-priori bounds (valid before any sample is seen)
        if q.agg == "avg":
            lo0, hi0 = slot.a, slot.b
        elif q.agg == "count":
            lo0, hi0 = 0.0, float(self.R)
        else:  # sum
            lo0 = min(0.0, self.R * slot.a)
            hi0 = max(0.0, self.R * slot.b)
        self.lo = np.full(G, lo0)
        self.hi = np.full(G, hi0)
        self.est = np.full(G, slot.center)
        self.refreshed = np.zeros(G, dtype=bool)
        self.active = slot.valid.copy()
        self.finished = False

    def export_state(self) -> Dict[str, object]:
        """Deep-copy the running interval state (the checkpoint twin of
        :meth:`_ScanViews.export_state` for per-query state)."""
        return dict(lo=np.array(self.lo), hi=np.array(self.hi),
                    est=np.array(self.est),
                    refreshed=np.array(self.refreshed),
                    active=np.array(self.active),
                    finished=bool(self.finished))

    def import_state(self, snap: Dict[str, object]) -> None:
        self.lo = np.array(snap["lo"])
        self.hi = np.array(snap["hi"])
        self.est = np.array(snap["est"])
        self.refreshed = np.array(snap["refreshed"])
        self.active = np.array(snap["active"])
        self.finished = bool(snap["finished"])

    def cond_active(self) -> np.ndarray:
        """Stopping-condition activity over EXISTING views only (phantom
        composite codes must not distort orderings)."""
        slot = self.slot
        out = np.zeros(slot.G, dtype=bool)
        v = slot.valid
        if v.any():
            out[v] = self.q.stop.active(self.lo[v], self.hi[v],
                                        self.est[v], slot.counts[v])
        return out

    def refresh(self, k: int, r: int) -> None:
        """Step 3: batched CI refresh at OptStop round ``k`` with ``r``
        clean-prefix rows, then collapse fully-covered views to their
        exact point (one batched call, no G-loop)."""
        slot = self.slot
        dk = delta_schedule(self.delta_view, k)
        counts = slot.counts
        refresh = ~slot.tainted & (counts > 0) & (self.active
                                                  | ~self.refreshed)
        gidx = np.nonzero(refresh)[0]
        if gidx.size:
            sb = StatsBatch.from_state(
                slot.state, slot.hist if self.use_hist else None).take(gidx)
            glo, ghi, gest = _batched_view_ci(
                self.q, sb, slot.a, slot.b, r, self.R, dk, self.known_n,
                self.bounder, self.cfg.alpha)
            self.lo[gidx] = np.maximum(self.lo[gidx], glo)
            self.hi[gidx] = np.minimum(self.hi[gidx], ghi)
            self.est[gidx] = gest
            self.refreshed[gidx] = True
        self.collapse_exact()

    def collapse_exact(self) -> None:
        """Full coverage -> point interval at the exact aggregate."""
        slot = self.slot
        counts = slot.counts
        full = slot.exact & (counts > 0)
        if full.any():
            ex = _exact_estimate(self.q, counts, slot.state.mean, self.R)
            self.lo[full] = self.hi[full] = self.est[full] = ex[full]

    def update_active(self) -> bool:
        """Step 4: recompute the active mask from the stopping condition;
        returns True while any view is still active."""
        self.active = self.cond_active() & ~self.slot.exact & self.slot.valid
        return bool(self.active.any())

    def result(self, rounds: int, pos: int, cum_rows: np.ndarray,
               metrics: Dict[str, int], t0: float,
               stopped_early: bool,
               rows_covered: Optional[int] = None) -> QueryResult:
        """Build the QueryResult from the CURRENT slot/query state (the
        arrays are copied — including ``count_seen``, which must not
        alias the slot's live fold state — so the result is a consistent
        snapshot even if a shared scan keeps mutating the slot afterwards
        — the serving layer calls this the moment a query finishes).
        ``rows_covered`` overrides the prefix-sum lookup for anchored
        slots whose lap does not start at cursor position 0."""
        slot = self.slot
        counts = slot.counts
        if rows_covered is None:
            rows_covered = int(cum_rows[pos - 1]) if pos else 0
        return QueryResult(
            group_codes=np.arange(slot.G), estimate=self.est.copy(),
            lo=self.lo.copy(), hi=self.hi.copy(),
            count_seen=counts.copy(),
            nonempty=counts > 0, exact=slot.exact.copy(),
            tainted=slot.tainted.copy(),
            rows_covered=rows_covered,
            blocks_fetched=slot.blocks_fetched,
            blocks_skipped_active=metrics["skipped_active"],
            blocks_skipped_static=metrics["skipped_static"],
            bitmap_probes=metrics["probes"], rounds=rounds,
            wall_time_s=time.perf_counter() - t0,
            stopped_early=stopped_early)


class _FusedScan:
    """Device-resident scan context for one query: assembles the cached
    value column, predicate mask, group codes and bitmap words, then
    drives :func:`repro.kernels.fused_scan.fused_round` — one device
    dispatch and one host sync per round.

    Materialization is identical (bitwise) to the per-block reference
    path's per-round ``_materialize``: predicates and value expressions
    are elementwise, so evaluating them over the full blocked columns and
    gathering on device yields the same rows the reference gathers on
    host. The device arrays come from :class:`FastFrame`'s materialization
    caches, so repeat queries (and :class:`repro.serve.FrameServer`
    slots) reuse the same buffers.
    """

    def __init__(self, frame: "FastFrame", q: AggQuery, value_src, gcol,
                 G: int, center: float, a: float, b: float, use_hist: bool,
                 probe: bool, lookahead: int, budget: int, cover_cap: int,
                 static_ok: np.ndarray, group_bm, order: np.ndarray):
        sc = frame.scramble
        nb = sc.n_blocks
        self.window = _round_window(nb, lookahead, cover_cap)
        self.budget = budget
        self.nb = nb
        self.probe = probe
        self.use_hist = use_hist
        self.center = float(center)
        self.a = float(a)
        self.b = float(b)
        self.G = G
        self.nbins = frame.config.hist_bins
        self.impl = kops.resolve_impl(frame.config.impl)

        self.values = frame._device_values(value_src)
        self.gids = frame._device_gids(gcol)
        self.mask = frame._device_mask(q.filters)
        self.words = (jnp.asarray(group_bm.words) if group_bm is not None
                      else None)
        opad = np.zeros(nb + self.window, np.int32)
        opad[:nb] = order
        self.order_pad = jnp.asarray(opad)
        self.static_ok = jnp.asarray(static_ok)

    def round(self, pos: int, active_words):
        """One fused round from cursor ``pos``. Returns host-side
        ``(moment_delta, hist_delta, ok, flags, new_pos)``."""
        state, hist, ok, flags, new_pos = kfused.fused_round(
            self.values, self.gids, self.mask, self.words, self.order_pad,
            self.static_ok, jnp.asarray(pos, jnp.int32), active_words,
            nb=self.nb, window=self.window, budget=self.budget,
            center=self.center, a=self.a, b=self.b, num_groups=self.G,
            nbins=self.nbins, use_hist=self.use_hist, probe=self.probe,
            impl=self.impl)
        return (state, hist, np.asarray(ok), np.asarray(flags),
                int(new_pos))


def _make_device_refresh(q: AggQuery, qci: "_QueryIntervals",
                         a: float, b: float, use_hist: bool, R: float,
                         valid: np.ndarray):
    """Build the jittable per-round CI-refresh + stop-test closure for
    one query — the device twin of ``_QueryIntervals.refresh`` +
    ``collapse_exact`` + ``update_active``, with the query's static
    configuration (bounder, delta schedule, stopping condition, valid
    mask) baked in. One of the ``refresh_fns`` of
    :func:`repro.kernels.fused_scan.build_pass_loop`."""
    bounder = qci.bounder
    delta_view = qci.delta_view
    known_n = qci.known_n
    alpha = qci.cfg.alpha
    stop = q.stop
    valid_dev = jnp.asarray(valid)

    def refresh_fn(k, r, state, hist, tainted, exact, lo, hi, est,
                   refreshed, active):
        counts = state.count  # f64 in the loop carry
        dk = delta_schedule_device(delta_view, k)
        refresh = ~tainted & (counts > 0) & (active | ~refreshed)
        sb = DevStatsBatch.from_state(state, hist if use_hist else None)
        glo, ghi, gest = _view_ci_device(q, sb, a, b, r, R, dk, known_n,
                                         bounder, alpha)
        lo = jnp.where(refresh, jnp.maximum(lo, glo), lo)
        hi = jnp.where(refresh, jnp.minimum(hi, ghi), hi)
        est = jnp.where(refresh, gest, est)
        refreshed = refreshed | refresh
        full = exact & (counts > 0)
        ex = _exact_estimate(q, counts, state.mean, R)
        lo = jnp.where(full, ex, lo)
        hi = jnp.where(full, ex, hi)
        est = jnp.where(full, ex, est)
        active = (stop.active_device(lo, hi, est, counts, valid_dev)
                  & ~exact & valid_dev)
        return lo, hi, est, refreshed, active

    return refresh_fn


def _host_copy(x, dtype=None) -> np.ndarray:
    """Writable host copy of a device array (np.asarray views device
    buffers read-only; the host bookkeeping mutates in place)."""
    return np.array(x, dtype=dtype)


class _PassSlot:
    """One slot of a device pass: a scan's views, the intervals of the
    queries that share it, the slot's cursor, lap and scan metrics, and
    its device columns. :func:`_build_pass` compiles the round loop of a
    list of slots, :func:`_pass_carry` packs their state into its carry
    and :func:`_write_back` reads the carry back into them, so the
    recovery pass and result construction run the exact host-loop code
    on identical state. ``FastFrame.run`` drives a pass of one slot
    holding one query; :class:`repro.serve.SharedPass` one of many.

    ``anchor`` is the pass-coordinate position where the slot was
    admitted (its lap is ``[anchor, anchor + n_blocks)``), ``join_round``
    the pass round count at admission and ``row_offset`` the valid rows
    before the anchor; all three are 0 for a solo run. ``words`` are the
    padded group-bitmap words of a probe slot, None otherwise."""

    def __init__(self, views: _ScanViews, qcis: Sequence["_QueryIntervals"],
                 probe: bool, values, gids, words, anchor: int = 0,
                 join_round: int = 0, row_offset: int = 0):
        self.views = views
        self.qcis = list(qcis)
        self.probe = probe
        self.values, self.gids, self.words = values, gids, words
        self.anchor = anchor
        self.join_round = join_round
        self.row_offset = row_offset
        self.pos = anchor              # this slot's cursor, pass coords
        self.lap_done_round = None     # pass round when the lap completed
        self.metrics = {"skipped_static": 0, "skipped_active": 0,
                        "probes": views.probes0}

    def spec(self, nbins: int) -> kfused.SlotSpec:
        """The slot's static configuration for the round kernels."""
        v = self.views
        return kfused.SlotSpec(
            num_groups=v.G, nbins=nbins, use_hist=v.use_hist,
            a=float(v.a), b=float(v.b), center=float(v.center),
            probe=self.probe,
            n_words=v.group_bm.n_words if self.probe else 0)


def _slot_columns(frame: "FastFrame", views: _ScanViews, probe: bool,
                  window: int, shards: Optional[adist.BlockShards]):
    """A slot's device columns ``(values, gids, words)``: the value and
    group-code slabs from the frame's caches (row-sharded over the mesh
    when ``shards`` is given) and, for a probe slot, its replicated
    :func:`~repro.kernels.fused_scan.pad_blocks`-padded bitmap words."""
    words = (adist.place_replicated(
        shards, kfused.pad_blocks(views.group_bm.words, window))
        if probe else None)
    return (frame._device_values(views.value_src, shards),
            frame._device_gids(views.gcol, shards), words)


def _build_pass(frame: "FastFrame", slots: Sequence[_PassSlot], *,
                window: int, lookahead: int, max_rounds: int,
                chunk: Optional[int],
                shards: Optional[adist.BlockShards]):
    """The jitted pass loop of ``slots``
    (:func:`~repro.kernels.fused_scan.build_pass_loop`) and the slots'
    padded presence buffers. Both depend only on the slots' static
    configuration, so callers cache them on the frame."""
    cfg = frame.config
    rep = lambda a: adist.place_replicated(shards, a)
    refresh_fns = tuple(
        tuple(_make_device_refresh(qc.q, qc, s.views.a, s.views.b,
                                   qc.use_hist, float(qc.R), s.views.valid)
              for qc in s.qcis)
        for s in slots)
    chunk_fn = kfused.build_pass_loop(
        nb=frame.scramble.n_blocks, window=window, budget=cfg.round_blocks,
        impl=kops.resolve_impl(cfg.impl), lookahead=lookahead,
        cover_cap=cfg.round_blocks * cfg.cover_cap_factor,
        max_rounds=max_rounds, chunk=chunk,
        slot_specs=tuple(s.spec(cfg.hist_bins) for s in slots),
        refresh_fns=refresh_fns,
        shard=shards.info if shards is not None else None,
        anchors=tuple(s.anchor for s in slots),
        round_offsets=tuple(s.join_round for s in slots),
        row_offsets=tuple(s.row_offset for s in slots))
    presence = tuple(rep(kfused.pad_blocks(s.views.presence, window))
                     for s in slots)
    presence_total = tuple(rep(s.views.presence_total.astype(np.int32))
                           for s in slots)
    return chunk_fn, presence, presence_total


def _pass_buffers(slots: Sequence[_PassSlot], mask, order_pad, static_ok,
                  cum_rows, presence, presence_total
                  ) -> kfused.PassLoopBuffers:
    """The pass loop's inputs: the pass-wide buffers and each slot's
    columns and presence."""
    return kfused.PassLoopBuffers(
        mask=mask, order_pad=order_pad, static_ok=static_ok,
        cum_rows=cum_rows, values=tuple(s.values for s in slots),
        gids=tuple(s.gids for s in slots),
        words=tuple(s.words for s in slots), presence=presence,
        presence_total=presence_total)


def _pass_carry(slots: Sequence[_PassSlot], *, rounds: int, n_live: int,
                nbins: int, shards: Optional[adist.BlockShards]
                ) -> kfused.PassCarry:
    """The pass loop's carry from the slots' host state: each slot's
    cursor, fold, coverage and metrics held absolute (written back as
    they come out), each query's running intervals."""
    cadence = shards is not None and shards.merge_every > 1
    f64 = lambda x: jnp.asarray(x, jnp.float64)
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    i64 = lambda v: jnp.asarray(v, jnp.int64)

    def pending(v: _ScanViews) -> dict:
        # collective-cadence pending slots: empty local delta
        if not cadence:
            return {}
        return dict(
            pend_sums=jnp.zeros((3, v.G), jnp.float64),
            pend_vmin=jnp.full((v.G,), np.inf, jnp.float64),
            pend_vmax=jnp.full((v.G,), -np.inf, jnp.float64),
            pend_hist=(jnp.zeros((v.G, nbins), jnp.float64)
                       if v.use_hist else None))

    slot_carries = tuple(
        kfused.SlotCarry(
            pos=i32(s.pos),
            state=MomentState(*(f64(x) for x in s.views.state)),
            hist=(f64(s.views.hist) if s.views.use_hist else None),
            seen_presence=jnp.asarray(
                s.views.seen_presence.astype(np.int32)),
            tainted=jnp.asarray(s.views.tainted),
            exact=jnp.asarray(s.views.exact),
            processed=jnp.asarray(s.views.processed),
            blocks_fetched=i64(s.views.blocks_fetched),
            skipped_static=i64(s.metrics["skipped_static"]),
            skipped_active=i64(s.metrics["skipped_active"]),
            probes=i64(s.metrics["probes"]),
            lap_rounds=i32(s.lap_done_round
                           if s.lap_done_round is not None else -1),
            **pending(s.views))
        for s in slots)
    query_carries = tuple(
        tuple(kfused.PassQueryCarry(
            lo=f64(qc.lo), hi=f64(qc.hi), est=f64(qc.est),
            refreshed=jnp.asarray(qc.refreshed),
            active=jnp.asarray(qc.active & ~np.asarray(qc.finished)),
            finished=jnp.asarray(bool(qc.finished)),
            stopped_early=jnp.asarray(False),
            finish_rounds=i32(0), finish_pos=i32(0),
            finish_blocks_fetched=i64(0), finish_skipped_static=i64(0),
            finish_skipped_active=i64(0), finish_probes=i64(0),
            snap_counts=jnp.zeros(s.views.G, jnp.float64),
            snap_exact=jnp.zeros(s.views.G, bool),
            snap_tainted=jnp.zeros(s.views.G, bool))
            for qc in s.qcis)
        for s in slots)
    return kfused.PassCarry(
        rounds=i32(rounds), it=i32(0), n_live=i32(n_live),
        slots=slot_carries, queries=query_carries,
        **(dict(pend_rounds=i32(0)) if cadence else {}))


def _write_back(slots: Sequence[_PassSlot], carry: kfused.PassCarry
                ) -> List[Tuple[_PassSlot, "_QueryIntervals",
                                kfused.PassQueryCarry]]:
    """Copy a pass carry back into the slots: each slot's cursor, lap,
    fold/coverage/soundness state and metrics, and the intervals of each
    query that was unfinished when the carry was packed. Returns
    ``(slot, query, query carry)`` for the queries that finished in the
    loop, whose finish-time snapshots the carry holds."""
    host = _host_copy
    newly = []
    for s, sc, qcars in zip(slots, carry.slots, carry.queries):
        v = s.views
        v.state = MomentState(*(host(f, np.float64) for f in sc.state))
        if v.use_hist:
            v.hist = host(sc.hist, np.float64)
        v.processed = host(sc.processed)
        v.seen_presence = host(sc.seen_presence, np.int64)
        v.tainted = host(sc.tainted)
        v.exact = host(sc.exact)
        v.blocks_fetched = int(sc.blocks_fetched)
        for f in ("skipped_static", "skipped_active", "probes"):
            s.metrics[f] = int(getattr(sc, f))
        s.pos = int(sc.pos)
        if s.pos >= v.lap_end and s.lap_done_round is None:
            s.lap_done_round = int(sc.lap_rounds)
        for qc, qcar in zip(s.qcis, qcars):
            if qc.finished:
                continue   # its carry stayed frozen
            qc.lo = host(qcar.lo, np.float64)
            qc.hi = host(qcar.hi, np.float64)
            qc.est = host(qcar.est, np.float64)
            qc.refreshed = host(qcar.refreshed)
            qc.active = host(qcar.active)
            qc.finished = bool(qcar.finished)
            if qc.finished:
                newly.append((s, qc, qcar))
    return newly


class _DeviceLoop:
    """``FastFrame.run``'s device round loop: the pass loop of one slot
    holding one query at anchor 0 (:func:`_build_pass`), with the slot's
    device buffers. Cached on the frame by the query's static identity,
    so the jitted loop compiles once per query shape; each run installs
    its scan order, and :meth:`run` dispatches chunks of up to
    ``sync_every``/``chunk_rounds`` rounds with one scalar host sync
    between them."""

    def __init__(self, frame: "FastFrame", q: AggQuery, slot: _ScanViews,
                 qci: "_QueryIntervals", probe: bool, lookahead: int,
                 max_rounds: int,
                 shards: Optional[adist.BlockShards] = None):
        require_x64("the device-resident round loop")
        cfg = frame.config
        nb = frame.scramble.n_blocks
        window = _round_window(nb, lookahead,
                               cfg.round_blocks * cfg.cover_cap_factor)
        self.nb = nb
        self.window = window
        self.probe = probe
        self.max_rounds = max_rounds
        self.shards = shards
        # scan-order-independent buffers, padded on the host once per
        # loop (see kfused.pad_blocks); order_pad / cum_rows are set per
        # run. When sharded, the data slabs are row-sharded over the
        # mesh and every other buffer is placed replicated.
        self.columns = _slot_columns(frame, slot, probe, window, shards)
        self.mask = frame._device_mask(q.filters, shards)
        self.static_ok = adist.place_replicated(
            shards, kfused.pad_blocks(slot.static_ok, window))
        self.nbins = cfg.hist_bins
        self._chunk_fn, self.presence, self.presence_total = _build_pass(
            frame, [_PassSlot(slot, [qci], probe, *self.columns)],
            window=window, lookahead=lookahead, max_rounds=max_rounds,
            chunk=cfg.sync_every or cfg.chunk_rounds, shards=shards)

    def start(self, views: _ScanViews, qci: "_QueryIntervals", start: int,
              cum_rows: np.ndarray) -> Tuple[_PassSlot, kfused.PassCarry]:
        """This run's one-query slot and first carry, with the scan order
        installed: the rotation starting at block ``start``, the only
        run-dependent input."""
        slot = _PassSlot(views, [qci], self.probe, *self.columns)
        rep = lambda a: adist.place_replicated(self.shards, a)
        self.bufs = _pass_buffers(
            [slot], self.mask,
            rep(kfused.rotation_order_pad(start, self.nb, self.window)),
            self.static_ok, rep(cum_rows.astype(np.int64)), self.presence,
            self.presence_total)
        return slot, _pass_carry([slot], rounds=0, n_live=1,
                                 nbins=self.nbins, shards=self.shards)

    def run(self, carry: kfused.PassCarry,
            on_sync: Optional[Callable] = None) -> kfused.PassCarry:
        """Dispatch chunks until the loop terminates; between dispatches
        the host pulls one scalar (plus the streaming snapshot for
        ``on_sync`` subscribers when ``sync_every`` is set)."""
        while True:
            carry = self._chunk_fn(self.bufs, carry)
            (sc,), ((qc,),) = carry.slots, carry.queries
            if on_sync is not None:
                on_sync(dict(
                    rounds=int(carry.rounds), pos=int(sc.pos),
                    lo=np.asarray(qc.lo, np.float64),
                    hi=np.asarray(qc.hi, np.float64),
                    est=np.asarray(qc.est, np.float64),
                    live=not bool(qc.finished)))
            if (bool(qc.finished) or int(sc.pos) >= self.nb
                    or int(carry.rounds) >= self.max_rounds):
                return carry


class FastFrame:
    """Sampling-optimized in-memory column store (paper §4).

    Wraps a :class:`~repro.aqp.scramble.Scramble` with block bitmap
    indexes and the OptStop round loop; :meth:`run` answers one
    :class:`~repro.aqp.query.AggQuery` with anytime-valid intervals.
    Concurrent batches of queries are served with shared scans by
    :class:`repro.serve.FrameServer`.
    """

    def __init__(self, scramble: Scramble, config: EngineConfig = None):
        self.scramble = scramble
        self.config = config or EngineConfig()
        self._bitmaps: Dict[str, BlockBitmap] = {}
        self._static_cache: Dict[Tuple, np.ndarray] = {}
        self._valid_counts = scramble.valid.sum(axis=1).astype(np.int64)
        # device-resident materialization caches, keyed by the components
        # of the (filters, column, group-by) scan signature (+ whether
        # the buffer is mesh-sharded); LRU-bounded
        # (config.mat_cache_entries) so a long-lived server receiving
        # ad-hoc filter values cannot grow device memory without limit —
        # in-flight scans hold direct references, so eviction only drops
        # the cache's pin, never a buffer a pass is using
        cap = self.config.mat_cache_entries
        self._dev_masks = LRUCache(cap)
        self._dev_values = LRUCache(cap)
        self._dev_gids = LRUCache(cap)
        # compiled device-resident round loops (engine + serving pass),
        # keyed by the query/pass static identity: repeat queries reuse
        # the traced lax.while_loop instead of recompiling per run.
        # Public: the serving layer hangs its compiled pass loops here.
        self.device_loops = LRUCache(cap)
        # host (blocks × groups) presence matrices by group column
        self._presence = LRUCache(cap)
        self._block_shards: Optional[adist.BlockShards] = None
        self._shards_resolved = False

    def block_shards(self) -> Optional[adist.BlockShards]:
        """The frame's sharded block layout, or ``None`` when sharding is
        off (``EngineConfig.shard_rows`` resolves False, or the mesh
        would have a single device). Built once and cached so every run
        and serving pass shards over the same mesh object."""
        if not self._shards_resolved:
            shards = None
            if self.config.resolve_shard_rows():
                mesh = adist.make_aqp_mesh(self.config.mesh_shape)
                shards = adist.build_block_shards(
                    self.scramble.n_blocks, mesh,
                    self.scramble.valid.shape[1],
                    merge_every=self.config.merge_every)
            self._block_shards = shards
            self._shards_resolved = True
        return self._block_shards

    # -- index plumbing ------------------------------------------------------

    def bitmap(self, column: str) -> BlockBitmap:
        if column not in self._bitmaps:
            self._bitmaps[column] = build_bitmap(self.scramble, column)
        return self._bitmaps[column]

    def _group_presence(self, gcol: Optional[str], G: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """The read-only ``(n_blocks, G)`` bool presence matrix of group
        column ``gcol`` (one all-true view without a GROUP BY) and its
        per-group block counts. Both depend on the column alone, so they
        are cached: unpacking a 606M-row table's 200-group bitmap and
        summing it takes some 0.18 s of host time a query."""

        def build():
            if gcol is None:
                presence = np.ones((self.scramble.n_blocks, 1), dtype=bool)
            else:
                presence = unpack_words(self.bitmap(gcol).words, G)
            # block counts fit int32, whose accumulator halves the pass
            total = presence.sum(axis=0, dtype=np.int32)
            presence.setflags(write=False)
            total.setflags(write=False)
            return presence, total

        return self._presence.get_or_build(gcol, build)

    def _composite_group(self, cols: Tuple[str, ...]) -> Tuple[str, int]:
        """Synthesize (and cache) a composite group-code column.

        Raises:
            ValueError: when the cardinality product exceeds the int32
                group-code space the kernels operate in — composite codes
                would silently wrap and merge unrelated groups.
        """
        if len(cols) == 1:
            return cols[0], self.scramble.categorical[cols[0]]
        name = "__grp_" + "_".join(cols)
        if name not in self.scramble.columns:
            card = 1
            for c in cols:
                card *= int(self.scramble.categorical[c])
            if card > _INT32_MAX:
                raise ValueError(
                    f"composite GROUP BY over {cols} has cardinality "
                    f"product {card} > int32 max ({_INT32_MAX}); group "
                    "codes would wrap and merge unrelated groups. Reduce "
                    "the grouping cardinality (e.g. pre-bucket a column).")
            codes = np.zeros_like(self.scramble.columns[cols[0]],
                                  dtype=np.int64)
            for c in cols:
                cc = self.scramble.categorical[c]
                codes = codes * cc + self.scramble.columns[c]
            self.scramble.columns[name] = codes.astype(np.int32)
            self.scramble.categorical[name] = card
        return name, self.scramble.categorical[name]

    def _static_ok(self, q: AggQuery) -> Tuple[np.ndarray, int]:
        """Block-level predicate prefilter from categorical eq/isin filters
        (available to every approximate strategy, incl. Scan — §5.2)."""
        key = tuple(f.key() for f in q.filters
                    if f.categorical_eq and f.column in
                    self.scramble.categorical)
        if not key:
            return np.ones(self.scramble.n_blocks, dtype=bool), 0
        if key in self._static_cache:
            return self._static_cache[key], 0
        ok = np.ones(self.scramble.n_blocks, dtype=bool)
        probes = 0
        for f in q.filters:
            if not (f.categorical_eq and f.column in
                    self.scramble.categorical):
                continue
            bm = self.bitmap(f.column)
            cmask = np.zeros(bm.cardinality, dtype=bool)
            vals = np.atleast_1d(np.asarray(f.value))
            cmask[vals] = True
            hit = kops.active_blocks(jnp.asarray(bm.words),
                                     jnp.asarray(pack_mask(cmask)),
                                     impl=self.config.impl)
            ok &= np.asarray(hit) > 0
            probes += self.scramble.n_blocks
        self._static_cache[key] = ok
        return ok, probes

    # -- value / mask materialization -----------------------------------------

    def _values_and_bounds(self, q: AggQuery):
        if q.agg == "count":
            return None, (0.0, 1.0)
        if isinstance(q.column, Expression):
            return q.column, q.column.derived_bounds(self.scramble.catalog)
        return q.column, self.scramble.catalog[q.column]

    @staticmethod
    def _put_blocks(arr: np.ndarray, shards: Optional[adist.BlockShards]
                    ) -> jnp.ndarray:
        """Place a (n_blocks, block_rows) slab on device: row-sharded
        over the mesh when ``shards`` is set, single-device otherwise."""
        if shards is not None:
            return shards.put_blocks(arr)
        return jnp.asarray(arr)

    def _device_mask(self, filters, shards=None) -> jnp.ndarray:
        """Device-resident (n_blocks, block_rows) f32 predicate*valid
        mask, cached by the filters' key (per sharded/unsharded
        layout)."""

        def build():
            sc = self.scramble
            mask = sc.valid.copy()
            for f in filters:
                mask &= f.evaluate(sc.columns)
            return self._put_blocks(mask.astype(np.float32), shards)

        key = (tuple(f.key() for f in filters), shards is not None)
        return self._dev_masks.get_or_build(key, build)

    def _device_values(self, value_src, shards=None) -> jnp.ndarray:
        """Device-resident f32 value column (zeros for COUNT), cached by
        the column name / Expression (per sharded/unsharded layout)."""

        def build():
            sc = self.scramble
            if isinstance(value_src, Expression):
                values = value_src.evaluate(sc.columns)
            elif isinstance(value_src, str):
                values = sc.columns[value_src].astype(np.float32)
            else:  # COUNT: value column unused
                values = np.zeros(sc.valid.shape, np.float32)
            return self._put_blocks(np.asarray(values, np.float32),
                                    shards)

        return self._dev_values.get_or_build(
            (value_src, shards is not None), build)

    def _device_gids(self, gcol: Optional[str], shards=None) -> jnp.ndarray:
        """Device-resident int32 group-code column, cached by name (per
        sharded/unsharded layout)."""

        def build():
            sc = self.scramble
            gids = (sc.columns[gcol].astype(np.int32) if gcol is not None
                    else np.zeros(sc.valid.shape, np.int32))
            return self._put_blocks(gids, shards)

        return self._dev_gids.get_or_build((gcol, shards is not None),
                                           build)

    def _materialize(self, q: AggQuery, idx: np.ndarray, value_src,
                     gcol: Optional[str]):
        sc = self.scramble
        block_cols = {}
        needed = set(f.column for f in q.filters)
        if isinstance(value_src, Expression):
            needed |= set(value_src.columns)
        elif isinstance(value_src, str):
            needed.add(value_src)
        for c in needed:
            block_cols[c] = sc.columns[c][idx]
        mask = sc.valid[idx].copy()
        for f in q.filters:
            mask &= f.evaluate(block_cols)
        if isinstance(value_src, Expression):
            values = value_src.evaluate(block_cols)
        elif isinstance(value_src, str):
            values = block_cols[value_src].astype(np.float32)
        else:  # COUNT: value column unused
            values = np.zeros_like(mask, dtype=np.float32)
        gids = (sc.columns[gcol][idx] if gcol is not None
                else np.zeros(mask.shape, dtype=np.int32))
        return values, gids.astype(np.int32), mask

    # -- block folding ---------------------------------------------------------

    def _fold_blocks(self, q, idx, value_src, gcol, G, center, a, b,
                     state, hist, use_hist, pad_to: Optional[int] = None):
        """Materialize blocks ``idx`` and fold them into the running
        per-group moment state (+ histogram): the one shared ingest path
        for the main round loop and the recovery pass.

        ``pad_to`` pads the fold input to a static block count so tail
        rounds do not retrace the XLA fold computation; padding rows
        carry ``mask == 0`` and contribute exact zeros.
        """
        cfg = self.config
        values, gids, mask = self._materialize(q, idx, value_src, gcol)
        if pad_to is not None and len(idx) < pad_to:
            pr = pad_to - len(idx)
            br = mask.shape[1]
            values = np.concatenate(
                [values, np.zeros((pr, br), values.dtype)])
            gids = np.concatenate([gids, np.zeros((pr, br), gids.dtype)])
            mask = np.concatenate([mask, np.zeros((pr, br), mask.dtype)])
        vf = jnp.asarray(values.reshape(-1))
        gf = jnp.asarray(gids.reshape(-1))
        mf = jnp.asarray(mask.reshape(-1).astype(np.float32))
        upd = kops.grouped_moments(vf, gf, mf, G, center, impl=cfg.impl)
        state = merge_moments_host(state, to_host(upd))
        if use_hist:
            hupd = kops.grouped_hist(vf, gf, mf, G, a, b,
                                     nbins=cfg.hist_bins, impl=cfg.impl)
            hist = merge_hist_host(hist, hupd.hist)
        return state, hist

    # -- cursor advance --------------------------------------------------------

    def _advance(self, order, pos, static_ok, group_bm, active_words,
                 presence, tainted, lookahead, budget, cover_cap,
                 skipping, metrics):
        """Advance the scan cursor, selecting up to ``budget`` blocks.

        Returns (idx_to_process, new_pos). Skip accounting (taint, counters)
        is applied only to positions actually covered (< new_pos)."""
        nb = order.shape[0]
        records = []
        p = pos
        total_sel = 0
        while (total_sel < budget and p < nb and (p - pos) < cover_cap):
            end = min(p + lookahead, nb)
            batch = order[p:end]
            ok = static_ok[batch]
            flags = ok.copy()
            if skipping and group_bm is not None:
                # pad the tail batch to a full lookahead so the probe
                # shapes stay static (no per-shape XLA retrace at the
                # scramble tail); padded zero-words can never be active
                bwords = group_bm.words[batch]
                if len(batch) < lookahead:
                    bwords = np.concatenate(
                        [bwords, np.zeros((lookahead - len(batch),
                                           group_bm.n_words), np.uint32)])
                act = np.asarray(kops.active_blocks(
                    jnp.asarray(bwords), active_words,
                    impl=self.config.impl))[:len(batch)] > 0
                metrics["probes"] += len(batch)
                flags &= act
            records.append((p, batch, ok, flags))
            total_sel += int(flags.sum())
            p = end

        # cut position: just after the budget-th selected block
        selected = []
        cut = p
        remaining = budget
        for (base, batch, ok, flags) in records:
            sel_local = np.nonzero(flags)[0]
            take = sel_local[:remaining]
            selected.append(batch[take])
            remaining -= len(take)
            if remaining == 0:
                cut = base + int(take[-1]) + 1
                break
        new_pos = min(cut, p)

        # skip accounting within the covered range only
        for (base, batch, ok, flags) in records:
            if base >= new_pos:
                break
            n = min(new_pos - base, len(batch))
            okc, flagsc = ok[:n], flags[:n]
            metrics["skipped_static"] += int((~okc).sum())
            act_skip = okc & ~flagsc
            metrics["skipped_active"] += int(act_skip.sum())
            if act_skip.any():
                tainted |= presence[batch[:n][act_skip]].any(axis=0)
        idx = (np.concatenate(selected) if selected
               else np.zeros(0, dtype=np.int64))
        return idx, new_pos

    def _fused_accounting(self, order, pos, new_pos, ok, flags, presence,
                          tainted, lookahead, budget, cover_cap, probe,
                          metrics, lap_end=None):
        """Host-side bookkeeping for one fused round: replicates the
        reference `_advance` skip/taint/probe accounting bit-for-bit from
        the per-position verdicts the kernel returned, and materializes
        the selected block ids.

        ``lap_end`` clamps the accounting to one slot's carousel lap in a
        shared pass whose cursor runs past ``n_blocks`` (late joiners):
        window positions at or beyond the slot's lap end belong to other
        slots' laps and must not count toward this slot's skip/taint/
        probe metrics, nor appear in its selected block ids. The cursor
        position maps to a block via ``order[position % n_blocks]``
        (the scan order is a rotation for every anchor). Defaults to
        ``n_blocks`` — the plain single-lap scan."""
        nb = order.shape[0]
        end = nb if lap_end is None else lap_end
        if probe:
            # probe metric: the reference path probes whole lookahead
            # batches until the budget is met (or cap/end reached)
            win_len = min(len(flags), end - pos)
            total, p = 0, 0
            while total < budget and p < win_len and p < cover_cap:
                e = min(p + lookahead, win_len)
                metrics["probes"] += e - p
                total += int(flags[p:e].sum())
                p = e
        covered = min(new_pos, end) - pos
        okc, flagsc = ok[:covered], flags[:covered]
        metrics["skipped_static"] += int((~okc).sum())
        act_skip = okc & ~flagsc
        metrics["skipped_active"] += int(act_skip.sum())
        win_ids = order[(pos + np.arange(covered)) % nb]
        if act_skip.any():
            tainted |= presence[win_ids[act_skip]].any(axis=0)
        sel = np.nonzero(flagsc)[0][:budget]
        return (win_ids[sel] if sel.size
                else np.zeros(0, dtype=np.int64))

    # -- recovery (soundness of termination) -----------------------------------

    def _recovery_pass(self, slot: _ScanViews,
                       qcis: List[_QueryIntervals], rounds: int,
                       max_rounds: int) -> int:
        """After the cursor exhausts the scramble, any still-active view is
        either tainted (its CI froze when its blocks were skipped while it
        was inactive) or empty. Tainted views cannot tighten via sampling
        (their scan prefix is broken), but full coverage is always sound:
        process their remaining unprocessed blocks until the aggregate is
        exact. Guarantees termination for every stopping condition
        (e.g. top-K with a moving midpoint re-activating frozen views).

        Shared by :meth:`run` (one query) and ``FrameServer`` (all of a
        slot's unfinished queries at once — the needed-block union covers
        every query's active views). Returns the updated round count.
        """
        cfg = self.config

        def union_active():
            u = np.zeros(slot.G, dtype=bool)
            for qc in qcis:
                qc.active = qc.cond_active() & ~slot.exact & slot.valid
                u |= qc.active
            return u

        while rounds < max_rounds:
            counts = slot.counts
            union = union_active()
            if not union.any():
                break
            rounds += 1
            need = slot.presence[:, union].any(axis=1) & ~slot.processed
            idx = np.nonzero(need)[0][:cfg.lookahead_blocks]
            if len(idx) == 0:
                # active views with zero observed rows over full coverage
                # are empty views: drop them
                slot.exact |= union & (counts == 0)
                if not union_active().any():
                    break
                continue
            slot.ingest_blocks(idx, pad_to=cfg.lookahead_blocks)
            slot.update_exact()
            for qc in qcis:
                qc.collapse_exact()
        return rounds

    # -- main entry ------------------------------------------------------------

    def run(self, q: AggQuery, sampling: str = "active_peek",
            start_block: Optional[int] = None, seed: int = 0,
            max_rounds: int = 100_000,
            on_sync: Optional[Callable] = None) -> QueryResult:
        """Execute one aggregate query.

        Args:
            q: the query (aggregate, filters, GROUP BY, stopping
                condition, bounder configuration).
            sampling: scan strategy — ``'active_peek'`` (batched bitmap
                lookahead, paper §4.3), ``'active_sync'`` (synchronous
                probes), ``'scan'`` (no activity skipping) or ``'exact'``
                (full sequential sweep, the paper's strawman baseline;
                also forced when ``q.stop is None``).
            start_block: scan start position (default: random from
                ``seed``); the scan order wraps around the scramble.
            seed: RNG seed for the scan start.
            max_rounds: hard cap on OptStop rounds (safety valve).
            on_sync: optional streaming callback for the device-resident
                loop: called after every dispatch (i.e. every
                ``EngineConfig.sync_every`` rounds, or once at
                termination when unchunked) with a dict snapshot
                (``rounds``, ``pos``, ``lo``, ``hi``, ``est``,
                ``live``). Ignored by the host loop and exact mode.

        Returns:
            :class:`~repro.aqp.query.QueryResult` with per-group
            estimates, anytime-valid ``(1 - q.delta)`` intervals and scan
            metrics.

        Each call records host spans (a ``jax.profiler.TraceAnnotation``
        each, seen only while a profiler trace is on): ``aqp:run`` around
        the call, and inside it, on the device-loop path and in order,
        ``aqp:views`` (the scan views and query intervals),
        ``aqp:upload`` (scan order, the device loop and its inputs),
        ``aqp:loop`` (the device loop's dispatches and syncs),
        ``aqp:writeback``, ``aqp:recovery`` and ``aqp:result``. The host
        loop and the exact sweep record ``aqp:run``, ``aqp:views``,
        ``aqp:recovery`` (not the exact sweep) and ``aqp:result``.
        """
        with TraceAnnotation("aqp:run"):
            return self._run(q, sampling, start_block, seed, max_rounds,
                             on_sync)

    def _run(self, q: AggQuery, sampling: str, start_block: Optional[int],
             seed: int, max_rounds: int,
             on_sync: Optional[Callable]) -> QueryResult:
        t0 = time.perf_counter()
        cfg = self.config
        sc = self.scramble
        nb = sc.n_blocks
        rng = np.random.default_rng(seed)
        exact_mode = (sampling == "exact") or (q.stop is None)
        if cfg.shard_rows:
            # explicit sharding that cannot take effect (no device loop /
            # single device) must fail loudly, not silently run unsharded
            cfg.resolve_shard_rows()

        def scan_order():
            # random start, wrap around (paper §5.2)
            start = int(rng.integers(nb) if start_block is None
                        else start_block) % nb
            order = (start + np.arange(nb)) % nb
            return start, order, np.cumsum(self._valid_counts[order])

        with TraceAnnotation("aqp:views"):
            slot = _ScanViews(self, q)
            qci = _QueryIntervals(self, q, slot)
        metrics = {"skipped_static": 0, "skipped_active": 0,
                   "probes": slot.probes0}

        pos = 0
        rounds = 0
        stopped_early = False
        skipping = (not exact_mode) and sampling in ("active_peek",
                                                     "active_sync")
        lookahead = (cfg.sync_lookahead_blocks if sampling == "active_sync"
                     else cfg.lookahead_blocks)
        cover_cap = cfg.round_blocks * cfg.cover_cap_factor

        if not exact_mode and cfg.resolve_device_loop():
            # ---- device-resident round loop (tentpole path): the whole
            # OptStop loop in lax.while_loop dispatches; one host sync
            # per chunk, full writeback at termination -----------------
            with TraceAnnotation("aqp:upload"):
                start, _, cum_rows = scan_order()
                probe = skipping and slot.group_bm is not None
                shards = self.block_shards()
                key = ("run", q.scan_signature(), q.agg, q.bounder,
                       q.rangetrim, q.delta, repr(q.stop), probe,
                       lookahead, max_rounds,
                       cfg.sync_every or cfg.chunk_rounds,
                       (shards.n_shards, shards.shard_rows,
                        shards.merge_every)
                       if shards is not None else None)
                dloop = self.device_loops.get_or_build(
                    key,
                    lambda: _DeviceLoop(self, q, slot, qci, probe,
                                        lookahead, max_rounds, shards))
                pslot, carry = dloop.start(slot, qci, start, cum_rows)
            with TraceAnnotation("aqp:loop"):
                carry = dloop.run(carry, on_sync)
            with TraceAnnotation("aqp:writeback"):
                _write_back([pslot], carry)
                (qcar,), = carry.queries
                metrics = pslot.metrics
                pos = pslot.pos
                # a query that stopped answers from the round it stopped
                # on: a collective-cadence loop counts one more round,
                # frozen, before it exits
                rounds = int(qcar.finish_rounds if qci.finished
                             else carry.rounds)
                stopped_early = qci.finished and bool(qcar.stopped_early)
            with TraceAnnotation("aqp:recovery"):
                rounds = self._recovery_pass(slot, [qci], rounds,
                                             max_rounds)
            with TraceAnnotation("aqp:result"):
                qci.collapse_exact()
                return qci.result(rounds, pos, cum_rows, metrics, t0,
                                  stopped_early)

        _, order, cum_rows = scan_order()
        active_words = (jnp.asarray(pack_mask(qci.active))
                        if slot.gcol is not None else None)
        fscan = None
        if cfg.fused and not exact_mode:
            probe = skipping and slot.group_bm is not None
            fscan = _FusedScan(self, q, slot.value_src, slot.gcol, slot.G,
                               slot.center, slot.a, slot.b, slot.use_hist,
                               probe, lookahead, cfg.round_blocks,
                               cover_cap, slot.static_ok,
                               slot.group_bm if probe else None, order)

        while pos < nb and rounds < max_rounds:
            rounds += 1
            # ---- 1+2. cursor advance + fold --------------------------------
            upd = hupd = None
            if exact_mode:
                end = min(pos + cfg.lookahead_blocks, nb)
                idx = order[pos:end]  # full sweep, no skipping (strawman)
                pos = end
            elif fscan is not None:
                # fused: one device dispatch + one host sync per round
                upd, hupd, ok_w, flags_w, new_pos = \
                    fscan.round(pos, active_words)
                idx = self._fused_accounting(
                    order, pos, new_pos, ok_w, flags_w, slot.presence,
                    slot.tainted, lookahead, cfg.round_blocks, cover_cap,
                    fscan.probe, metrics)
                pos = new_pos
            else:
                idx, pos = self._advance(
                    order, pos, slot.static_ok, slot.group_bm,
                    active_words, slot.presence, slot.tainted, lookahead,
                    cfg.round_blocks, cover_cap, skipping, metrics)

            if len(idx):
                if upd is not None:
                    slot.ingest_delta(idx, upd, hupd)
                else:
                    slot.ingest_blocks(
                        idx, pad_to=(cfg.lookahead_blocks if exact_mode
                                     else cfg.round_blocks))
            slot.update_exact(pos)

            if exact_mode:
                continue

            # ---- 3. per-view CI refresh ------------------------------------
            r = int(cum_rows[pos - 1]) if pos > 0 else 0
            qci.refresh(rounds, r)

            # ---- 4. stopping / activity ------------------------------------
            if not qci.update_active():
                stopped_early = pos < nb
                break
            if slot.gcol is not None:
                active_words = jnp.asarray(pack_mask(qci.active))

        if not exact_mode:
            with TraceAnnotation("aqp:recovery"):
                rounds = self._recovery_pass(slot, [qci], rounds,
                                             max_rounds)

        with TraceAnnotation("aqp:result"):
            qci.collapse_exact()
            if exact_mode:
                stopped_early = False
            return qci.result(rounds, pos, cum_rows, metrics, t0,
                              stopped_early)
