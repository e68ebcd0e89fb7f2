"""FrameServer: shared-scan serving of concurrent AggQuery batches.

``FastFrame.run`` answers one query per scan: it materializes device
columns, walks the scramble, and folds blocks for that query alone. Under
concurrent traffic most of that work is redundant — queries over the same
table share filters, value columns and groupings, and every query walks
the same scramble. :class:`FrameServer` amortizes it three ways:

  1. **Materialization caching** — the device-resident value / mask /
     group-code columns are cached on the :class:`~repro.aqp.engine.
     FastFrame` keyed by the components of the ``(filters, column,
     group-by)`` scan signature, so repeat queries (within a batch and
     across batches) never re-upload columns.
  2. **Shared fused-scan passes** — queries with the same filters are
     planned into one *pass*: one per-round device dispatch
     (:func:`repro.kernels.fused_scan.fused_round_multi`) advances every
     distinct ``(column, group-by)`` *slot* of the pass at once. Each
     slot walks its OWN cursor with its OWN activity flags (the union
     over the slot's queries), so a slot's selection/fold sequence is
     the solo run's, whatever else is co-resident; what is amortized is
     the dispatch, the shared mask/prefilter buffers and the
     materialization, not the selection.
  3. **Fold sharing** — queries with bitwise-equal scan signatures map to
     the same slot and share one :class:`~repro.aqp.engine._ScanViews`
     fold state; each keeps its own :class:`~repro.aqp.engine.
     _QueryIntervals` (OptStop schedule, CI refresh, stopping condition),
     which is the cheap part of a round.

A pass is no longer a static batch: :class:`SharedPass` exposes the
lifecycle as **admit / step / retire / finish**, so a serving loop
(:mod:`repro.serve.scheduler`) can feed queries into an in-flight cursor
walk continuously:

  * ``admit`` at any round boundary anchors a new slot at the current
    cursor frontier. Slot cursors run past ``n_blocks`` in unwrapped
    *pass coordinates* — a "carousel": each slot's lap is
    ``[anchor, anchor + n_blocks)``, the block under cursor position
    ``p`` is ``order[p % n_blocks]``, and a late joiner starts
    immediately (its skipped prefix comes around at the end of its
    lap). Because the scan order is a rotation for every anchor and
    every slot selects with its own flags at its own cursor, a slot's
    lap replays the solo scan ``engine.run(start_block=(start + anchor)
    % n_blocks)`` — the fold/coverage/taint sequence, and therefore
    every finished query's :class:`~repro.aqp.query.QueryResult`, is
    bitwise identical to that solo run, probe slots included (the
    slot-level bitwise co-residency contract, docs/serving.md).
  * ``step`` runs one round (host) or one dispatch chunk (device loop),
    snapshotting each query's result the moment it finishes.
  * ``retire`` drops slots whose queries have all finished, freeing fold
    width for the next admission (``run_batch`` never retires — a static
    batch keeps its dispatch shapes stable).
  * ``finish`` runs the shared recovery pass for queries still active at
    lap exhaustion and assembles the remaining results.

Under the device-resident pass loop, a frame with a sharded block
layout (``EngineConfig.shard_rows``; :mod:`repro.aqp.distributed`) runs
the whole pass SHARDED over the device mesh: the divided scan — each
slot's value/group slabs are row-slice-sharded, each shard gathers and
folds only its ``1/n_shards`` row slice of each slot's selection, and
per-slot cursors / interval state stay replicated (see
``docs/architecture.md``). Carousel (anchored) passes compose with the
sharded loop — mid-scan admission is just another static anchor. The
one exception is the collective cadence: on a ``merge_every > 1`` pass
a mid-lap joiner's refresh schedule would be quantized to merge
boundaries, so mid-scan admission and wrapped restores there raise the
typed :class:`UnsupportedPassConfig` for the scheduler to reroute.

Soundness: each slot skips a block only when none of ITS queries has an
active view there, so each query's skipped blocks contain only views
inactive for that query — exactly the single-query taint invariant
(within a slot, queries share the fold and the slot-level selection
union). Every query keeps its own delta schedule (evaluated at its
slot-local OptStop round number, a valid schedule), and the recovery
pass finishes any view left active at lap exhaustion. A late-joining
slot is never marked exact before its own lap covers the prefix it
skipped (`_ScanViews.lap_end` gates exhaustion-exactness).

A batch containing a single query (or a pass whose slots reduce to one
query) runs the same selection/fold computation as ``FastFrame.run`` and
returns a bitwise-identical :class:`~repro.aqp.query.QueryResult`
(``tests/test_serve.py`` asserts this against the engine's own fused and
per-block reference paths).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.aqp import distributed as adist
from repro.aqp.bitmap import pack_mask
from repro.aqp.engine import (FastFrame, _QueryIntervals, _ScanViews,
                              _host_copy, _make_device_refresh,
                              _restore_views_from_carry, _round_window)
from repro.aqp.query import AggQuery, QueryResult
from repro.core.state import MomentState, moments_nonfinite
from repro.kernels import fused_scan as kfused
from repro.kernels import ops as kops
from repro.serve.checkpoint import PassCheckpoint, SlotCheckpoint

__all__ = ["FrameServer", "SharedPass", "UnsupportedPassConfig"]


class UnsupportedPassConfig(RuntimeError):
    """A pass configuration the serving stack cannot run — currently
    mid-scan admission (anchor > 0) or a wrapped restore on a sharded
    pass running the collective cadence (``merge_every > 1``): a
    mid-lap joiner's observable round boundaries would be merge
    boundaries, up to K rounds apart from its solo run's refresh
    schedule. Raised by admission-time validation BEFORE any pass state
    mutates, so a scheduler can catch it and route the queries to a
    fresh pass instead of crashing the serving loop (the loop builder
    keeps its own late check as a backstop)."""


class _SlotExec:
    """One (filters, column, group-by) signature inside a pass: the shared
    fold state plus the device buffers and per-query interval states.

    ``anchor`` is the pass-coordinate position where the slot was
    admitted (its lap is ``[anchor, anchor + n_blocks)``; 0 for a static
    batch) and ``join_round`` the pass round count at admission, so
    slot-local OptStop rounds are ``pass_rounds - join_round``. ``pos``
    is the slot's OWN cursor (every slot advances independently; the
    pass tracks only the frontier ``max(pos)`` for anchoring new
    admissions).

    ``shards`` (a :class:`repro.aqp.distributed.BlockShards`) row-shards
    the slot's value/group slabs over the mesh for the sharded device
    pass loop; the bitmap words stay replicated (the activity test and
    selection are replicated computations)."""

    def __init__(self, frame: FastFrame, rep_q: AggQuery, skipping: bool,
                 queries: Sequence[AggQuery], window: int, shards=None,
                 anchor: int = 0, join_round: int = 0,
                 row_offset: int = 0):
        use_hist_any = any(q.needs_hist for q in queries)
        self.views = _ScanViews(frame, rep_q, use_hist=use_hist_any,
                                anchor=anchor)
        self.qcis = [_QueryIntervals(frame, q, self.views) for q in queries]
        self.anchor = anchor
        self.join_round = join_round
        self.row_offset = row_offset   # rows before anchor, pass coords
        self.pos = anchor              # this slot's cursor, pass coords
        self.lap_done_round = None     # pass round when the lap completed
        v = self.views
        # probe slots activity-test their real group bitmap; non-probe
        # slots (no GROUP BY, or non-skipping sampling) carry an all-ones
        # engagement bitmap so a finished query stops pulling blocks
        # without changing which blocks it saw while running
        self.probe = skipping and v.group_bm is not None
        self.values = frame._device_values(v.value_src, shards)
        self.gids = frame._device_gids(v.gcol, shards)
        nb = frame.scramble.n_blocks
        words = (v.group_bm.words if self.probe
                 else np.ones((nb, 1), np.uint32))
        self.words = adist.place_replicated(
            shards, kfused.pad_blocks(words, window))
        self.meta = (v.G, frame.config.hist_bins, v.use_hist,
                     float(v.a), float(v.b), float(v.center))
        self.metrics = {"skipped_static": 0, "skipped_active": 0,
                        "probes": v.probes0}

    def active_stack(self) -> jnp.ndarray:
        """(Q, W) uint32 per-query active words for this round."""
        if self.probe:
            rows = [pack_mask(qc.active) for qc in self.qcis]
        else:
            rows = [np.asarray([0 if qc.finished else 1], np.uint32)
                    for qc in self.qcis]
        return jnp.asarray(np.stack(rows))


class SharedPass:
    """One shared cursor walk with a continuous admit/step/retire/finish
    lifecycle (the carousel described in the module docstring).

    Construct via :meth:`FrameServer.open_pass`; all queries of a pass
    must share their filters. ``chunk_rounds`` overrides the device-loop
    dispatch granularity (``EngineConfig.sync_every``/``chunk_rounds``)
    — a scheduler uses small chunks so admission boundaries come up
    often; ``run_batch`` keeps the config default and runs to
    completion."""

    def __init__(self, frame: FastFrame, filters, sampling: str,
                 start_block: Optional[int], seed: int, max_rounds: int,
                 chunk_rounds: Optional[int] = None,
                 force_host: bool = False,
                 force_unsharded: bool = False):
        self.t0 = time.perf_counter()
        self.frame = frame
        cfg = frame.config
        self.cfg = cfg
        sc = frame.scramble
        self.nb = sc.n_blocks
        self.filters = tuple(filters)
        self.sampling = sampling
        self.max_rounds = max_rounds
        rng = np.random.default_rng(seed)
        self.start = (rng.integers(self.nb) if start_block is None
                      else start_block)
        self.order = (self.start + np.arange(self.nb)) % self.nb
        self.cum_rows = np.cumsum(frame._valid_counts[self.order])
        self.R_total = int(self.cum_rows[-1])

        self.skipping = sampling in ("active_peek", "active_sync")
        self.lookahead = (cfg.sync_lookahead_blocks
                          if sampling == "active_sync"
                          else cfg.lookahead_blocks)
        self.cover_cap = cfg.round_blocks * cfg.cover_cap_factor
        self.window = _round_window(self.nb, self.lookahead,
                                    self.cover_cap)
        self.impl = kops.resolve_impl(cfg.impl)
        # the degradation ladder (docs/robustness.md) rebuilds a faulty
        # pass from its checkpoint with these flags: force_host drops to
        # the per-round host oracle loop, force_unsharded keeps the
        # device loop but on a single device — both are existing oracle
        # paths, so every rung preserves soundness.
        self.force_host = bool(force_host)
        self.force_unsharded = bool(force_unsharded)
        self.device_pass = cfg.resolve_device_loop() and not force_host
        if cfg.shard_rows:
            cfg.resolve_shard_rows()  # loud guard, as in FastFrame.run
        # the sharded layout applies to the device pass loop only (the
        # host loop and the recovery pass materialize on host)
        self.shards = (frame.block_shards()
                       if self.device_pass and not force_unsharded
                       else None)
        self.chunk = (chunk_rounds if chunk_rounds is not None
                      else (cfg.sync_every or cfg.chunk_rounds))

        # wrap-filled order pad: the window slice at ``pos % nb`` is a
        # rotation of the scan order, so the pad never grows when late
        # admissions push the horizon past nb (static dispatch shapes
        # forever). For the non-wrap path the tail is invisible — the
        # in-range mask zeroes every lane past the cursor limit.
        rep = lambda a: adist.place_replicated(self.shards, a)
        self._rep = rep
        self.order_pad_dev = rep(kfused.rotation_order_pad(
            int(self.start) % self.nb, self.nb, self.window))
        self.mask_dev = None      # set on first admit (needs a query)
        self.static_ok_dev = None

        self.pos = 0              # cursor frontier: max over slot cursors
                                  # (anchors new admissions; each slot
                                  # advances its own _SlotExec.pos)
        self.rounds = 0
        self.n_live = 0
        self.wrap = False         # sticky: any slot anchored past 0
        self.slots: List[_SlotExec] = []
        self.finished: Dict[int, QueryResult] = {}  # id(qci) -> result
        self._qc_of: Dict[int, _QueryIntervals] = {}  # id(query) -> qci
        self._t0: Dict[int, float] = {}             # id(qci) -> t0
        self._rec_rounds: Dict[int, int] = {}       # id(slot) -> rounds
        # results restored from a checkpoint for queries whose slots no
        # longer exist (retired before the snapshot): id(query) -> result
        self._ext_results: Dict[int, QueryResult] = {}
        # per-slot kernel NaN sentinel from the last device chunk
        # (None on the host path; see quarantine())
        self._sentinel: Optional[Tuple[bool, ...]] = None

    # -- coordinates -----------------------------------------------------------

    def _rows_at(self, p: int) -> int:
        """Valid rows under pass-cursor positions ``[0, p)``. Rows are
        periodic in the lap length, so no extended prefix sums needed."""
        if p <= 0:
            return 0
        laps, rem = divmod(p - 1, self.nb)
        return laps * self.R_total + int(self.cum_rows[rem])

    @property
    def can_step(self) -> bool:
        """True while stepping can still progress some unfinished query
        (queries stuck active past their lap end wait for the recovery
        pass in :meth:`finish`)."""
        if self.rounds >= self.max_rounds or self.n_live == 0:
            return False
        return any(not qc.finished and s.pos < s.views.lap_end
                   for s in self.slots for qc in s.qcis)

    # -- admit -----------------------------------------------------------------

    def admit(self, queries: Sequence[AggQuery],
              t0: Optional[float] = None) -> List[_QueryIntervals]:
        """Admit queries at the current round boundary. Queries sharing a
        scan signature form one slot anchored at the current cursor
        position (merged into a same-signature slot created at this same
        boundary, if histogram needs allow). Returns the new
        :class:`~repro.aqp.engine._QueryIntervals` in input order."""
        frame = self.frame
        t0 = self.t0 if t0 is None else t0
        if (self.shards is not None and self.shards.merge_every > 1
                and (self.wrap or self.pos > 0)):
            # typed and raised BEFORE any state mutates: the scheduler
            # catches this and opens a fresh pass for the late joiner.
            # Plain sharded carousels compose (anchors are static in the
            # trace); only the collective cadence cannot host a mid-lap
            # joiner — its refresh schedule would be quantized to merge
            # boundaries, up to K rounds off its solo run's.
            raise UnsupportedPassConfig(
                "mid-scan admission (anchor > 0) is not supported on a "
                "sharded pass with merge_every > 1; admit to a fresh "
                "pass or run the frame at merge_every=1")
        for q in queries:
            if tuple(f.key() for f in q.filters) != tuple(
                    f.key() for f in self.filters):
                raise ValueError("query filters do not match this pass")
        by_sig: Dict[Tuple, List[AggQuery]] = {}
        for q in queries:
            by_sig.setdefault(q.scan_signature(), []).append(q)
        out_qcis: Dict[int, _QueryIntervals] = {}
        for sig, qs in by_sig.items():
            slot = next(
                (s for s in self.slots
                 if s.anchor == self.pos and s.join_round == self.rounds
                 and s.views.rep_q.scan_signature() == sig
                 and (s.views.use_hist
                      or not any(q.needs_hist for q in qs))),
                None)
            if slot is not None:
                new = [_QueryIntervals(frame, q, slot.views) for q in qs]
                slot.qcis.extend(new)
            else:
                slot = _SlotExec(
                    frame, qs[0], self.skipping, qs, self.window,
                    self.shards,
                    anchor=self.pos, join_round=self.rounds,
                    row_offset=self._rows_at(self.pos))
                if self.pos > 0:
                    self.wrap = True
                self.slots.append(slot)
                new = slot.qcis[-len(qs):]
            for q, qc in zip(qs, new):
                self._qc_of[id(q)] = qc
                self._t0[id(qc)] = t0
                out_qcis[id(q)] = qc
            self.n_live += len(qs)
        if self.mask_dev is None:
            self.mask_dev = frame._device_mask(queries[0].filters,
                                               self.shards)
            self.static_ok_dev = self._rep(kfused.pad_blocks(
                self.slots[0].views.static_ok, self.window))
        return [out_qcis[id(q)] for q in queries]

    # -- retire ----------------------------------------------------------------

    def retire(self) -> int:
        """Drop slots whose queries have all finished, freeing their fold
        width (and device dispatch shapes) for the next admission.
        Called by the scheduler at admission boundaries; ``run_batch``
        keeps its slots static."""
        keep = [s for s in self.slots
                if not all(id(qc) in self.finished for qc in s.qcis)]
        dropped = len(self.slots) - len(keep)
        self.slots = keep
        return dropped

    # -- fault tolerance: checkpoint / restore / freeze / quarantine -----------

    def checkpoint(self) -> PassCheckpoint:
        """Snapshot the complete pass state at the current round/chunk
        boundary (see :mod:`repro.serve.checkpoint`). Every boundary is
        fully merged, so restoring the snapshot and stepping forward is
        bitwise-identical to never having stopped."""
        slots = [SlotCheckpoint(
            queries=[qc.q for qc in s.qcis],
            anchor=s.anchor, join_round=s.join_round,
            row_offset=s.row_offset, lap_done_round=s.lap_done_round,
            metrics=dict(s.metrics),
            views=s.views.export_state(),
            qcs=[qc.export_state() for qc in s.qcis],
            pos=int(s.pos))
            for s in self.slots]
        results: Dict[int, QueryResult] = dict(self._ext_results)
        t0s: Dict[int, float] = {}
        for qid, qc in self._qc_of.items():
            t0s[qid] = self._t0[id(qc)]
            res = self.finished.get(id(qc))
            if res is not None:
                results[qid] = res
        return PassCheckpoint(
            filters=self.filters, sampling=self.sampling,
            start=int(self.start), max_rounds=self.max_rounds,
            pos=self.pos, rounds=self.rounds, n_live=self.n_live,
            wrap=self.wrap, slots=slots, results=results, t0s=t0s)

    def restore(self, cp: PassCheckpoint) -> None:
        """Restore this pass in place from a checkpoint. The pass must
        have been opened with the checkpoint's filters/sampling/start
        (see :meth:`FrameServer.resume_pass`); slot execution state is
        rebuilt from scratch (device buffers re-materialize through the
        frame's caches) and the exported fold/interval state imported
        over it."""
        if tuple(f.key() for f in cp.filters) != tuple(
                f.key() for f in self.filters):
            raise ValueError("checkpoint filters do not match this pass")
        if int(cp.start) != int(self.start) or cp.sampling != \
                self.sampling:
            raise ValueError("checkpoint scan order does not match this "
                             "pass (start/sampling differ)")
        if (cp.wrap and self.shards is not None
                and self.shards.merge_every > 1):
            raise UnsupportedPassConfig(
                "cannot restore a carousel (wrapped) checkpoint onto a "
                "sharded pass with merge_every > 1; resume with "
                "force_unsharded/force_host or merge_every=1")
        self.pos, self.rounds = int(cp.pos), int(cp.rounds)
        self.wrap = bool(cp.wrap)
        self.slots = []
        self.finished = {}
        self._qc_of = {}
        self._t0 = {}
        self._rec_rounds = {}
        self._ext_results = {}
        self._sentinel = None
        frame = self.frame
        for sc in cp.slots:
            slot = _SlotExec(frame, sc.queries[0], self.skipping,
                             sc.queries, self.window, self.shards,
                             anchor=sc.anchor,
                             join_round=sc.join_round,
                             row_offset=sc.row_offset)
            slot.lap_done_round = sc.lap_done_round
            slot.metrics = dict(sc.metrics)
            # pre-per-slot-cursor snapshots carry no slot pos: fall back
            # to the shared cursor clamped to the slot's lap end, which
            # is where the shared-cursor loop had this slot
            slot.pos = (int(sc.pos) if sc.pos is not None
                        else min(int(cp.pos), slot.views.lap_end))
            slot.views.import_state(sc.views)
            for qc, snap in zip(slot.qcis, sc.qcs):
                qc.import_state(snap)
            self.slots.append(slot)
            for q, qc in zip(sc.queries, slot.qcis):
                self._qc_of[id(q)] = qc
                self._t0[id(qc)] = cp.t0s.get(id(q), self.t0)
                if id(q) in cp.results:
                    self.finished[id(qc)] = cp.results[id(q)]
        live_ids = {id(q) for s in cp.slots for q in s.queries}
        for qid, res in cp.results.items():
            if qid not in live_ids:
                self._ext_results[qid] = res
        self.n_live = sum(1 for s in self.slots for qc in s.qcis
                          if not qc.finished)
        if self.slots and self.mask_dev is None:
            self.mask_dev = frame._device_mask(
                self.slots[0].qcis[0].q.filters, self.shards)
            self.static_ok_dev = self._rep(kfused.pad_blocks(
                self.slots[0].views.static_ok, self.window))

    def freeze_partial(self, q: AggQuery) -> QueryResult:
        """Finalize ``q`` NOW from its current interval state: the
        anytime-valid CI at any round boundary is a sound answer, so a
        deadline-expired or ladder-exhausted query returns its current
        (wider) interval as a partial-with-guarantee result instead of
        being dropped. Idempotent for already-finished queries."""
        qc = self._qc_of[id(q)]
        if id(qc) in self.finished:
            return self.finished[id(qc)]
        s = next(s for s in self.slots if qc in s.qcis)
        le = s.views.lap_end
        k_s = max(self.rounds - s.join_round, 0)
        r_s = self._rows_at(min(s.pos, le)) - s.row_offset
        res = qc.result(k_s, s.pos, self.cum_rows, dict(s.metrics),
                        self._t0[id(qc)], stopped_early=True,
                        rows_covered=r_s)
        qc.finished = True
        qc.active = np.zeros_like(qc.active)
        self.finished[id(qc)] = res
        self.n_live -= 1
        return res

    def quarantine(self) -> List[AggQuery]:
        """Evict poisoned slots at the current round boundary: a slot
        whose fold state or query intervals went NaN (detected by the
        kernel sentinel on the device path, or
        :func:`~repro.core.state.moments_nonfinite` on host state) is
        dropped whole, its unfinished queries returned for the caller to
        fail/quarantine. Results snapshotted BEFORE the poison appeared
        stay valid and are kept; NaN-tainted snapshots are discarded.
        Co-resident slots are untouched — slot membership independence
        means their folds never saw the poison, so survivors stay
        bitwise-identical to a run that never admitted the poison
        query."""
        evicted: List[AggQuery] = []
        keep: List[_SlotExec] = []
        for i, s in enumerate(self.slots):
            poison = (self._sentinel is not None
                      and i < len(self._sentinel)
                      and bool(self._sentinel[i]))
            poison = poison or moments_nonfinite(
                s.views.state,
                s.views.hist if s.views.use_hist else None)
            if not poison:
                poison = any(
                    np.isnan(qc.lo).any() or np.isnan(qc.hi).any()
                    or np.isnan(qc.est).any() for qc in s.qcis)
            if not poison:
                keep.append(s)
                continue
            for qc in s.qcis:
                res = self.finished.get(id(qc))
                if res is not None:
                    if (np.isnan(res.lo).any() or np.isnan(res.hi).any()
                            or np.isnan(res.estimate).any()):
                        del self.finished[id(qc)]
                        evicted.append(qc.q)
                    continue
                qc.finished = True
                self.n_live -= 1
                evicted.append(qc.q)
        self.slots = keep
        self._sentinel = None
        return evicted

    # -- step ------------------------------------------------------------------

    def step(self) -> List[AggQuery]:
        """Advance the pass one round (host loop) or one dispatch chunk
        (device loop); returns the queries that finished during it."""
        if self.device_pass:
            return self._device_step(until_done=False)
        return self._step_host()

    def run_to_completion(self) -> None:
        """Step until no unfinished query can progress (static-batch
        driver; the device path keeps its carry resident across chunk
        dispatches and writes back once, exactly the ``run_batch``
        behavior)."""
        if self.device_pass:
            self._device_step(until_done=True)
        else:
            while self.can_step:
                self._step_host()

    def _step_host(self) -> List[AggQuery]:
        frame = self.frame
        cfg = self.cfg
        self._sentinel = None  # host path: quarantine inspects views
        self.rounds += 1
        # frozen slots — lapped, or every query finished — must not
        # advance (their solo twin exited its loop; a finished slot's
        # empty flags would cover ground without selecting). The jitted
        # round computes all S slots (static shapes); frozen slots'
        # outputs are simply discarded.
        live = [s.pos < s.views.lap_end
                and any(not qc.finished for qc in s.qcis)
                for s in self.slots]
        stacks = tuple(s.active_stack() for s in self.slots)
        pos_vec = jnp.asarray([s.pos for s in self.slots], jnp.int32)
        states, hists, flag_stacks, oks, new_pos_d = \
            kfused.fused_round_multi(
                self.mask_dev, self.order_pad_dev, self.static_ok_dev,
                pos_vec,
                tuple(s.values for s in self.slots),
                tuple(s.gids for s in self.slots),
                tuple(s.words for s in self.slots), stacks,
                nb=self.nb, window=self.window,
                budget=cfg.round_blocks,
                meta=tuple(s.meta for s in self.slots), impl=self.impl,
                anchors=jnp.asarray([s.anchor for s in self.slots],
                                    jnp.int32))
        new_pos_v = np.asarray(new_pos_d)
        newly: List[AggQuery] = []
        for i, (s, st, h) in enumerate(zip(self.slots, states, hists)):
            if not live[i]:
                continue
            le = s.views.lap_end
            pos0 = s.pos
            new_pos = int(new_pos_v[i])
            ok = np.asarray(oks[i])
            flags = np.asarray(flag_stacks[i]).any(axis=0)
            idx = frame._fused_accounting(
                self.order, pos0, new_pos, ok, flags, s.views.presence,
                s.views.tainted, self.lookahead, cfg.round_blocks,
                self.cover_cap, s.probe, s.metrics, lap_end=le)
            if len(idx):
                s.views.ingest_delta(idx, st, h)
            s.views.update_exact(new_pos)
            s.pos = new_pos
            if new_pos >= le and s.lap_done_round is None:
                s.lap_done_round = self.rounds
            k_s = self.rounds - s.join_round
            r_s = self._rows_at(min(new_pos, le)) - s.row_offset
            for qc in s.qcis:
                if qc.finished:
                    continue
                qc.refresh(k_s, r_s)
                if not qc.update_active():
                    qc.finished = True
                    self.n_live -= 1
                    self.finished[id(qc)] = qc.result(
                        k_s, new_pos, self.cum_rows, dict(s.metrics),
                        self._t0[id(qc)], stopped_early=new_pos < le,
                        rows_covered=r_s)
                    newly.append(qc.q)
        self.pos = max([self.pos] + [s.pos for s in self.slots])
        return newly

    # -- finish ----------------------------------------------------------------

    def finish(self) -> None:
        """Recovery per slot for queries that exhausted their lap while
        still active (shared block fetches across the slot's queries),
        then assemble their results. Idempotent per slot."""
        frame = self.frame
        for s in self.slots:
            rec = [qc for qc in s.qcis if not qc.finished]
            if rec and id(s) not in self._rec_rounds:
                base = (s.lap_done_round - s.join_round
                        if s.lap_done_round is not None
                        else self.rounds - s.join_round)
                self._rec_rounds[id(s)] = frame._recovery_pass(
                    s.views, rec, base, self.max_rounds)
            for qc in s.qcis:
                if id(qc) in self.finished:
                    continue
                qc.collapse_exact()
                le = s.views.lap_end
                r_s = self._rows_at(min(s.pos, le)) - s.row_offset
                local = self._rec_rounds.get(
                    id(s), self.rounds - s.join_round)
                self.finished[id(qc)] = qc.result(
                    local, s.pos, self.cum_rows, s.metrics,
                    self._t0[id(qc)], False, rows_covered=r_s)
                qc.finished = True

    def result_of(self, q: AggQuery) -> QueryResult:
        qc = self._qc_of.get(id(q))
        if qc is not None and id(qc) in self.finished:
            return self.finished[id(qc)]
        # restored from a checkpoint after the query's slot retired
        return self._ext_results[id(q)]

    # -- device-resident stepping ----------------------------------------------

    def _device_step(self, until_done: bool) -> List[AggQuery]:
        """Run the pass's round loop device-resident
        (:func:`repro.kernels.fused_scan.build_pass_loop`).

        ``until_done=True`` keeps the carry device-resident across chunk
        dispatches and writes back once (the ``run_batch`` whole-pass
        behavior). ``until_done=False`` runs ONE chunk dispatch and
        writes the carry back to host so admission/retirement can change
        the slot membership before the next step; the loop is rebuilt
        (and LRU-cached) per membership epoch — anchors and round
        offsets are static in the trace."""
        frame = self.frame
        cfg = self.cfg
        nb = self.nb
        slots = self.slots
        shards = self.shards
        f64 = lambda x: jnp.asarray(x, jnp.float64)
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        i64 = lambda v: jnp.asarray(v, jnp.int64)
        rep = self._rep

        # the compiled pass loop (+ its order-independent device buffers)
        # is cached on the frame by the pass's static identity: repeat
        # batches / epochs reuse the traced lax.while_loop
        key = ("pass",
               tuple((qc.q.scan_signature(), qc.q.agg, qc.q.bounder,
                      qc.q.rangetrim, qc.q.delta, repr(qc.q.stop))
                     for s in slots for qc in s.qcis),
               tuple((len(s.qcis), s.probe, s.views.use_hist)
                     for s in slots),
               self.lookahead, self.max_rounds, self.chunk,
               (shards.n_shards, shards.shard_rows, shards.merge_every)
               if shards is not None else None,
               tuple(s.anchor for s in slots),
               tuple(s.join_round for s in slots))

        def build():
            slot_specs = tuple(
                kfused.SlotSpec(
                    num_groups=s.views.G, nbins=cfg.hist_bins,
                    use_hist=s.views.use_hist, a=float(s.views.a),
                    b=float(s.views.b), center=float(s.views.center),
                    probe=s.probe, n_words=int(s.words.shape[1]))
                for s in slots)
            refresh_fns = tuple(
                tuple(_make_device_refresh(qc.q, qc, s.views.a,
                                           s.views.b, qc.use_hist,
                                           float(qc.R), s.views.valid)
                      for qc in s.qcis)
                for s in slots)
            chunk_fn = kfused.build_pass_loop(
                nb=nb, window=self.window, budget=cfg.round_blocks,
                impl=self.impl, lookahead=self.lookahead,
                cover_cap=self.cover_cap, max_rounds=self.max_rounds,
                chunk=self.chunk, slot_specs=slot_specs,
                refresh_fns=refresh_fns,
                shard=shards.info if shards is not None else None,
                anchors=tuple(s.anchor for s in slots),
                round_offsets=tuple(s.join_round for s in slots),
                row_offsets=tuple(s.row_offset for s in slots))
            presence = tuple(rep(kfused.pad_blocks(s.views.presence,
                                                   self.window))
                             for s in slots)
            presence_total = tuple(
                rep(s.views.presence_total.astype(np.int32))
                for s in slots)
            return chunk_fn, presence, presence_total

        chunk_fn, presence_t, presence_total_t = \
            frame.device_loops.get_or_build(key, build)

        bufs = kfused.PassLoopBuffers(
            mask=self.mask_dev, order_pad=self.order_pad_dev,
            static_ok=self.static_ok_dev,
            cum_rows=rep(self.cum_rows.astype(np.int64)),
            values=tuple(s.values for s in slots),
            gids=tuple(s.gids for s in slots),
            words=tuple(s.words for s in slots),
            presence=presence_t, presence_total=presence_total_t)
        cadence = shards is not None and shards.merge_every > 1

        def _slot_pend(s):
            # collective-cadence pending slots: empty local delta
            if not cadence:
                return {}
            G = s.views.G
            return dict(
                pend_sums=jnp.zeros((3, G), jnp.float64),
                pend_vmin=jnp.full((G,), np.inf, jnp.float64),
                pend_vmax=jnp.full((G,), -np.inf, jnp.float64),
                pend_hist=(jnp.zeros((G, cfg.hist_bins), jnp.float64)
                           if s.views.use_hist else None))

        # per-slot cursor + coverage/metrics, held ABSOLUTE in the carry
        # (initialized from host state, written back as-is)
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
                **_slot_pend(s))
            for s in slots)
        query_carries = tuple(
            tuple(kfused.PassQueryCarry(
                lo=f64(qc.lo), hi=f64(qc.hi), est=f64(qc.est),
                refreshed=jnp.asarray(qc.refreshed),
                active=jnp.asarray(qc.active
                                   & ~np.asarray(qc.finished)),
                finished=jnp.asarray(bool(qc.finished)),
                stopped_early=jnp.asarray(False),
                finish_rounds=i32(0), finish_pos=i32(0),
                finish_blocks_fetched=i64(0),
                finish_skipped_static=i64(0),
                finish_skipped_active=i64(0), finish_probes=i64(0),
                snap_counts=jnp.zeros(s.views.G, jnp.float64),
                snap_exact=jnp.zeros(s.views.G, bool),
                snap_tainted=jnp.zeros(s.views.G, bool))
                for qc in s.qcis)
            for s in slots)
        pend = dict(pend_rounds=i32(0)) if cadence else {}
        carry = kfused.PassCarry(
            rounds=i32(self.rounds), it=i32(0),
            n_live=i32(self.n_live),
            slots=slot_carries, queries=query_carries, **pend)

        while True:
            carry = chunk_fn(bufs, carry)
            if not until_done:
                break
            if (int(carry.n_live) == 0
                    or int(carry.rounds) >= self.max_rounds):
                break
            progressable = any(
                int(sc.pos) < s.views.lap_end
                and any(not bool(qcar.finished) for qcar in qcars)
                for s, sc, qcars in zip(slots, carry.slots,
                                        carry.queries))
            if not progressable:
                break

        # kernel-layer NaN sentinel: per-slot poison flags over the
        # fetched carry, consumed by quarantine() at this boundary
        self._sentinel = kfused.carry_nonfinite_slots(carry)

        # -- writeback: slots' cursor + shared fold state + metrics -------
        self.rounds = int(carry.rounds)
        self.n_live = int(carry.n_live)
        host = _host_copy
        for s, scarry in zip(slots, carry.slots):
            _restore_views_from_carry(
                s.views, scarry.state, scarry.hist, scarry.processed,
                scarry.seen_presence, scarry.tainted, scarry.exact,
                scarry.blocks_fetched, s.metrics, 0, 0)
            s.metrics["skipped_static"] = int(scarry.skipped_static)
            s.metrics["skipped_active"] = int(scarry.skipped_active)
            s.metrics["probes"] = int(scarry.probes)
            s.pos = int(scarry.pos)
            if (s.pos >= s.views.lap_end
                    and s.lap_done_round is None):
                s.lap_done_round = int(scarry.lap_rounds)
        self.pos = max([self.pos] + [s.pos for s in slots])

        # -- per-query interval state + finish-time snapshot results ------
        newly: List[AggQuery] = []
        for s, qcarries in zip(slots, carry.queries):
            le = s.views.lap_end
            for qc, qcar in zip(s.qcis, qcarries):
                if id(qc) in self.finished:
                    continue  # result already materialized; carry frozen
                qc.lo = host(qcar.lo, np.float64)
                qc.hi = host(qcar.hi, np.float64)
                qc.est = host(qcar.est, np.float64)
                qc.refreshed = host(qcar.refreshed)
                qc.active = host(qcar.active)
                qc.finished = bool(qcar.finished)
                if not qc.finished:
                    continue
                snap_counts = host(qcar.snap_counts, np.float64)
                fpos = int(qcar.finish_pos)
                rows_cov = self._rows_at(min(fpos, le)) - s.row_offset
                skipped_static = int(qcar.finish_skipped_static)
                skipped_active = int(qcar.finish_skipped_active)
                probes = int(qcar.finish_probes)
                self.finished[id(qc)] = QueryResult(
                    group_codes=np.arange(s.views.G),
                    estimate=host(qcar.est, np.float64),
                    lo=host(qcar.lo, np.float64),
                    hi=host(qcar.hi, np.float64),
                    count_seen=snap_counts,
                    nonempty=snap_counts > 0,
                    exact=host(qcar.snap_exact),
                    tainted=host(qcar.snap_tainted),
                    rows_covered=rows_cov,
                    blocks_fetched=int(qcar.finish_blocks_fetched),
                    blocks_skipped_active=skipped_active,
                    blocks_skipped_static=skipped_static,
                    bitmap_probes=probes,
                    rounds=int(qcar.finish_rounds),
                    wall_time_s=(time.perf_counter()
                                 - self._t0[id(qc)]),
                    stopped_early=bool(qcar.stopped_early))
                newly.append(qc.q)
        return newly


class FrameServer:
    """Serve batches of :class:`~repro.aqp.query.AggQuery` over one
    :class:`~repro.aqp.engine.FastFrame` with shared fused-scan passes.

    Example::

        server = FrameServer(frame)
        results = server.run_batch([q1, q2, q3])   # one scan, 3 answers

    The server is stateless between batches except for the device
    materialization caches it shares with the frame, so it is safe to
    interleave ``run_batch`` with direct ``frame.run`` calls. For
    continuous serving, :meth:`open_pass` exposes the incremental
    :class:`SharedPass` lifecycle used by
    :class:`repro.serve.scheduler.QueryScheduler`.
    """

    def __init__(self, frame: FastFrame):
        self.frame = frame

    # -- planning --------------------------------------------------------------

    def plan(self, queries: Sequence[AggQuery]
             ) -> Dict[Tuple, List[int]]:
        """Group query indices into shared-scan passes by filters key.
        Exposed for tests/benchmarks; ``run_batch`` uses the same
        grouping."""
        passes: Dict[Tuple, List[int]] = {}
        for i, q in enumerate(queries):
            pkey = tuple(f.key() for f in q.filters)
            passes.setdefault(pkey, []).append(i)
        return passes

    def open_pass(self, filters, sampling: str = "active_peek",
                  start_block: Optional[int] = None, seed: int = 0,
                  max_rounds: int = 100_000,
                  chunk_rounds: Optional[int] = None) -> SharedPass:
        """Open an incremental shared pass for queries with ``filters``
        (admit/step/retire/finish lifecycle; see :class:`SharedPass`)."""
        return SharedPass(self.frame, filters, sampling, start_block,
                          seed, max_rounds, chunk_rounds)

    def resume_pass(self, cp: PassCheckpoint,
                    chunk_rounds: Optional[int] = None,
                    force_host: bool = False,
                    force_unsharded: bool = False) -> SharedPass:
        """Rebuild a pass from a :class:`~repro.serve.checkpoint.
        PassCheckpoint` — the retry path after a fault, and (with the
        ``force_*`` flags or a smaller ``chunk_rounds``) the degradation
        ladder's rung changes. The resumed pass answers ``result_of``
        for the same query objects and, under the same config, steps
        bitwise-identically to the uninterrupted original."""
        p = SharedPass(self.frame, cp.filters, cp.sampling,
                       start_block=int(cp.start), seed=0,
                       max_rounds=cp.max_rounds,
                       chunk_rounds=chunk_rounds,
                       force_host=force_host,
                       force_unsharded=force_unsharded)
        p.restore(cp)
        return p

    def run_batch(self, queries: Sequence[AggQuery],
                  sampling: str = "active_peek",
                  start_block: Optional[int] = None, seed: int = 0,
                  max_rounds: int = 100_000) -> List[QueryResult]:
        """Answer every query, sharing scans where signatures allow.

        Args mirror :meth:`FastFrame.run`; all queries of a batch use the
        same sampling strategy and scan start (queries are only merged
        into a pass when they share filters, and only into a slot when
        their full scan signature matches). Exact-mode queries
        (``sampling='exact'`` or ``stop is None``) cannot share a
        budgeted cursor walk and are delegated to ``frame.run``.

        Returns results in input order.
        """
        results: List[Optional[QueryResult]] = [None] * len(queries)
        shared: List[int] = []
        for i, q in enumerate(queries):
            if sampling == "exact" or q.stop is None:
                results[i] = self.frame.run(
                    q, sampling=sampling, start_block=start_block,
                    seed=seed, max_rounds=max_rounds)
            else:
                shared.append(i)
        for pkey, members in self.plan(
                [queries[i] for i in shared]).items():
            idxs = [shared[m] for m in members]
            out = self._run_pass([queries[i] for i in idxs], sampling,
                                 start_block, seed, max_rounds)
            for i, res in zip(idxs, out):
                results[i] = res
        return results

    # -- one shared pass (static batch) ----------------------------------------

    def _run_pass(self, queries: Sequence[AggQuery], sampling: str,
                  start_block: Optional[int], seed: int,
                  max_rounds: int) -> List[QueryResult]:
        """Static-batch pass: admit everything at cursor position 0,
        run to completion, recover, assemble — computation-for-
        computation the pre-lifecycle pass (bitwise-identical
        results)."""
        p = SharedPass(self.frame, queries[0].filters, sampling,
                       start_block, seed, max_rounds)
        p.admit(queries)
        p.run_to_completion()
        p.finish()
        return [p.result_of(q) for q in queries]
