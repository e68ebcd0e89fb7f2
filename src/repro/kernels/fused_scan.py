"""Fused Pallas scan superkernel: one device dispatch per OptStop round.

The engine's per-round scan work used to be three separate dispatches with
host round-trips between them: the (group-bitmap AND active-mask) activity
probe (``bitmap_active``), the grouped-moment fold (``block_agg``) and the
per-group histogram update (``hist``), glued together by a Python loop
that walked the scramble block-batch by block-batch. :func:`fused_round`
fuses the whole round — cursor window slice, activity test, budgeted
block selection, device-side gather, moment fold and histogram fold —
into a single jitted computation over *device-resident* column data, so
the host syncs exactly once per round (to fetch the mergeable deltas and
the per-position flags it needs for soundness bookkeeping).

Pipeline (all on device)::

    order[pos : pos+window] ──> static_ok ──┐
    bitmap.words[window]  ──ActiveTest──────┴─> flags ──cumsum──> take mask
                                                           │         │
                                                      new_pos   gather blocks
                                                                     │
                                     MomentState delta  <──fold──────┤
                                     hist delta         <──fold──────┘

Selection reproduces the reference cursor semantics bit-for-bit: the round
takes the first ``budget`` blocks whose static prefilter AND activity test
pass, and the cursor stops just past the budget-th selected block (or at
the window end).  The fold then sees exactly the rows the per-block
reference path would fold, in the same order, so moment/histogram deltas
are bitwise identical (padding lanes carry ``mask == 0`` and contribute
exact zeros).

:func:`fused_round_multi` generalizes the round to a *batch* of queries
sharing one cursor walk (the :class:`repro.serve.FrameServer` serving
path): per-query active-word stacks drive the activity test, selection
takes the union across queries, and each distinct (column, group-by)
slot folds its own moment/histogram state from the shared gather — still
one device dispatch and one host sync per round for the whole batch.

**Device-resident round loop** (``EngineConfig(device_loop=True)``):
:func:`build_query_loop` / :func:`build_pass_loop` go one step further
and remove the per-round host sync entirely. The whole OptStop round —
the :func:`fused_round` scan/fold, the float64 running-state merge, the
skip/taint/coverage accounting, the device CI refresh (the ``*_device``
bounder twins from :mod:`repro.core.bounders`) and the jittable stopping
conditions — runs inside one ``lax.while_loop`` whose carry holds every
piece of state the host loop used to keep in numpy. A dispatch executes
up to ``chunk`` rounds (``None`` = until stop or exhaustion); the host
syncs only between dispatches (one scalar pull) and once at termination
to read the final carry back into the engine's bookkeeping. Requires
64-bit JAX types (:func:`repro.core.state.require_x64`): the carry's
running moments, intervals and CI math are float64, exactly like the
host loop they replace.

**Sharded round loop** (``EngineConfig(shard_rows=True)`` /
:class:`ShardInfo`): the same loops run under ``shard_map`` over a
device mesh with the scan *divided*. The within-block row axis of the
value/group/mask slabs is sliced into ``n_shards`` equal pieces (the
block axis stays whole on every device), so the round body each shard
traces is literally the unsharded round body applied to its own
``block_rows / n_shards`` row slice — each shard gathers and folds only
``1/n_shards`` of every selected block's rows. Selection, the cursor,
coverage/taint accounting and the bound evaluation are replicated
computations over replicated inputs, so every scan decision is
identical on every device and identical to the single-device loop; each
merge's fold delta is the only thing that crosses the mesh (``psum`` of
the raw additive (count, dsum, dsq) sums + ``pmin``/``pmax`` extremes +
``psum`` histogram inside :func:`_fold` — O(groups) bytes, zero host
syncs). On a collective cadence (``merge_every=K``) the merge fires on
a deterministic replicated round counter, so between merges there is
*zero* cross-shard communication — no per-round rendezvous at all. See
``docs/architecture.md`` ("Dividing the scan across a mesh").

Backends (same selector as :mod:`repro.kernels.ops`):

  * ``impl='ref'``       — the fold reuses the pure-jnp oracles (XLA
    fuses the whole round into one CPU computation; default off-TPU);
  * ``impl='pallas'``    — :func:`fused_fold`, a single ``pallas_call``
    whose grid revisits each group tile across row tiles; Pallas's
    pipeline machinery double-buffers the HBM->VMEM tile copies so the
    moment + histogram matmuls of row tile ``r`` overlap the copy-in of
    row tile ``r+1`` (one double-buffered pass over block data);
  * ``impl='interpret'`` — the same superkernel under the Pallas
    interpreter (CPU-testable).

VMEM at the defaults (ROW_TILE=1024, GROUP_TILE=128): a v5e compile of
:func:`fused_fold` accepts 3.6-4.1 MiB of scoped VMEM at 1024 bins and
4.6 MiB at 2048 (``docs/kernels.md``), under the 16 MiB scoped default.

**Named scopes** (:data:`SCOPES`): the shared round helpers below wrap
their work in ``jax.named_scope``, so every operation of a round loop
carries its phase in its HLO ``op_name`` and a profiler trace can put
each device operation under one of them: ``select`` (window slice,
activity probe, budgeted selection), ``gather`` (block ids and the three
slab gathers), ``fold`` (the moment / histogram kernels), ``merge`` (the
f64 running-state merge, the histogram add, the cross-shard
collectives), ``account`` (taint, coverage and scan metrics) and
``refresh`` (CI refresh and the stopping test). Scopes are metadata
only: they change neither the computation nor its results.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.state import MomentState, merge_moments
from repro.kernels import bitmap_active as _bitmap
from repro.kernels import block_agg as _block_agg
from repro.kernels import hist as _hist
from repro.kernels import ops as kops

ROW_TILE = 1024   # rows per grid step (multiple of 128)
GROUP_TILE = 128  # groups per grid step (multiple of 128)
SCOPES = ("select", "gather", "fold", "merge", "account", "refresh")


def _fold_kernel(scale_ref, values_ref, gids_ref, mask_ref,
                 sums_ref, vmin_ref, vmax_ref, hist_ref):
    """Moments + histogram in one pass: the transposed group one-hot
    ``(Gt, R)`` is built once per (group, row) tile and feeds both MXU
    matmuls."""
    r = pl.program_id(1)
    g = pl.program_id(0)
    gt = sums_ref.shape[1]
    kt = hist_ref.shape[1]

    c = scale_ref[0, 0]
    a = scale_ref[0, 1]
    inv_width = scale_ref[0, 2]
    nbins_data = scale_ref[0, 3]

    v = values_ref[...]
    m = mask_ref[...].astype(jnp.float32)
    partial, vmin_p, vmax_p, onehot_g = _block_agg.tile_moments(
        v, gids_ref[...], m, c, g * gt, gt)
    hpartial = _hist.tile_hist(v, onehot_g, a, inv_width, nbins_data, 0, kt)

    @pl.when(r == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        vmin_ref[...] = jnp.full_like(vmin_ref, jnp.inf)
        vmax_ref[...] = jnp.full_like(vmax_ref, -jnp.inf)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    sums_ref[...] += partial
    vmin_ref[...] = jnp.minimum(vmin_ref[...], vmin_p)
    vmax_ref[...] = jnp.maximum(vmax_ref[...], vmax_p)
    hist_ref[...] += hpartial


@functools.partial(jax.jit, static_argnames=(
    "a", "b", "num_groups", "nbins", "row_tile", "group_tile", "interpret"))
def fused_fold(values: jax.Array, gids: jax.Array, mask: jax.Array,
               center: jax.Array, *, a: float, b: float, num_groups: int,
               nbins: int, row_tile: int = ROW_TILE,
               group_tile: int = GROUP_TILE, interpret: bool = False):
    """Raw fused moment+histogram launch over 1-D padded inputs
    (``values.shape[0] % row_tile == 0``, ``num_groups % group_tile == 0``,
    ``nbins`` a multiple of 128; padding rows carry ``mask == 0``).

    Returns ``(sums (3, G), vmin (1, G), vmax (1, G), hist (G, nbins))``.
    Grid = (group_tiles, row_tiles), row minor: each (group, bin) output
    block is revisited across row tiles and accumulated in place while
    the pipeline prefetches the next row tile (double buffering). Row
    tiles are ``(1, row_tile)`` lane vectors (the layout of
    :mod:`repro.kernels.block_agg`).
    """
    n = values.shape[0]
    assert n % row_tile == 0 and num_groups % group_tile == 0
    assert nbins % 128 == 0
    v2 = values.astype(jnp.float32).reshape(1, n)
    g2 = gids.astype(jnp.int32).reshape(1, n)
    m2 = mask.astype(jnp.float32).reshape(1, n)
    grid = (num_groups // group_tile, n // row_tile)
    inv_width = float(nbins) / max(float(b) - float(a), 1e-30)
    scale = jnp.stack([jnp.asarray(center, jnp.float32),
                       jnp.asarray(a, jnp.float32),
                       jnp.asarray(inv_width, jnp.float32),
                       jnp.asarray(float(nbins), jnp.float32)]).reshape(1, 4)
    bi = _block_agg.block_index
    row_spec = pl.BlockSpec((1, row_tile), lambda g, r: bi(0, r))
    col_spec = pl.BlockSpec((group_tile, 1), lambda g, r: bi(g, 0))

    sums, vmin, vmax, hist = pl.pallas_call(
        _fold_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 4), lambda g, r: bi(0, 0)),
                  row_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((3, group_tile), lambda g, r: bi(0, g)),
            col_spec, col_spec,
            pl.BlockSpec((group_tile, nbins), lambda g, r: bi(g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((3, num_groups), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, nbins), jnp.float32),
        ],
        interpret=interpret,
        name="fused_fold",
    )(scale, v2, g2, m2)
    return (sums, vmin.reshape(1, num_groups), vmax.reshape(1, num_groups),
            hist)


def _pad_groups(x, mult):
    pad = (-x) % mult
    return x + pad


class ShardInfo(NamedTuple):
    """Mesh geometry of the divided scan (see ``docs/architecture.md``
    and :mod:`repro.aqp.distributed`, which constructs these).

    The within-block row axis of the scramble's device-resident columns
    is sharded over every mesh axis in ``axes`` (flattened): shard ``d``
    owns rows ``[d * shard_rows, (d+1) * shard_rows)`` of EVERY block,
    with the row axis zero-padded so every device holds an equal-shape
    slab (padding rows carry ``mask == 0`` / ``values == 0`` /
    ``gids == 0`` and contribute exact zeros to the additive fold). The
    block axis is whole on every shard, so global block ids index the
    local slab directly — the gather needs no shard-local translation
    and each shard materializes only its ``1/n_shards`` row slice of the
    selection."""

    mesh: Mesh
    axes: Tuple[str, ...]
    n_shards: int
    shard_rows: int     # padded per-shard rows per block (equal on all)
    merge_every: int = 1  # collective cadence K: rounds between full
                          # psum/pmin/pmax merges (1 = merge every round,
                          # the bitwise oracle path)


def _fold_local(v, g, m, center, a, b, num_groups, nbins, use_hist, impl):
    """This device's raw additive fold of one round's rows: ``(sums
    (3, G), vmin (1, G), vmax (1, G), hist (G, nbins) | None)`` about
    ``center``, BEFORE any cross-shard merge or shifted-moment
    conversion. The additive form is what crosses the mesh (``psum`` /
    ``pmin`` / ``pmax``) — either per round inside :func:`_fold` or, on
    a collective cadence, accumulated in the loop carry's f64 pending
    slots and merged every ``ShardInfo.merge_every`` rounds."""
    with jax.named_scope("fold"):
        if impl == "ref" or not use_hist:
            # No histogram: the plain block_agg kernel already is the
            # fused moment pass; ref: XLA segment ops (bitwise-identical to
            # the per-block reference path, which calls the same
            # functions).
            sums, vmin, vmax = kops.grouped_sums(v, g, m, num_groups,
                                                 center, impl=impl)
            hist = None
            if use_hist:
                hist = kops.grouped_hist(v, g, m, num_groups, a, b,
                                         nbins=nbins, impl=impl).hist
        else:
            gpad = _pad_groups(num_groups, GROUP_TILE)
            kpad = _pad_groups(nbins, 128)
            n = v.shape[0]
            rpad = (-n) % ROW_TILE
            if rpad:
                v = jnp.concatenate([v, jnp.zeros(rpad, v.dtype)])
                g = jnp.concatenate([g, jnp.zeros(rpad, g.dtype)])
                m = jnp.concatenate([m, jnp.zeros(rpad, m.dtype)])
            sums, vmin, vmax, hist = fused_fold(
                v, g, m, jnp.asarray(center, jnp.float32), a=a, b=b,
                num_groups=gpad, nbins=kpad,
                interpret=(impl == "interpret"))
            sums = sums[:, :num_groups]
            vmin = vmin[:, :num_groups]
            vmax = vmax[:, :num_groups]
            hist = hist[:num_groups, :nbins]
        return sums, vmin, vmax, hist


def _fold(v, g, m, center, a, b, num_groups, nbins, use_hist, impl,
          shard_axes: Optional[Tuple[str, ...]] = None):
    """Dispatch one round's fold: ref oracle or the fused superkernel.

    With ``shard_axes`` the caller is inside ``shard_map`` and ``v/g/m``
    are this device's slice of the round's rows: the raw additive sums
    (count, dsum, dsq about ``center``) merge across the mesh with one
    ``psum`` and the extremes with ``pmin``/``pmax`` BEFORE the
    shifted-moment conversion, so the merged state is the single-device
    fold up to a reordering of the row sum (bitwise equal whenever the
    per-shard partials are exactly representable)."""
    sums, vmin, vmax, hist = _fold_local(v, g, m, center, a, b,
                                         num_groups, nbins, use_hist,
                                         impl)
    if shard_axes:
        # one collective set per round: O(groups) bytes across the mesh
        with jax.named_scope("merge"):
            sums = jax.lax.psum(sums, shard_axes)
            vmin = jax.lax.pmin(vmin, shard_axes)
            vmax = jax.lax.pmax(vmax, shard_axes)
            if hist is not None:
                hist = jax.lax.psum(hist, shard_axes)
    with jax.named_scope("fold"):
        return kops.moments_from_sums(sums, vmin, vmax, center), hist


def _pmin_pmax_f64(vmin, vmax, axes):
    """Cross-shard min / max of the f64 pending extremes. XLA:TPU lowers
    only the sum all-reduce for f64, so every shard gathers all shards'
    values and reduces them itself: exact, and the same on every
    shard."""
    return (jax.lax.all_gather(vmin, axes).min(axis=0),
            jax.lax.all_gather(vmax, axes).max(axis=0))


def _budget_select(flags: jax.Array, pos: jax.Array, nb, window: int,
                   budget: int):
    """Budgeted selection, replicating the reference cursor bit-for-bit:
    take the first ``budget`` flagged blocks; the cursor cut is one past
    the budget-th selected block, else the (limit-clamped) window end.
    ``nb`` is the cursor limit — the static block count for a plain scan,
    or a traced i32 horizon for a carousel pass whose cursor runs past
    the scramble length (late joiners walk a wrapped lap).
    Returns ``(take mask over the window, csum, new_pos)``, ``csum``
    being the running count of ``flags`` that ``_gather_blocks``
    compacts."""
    csum = jnp.cumsum(flags.astype(jnp.int32))
    take = flags & (csum <= budget)
    n_sel = csum[window - 1]
    cut = jnp.argmax((csum == budget) & flags).astype(jnp.int32)
    covered = jnp.where(n_sel >= budget, cut + 1,
                        jnp.minimum(jnp.int32(window),
                                    jnp.asarray(nb, jnp.int32) - pos))
    return take, csum, pos + covered


def _gather_blocks(csum: jax.Array, win: jax.Array, window: int,
                   budget: int):
    """Selected window positions -> padded block ids + padding-lane mask
    + window position per lane. Padding lanes point at block 0 with
    ``tvalid`` False (their rows are masked out of the fold) and
    ``take_idx`` = window.

    ``csum`` is ``_budget_select``'s running count of the flags. Lane
    ``j`` reads the ``j``-th selected position, which is the number of
    positions whose count is at most ``j`` (``window`` once fewer than
    ``j + 1`` are flagged): a dense ``budget x window`` compare and sum,
    equal to ``jnp.nonzero(take, size=budget, fill_value=window)``.
    ``nonzero`` lowers to a scatter-add of every window position into
    ``budget`` bins, whose colliding updates the TPU runs one by one."""
    lanes = jnp.arange(budget, dtype=jnp.int32)
    take_idx = (csum[None, :] <= lanes[:, None]).sum(axis=1,
                                                      dtype=jnp.int32)
    tvalid = take_idx < window
    blk = jnp.where(tvalid, win[jnp.minimum(take_idx, window - 1)], 0)
    return blk, tvalid, take_idx


def _gather_rows(values, gids, mask, csum, win, window: int, budget: int):
    """The round's rows: the selected blocks' ids (``_gather_blocks``) and
    the flattened ``(budget * block_rows,)`` value, group-code and mask
    rows gathered from the device slabs, padding lanes masked out."""
    with jax.named_scope("gather"):
        blk, tvalid, _ = _gather_blocks(csum, win, window, budget)
        v = values[blk].reshape(-1)
        g = gids[blk].reshape(-1)
        m = (mask[blk] * tvalid[:, None].astype(jnp.float32)).reshape(-1)
    return v, g, m


@functools.partial(jax.jit, static_argnames=(
    "nb", "window", "budget", "center", "a", "b", "num_groups", "nbins",
    "use_hist", "probe", "impl"))
def fused_round(values: jax.Array, gids: jax.Array, mask: jax.Array,
                words: jax.Array, order_pad: jax.Array,
                static_ok: jax.Array, pos: jax.Array,
                active_words: jax.Array, *, nb: int, window: int,
                budget: int, center: float, a: float, b: float,
                num_groups: int, nbins: int, use_hist: bool, probe: bool,
                impl: str):
    """One fused scan round over device-resident column data.

    Args (device arrays unless noted):
      values/gids/mask: ``(nb, block_rows)`` materialized value column
        (f32), group codes (i32) and predicate*valid mask (f32);
      words: ``(nb, W)`` uint32 group-bitmap words (unused when
        ``probe=False``);
      order_pad: ``(nb + window,)`` i32 scan order, zero-padded;
      static_ok: ``(nb,)`` bool static-prefilter verdict per block;
      pos: i32 scalar scan cursor (device-resident across rounds);
      active_words: ``(W,)`` uint32 packed active-group mask.

    Static config: ``window`` is the round's maximum cursor coverage
    (the reference path's ``lookahead``-batched cover cap, rounded up to
    whole lookahead batches); ``budget`` the processed-block budget.

    Returns ``(state, hist, ok, flags, new_pos)``: the mergeable
    :class:`~repro.core.state.MomentState` / histogram deltas for the
    round, the per-window-position static/activity verdicts the host
    needs for taint + skip accounting, and the advanced cursor.
    """
    offs = jnp.arange(window, dtype=jnp.int32)
    in_range = (pos + offs) < nb
    win = jax.lax.dynamic_slice(order_pad, (pos,), (window,))
    ok = static_ok[win] & in_range
    if probe:
        act = kops.active_blocks(words[win], active_words, impl=impl) > 0
        flags = ok & act
    else:
        flags = ok

    _, csum, new_pos = _budget_select(flags, pos, nb, window, budget)
    v, g, m = _gather_rows(values, gids, mask, csum, win, window, budget)

    state, hist = _fold(v, g, m, center, a, b, num_groups, nbins,
                        use_hist, impl)
    return state, hist, ok, flags, new_pos


@functools.partial(jax.jit, static_argnames=(
    "nb", "window", "budget", "meta", "impl"))
def fused_round_multi(mask: jax.Array, order_pad: jax.Array,
                      static_ok: jax.Array, pos: jax.Array,
                      values, gids, words, active, *, nb: int, window: int,
                      budget: int, meta, impl: str, anchors=None):
    """One fused scan round shared by several queries (one device
    dispatch per round for a whole :class:`repro.serve.FrameServer`
    pass). All queries share the predicate mask and static prefilter;
    each *slot* (distinct ``(column, group-by)`` over the shared
    filters) advances its OWN cursor through its own budgeted selection,
    gathers its own row slice and folds its own columns — so every
    slot's scan replays its solo run exactly, whatever else is
    co-resident. Each *query* contributes one row of its slot's
    active-word stack to that slot's activity test (selection within a
    slot is the union over the slot's queries).

    Args (device arrays unless noted):
      mask: ``(nb, block_rows)`` shared predicate*valid mask (f32);
      order_pad: ``(nb + window,)`` i32 scan order with a WRAP-FILLED
        tail (``order[:window]``) — every slot slices it at its own
        ``pos % nb``;
      static_ok: ``(nb,)`` bool static-prefilter verdict per block;
      pos: ``(S,)`` i32 per-slot cursors in pass coordinates (a slot's
        lap is ``[anchors[s], anchors[s] + nb)``);
      values / gids: length-S tuples of ``(nb, block_rows)`` per-slot
        value (f32) / group-code (i32) columns;
      words: length-S tuple of ``(nb, W_s)`` uint32 bitmap words — the
        slot's group bitmap, or an all-ones ``(nb, 1)`` engagement bitmap
        for slots that do not activity-skip (their queries then gate
        selection with a single engaged/finished bit);
      active: length-S tuple of ``(Q_s, W_s)`` uint32 per-query
        active-word stacks;
      anchors: ``(S,)`` i32 pass-coordinate admission positions
        (``None`` = all zero, the static-batch case) — dynamic, so
        admission epochs with the same shape profile hit the jit cache.

    Static config: ``meta`` is a length-S tuple of per-slot
    ``(num_groups, nbins, use_hist, a, b, center)`` tuples; ``nb`` /
    ``window`` / ``budget`` as in :func:`fused_round`.

    Because each slot selects with its own flags at its own cursor, a
    slot's selection/fold sequence is the same computation as
    :func:`fused_round` on the rotated order starting at its anchor —
    a served query is bitwise identical to its solo ``FastFrame.run``
    whatever other slots share the pass (the slot-level co-residency
    contract; multi-query slots match the solo run of that query
    *batch*). The caller is responsible for not advancing slots that
    are lapped (``pos >= anchor + nb``) or fully finished; a lapped
    slot's round is a no-op by construction (empty window), a finished
    slot's is not (its cursor would cover ground without selecting).

    Returns ``(states, hists, flag_stacks, oks, new_pos)``: per-slot
    mergeable deltas (``hists[s]`` is None when the slot has no
    histogram), per-slot ``(Q_s, window)`` bool per-query activity
    verdicts, per-slot ``(window,)`` static verdicts and the ``(S,)``
    advanced cursors.
    """
    if anchors is None:
        anchors = jnp.zeros((len(meta),), jnp.int32)
    offs = jnp.arange(window, dtype=jnp.int32)
    states, hists, flag_stacks, oks, new_positions = [], [], [], [], []
    for s, (num_groups, nbins, use_hist, a, b, center) in enumerate(meta):
        le = anchors[s] + nb
        p = pos[s]
        in_range = (p + offs) < le
        start = jax.lax.rem(p, jnp.int32(nb))
        win = jax.lax.dynamic_slice(order_pad, (start,), (window,))
        ok = static_ok[win] & in_range
        act = kops.active_blocks_multi(words[s][win], active[s],
                                       impl=impl) > 0
        fl = ok[None, :] & act
        flags = fl.any(axis=0)
        _, csum, new_p = _budget_select(flags, p, le, window, budget)
        v, g, m = _gather_rows(values[s], gids[s], mask, csum, win, window,
                               budget)
        st, h = _fold(v, g, m, center, a, b, num_groups, nbins,
                      use_hist, impl)
        states.append(st)
        hists.append(h)
        flag_stacks.append(fl)
        oks.append(ok)
        new_positions.append(new_p)
    return (tuple(states), tuple(hists), tuple(flag_stacks), tuple(oks),
            jnp.stack(new_positions))


# ---------------------------------------------------------------------------
# Device-resident round loop: the whole OptStop loop in one lax.while_loop.
# ---------------------------------------------------------------------------


def pack_active_device(active: jax.Array, n_words: int) -> jax.Array:
    """Jittable twin of :func:`repro.aqp.bitmap.pack_mask`: bool ``(G,)``
    active mask -> ``(n_words,)`` uint32 packed words (little-endian bit
    order, bit ``j`` of word ``w`` = group ``32 w + j``)."""
    G = active.shape[0]
    bits = jnp.zeros(n_words * 32, dtype=bool).at[:G].set(active)
    b32 = bits.reshape(n_words, 32).astype(jnp.uint32)
    return (b32 << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=1, dtype=jnp.uint32)


def _merge_f64(state: MomentState, delta: MomentState) -> MomentState:
    """Fold a round's f32 mergeable delta into the f64 running state —
    the device twin of ``merge_moments_host(state, to_host(delta))``.
    Same formula in the same order: counts (integral sums) stay exact;
    mean/m2 may differ from the host by the final ulp where XLA
    contracts a mul+add into an FMA."""
    with jax.named_scope("merge"):
        return merge_moments(
            state,
            MomentState(*(jnp.asarray(f, jnp.float64) for f in delta)))


def _merge_round(state: MomentState, hist, dstate: MomentState, dhist,
                 use_hist: bool):
    """Fold one round's merged delta into the f64 running state and
    histogram (``hist`` passes through unchanged without ``use_hist``)."""
    state = _merge_f64(state, dstate)
    with jax.named_scope("merge"):
        if use_hist:
            hist = hist + jnp.asarray(dhist, jnp.float64)
    return state, hist


def _pend_round(c, dsums, dvmin, dvmax, dhist, use_hist: bool) -> dict:
    """Add one round's raw local fold to the pending slots of a query or
    slot carry ``c`` (collective cadence); returns the updated slots."""
    with jax.named_scope("merge"):
        return dict(
            pend_sums=c.pend_sums + jnp.asarray(dsums, jnp.float64),
            pend_vmin=jnp.minimum(
                c.pend_vmin, jnp.asarray(dvmin, jnp.float64).reshape(-1)),
            pend_vmax=jnp.maximum(
                c.pend_vmax, jnp.asarray(dvmax, jnp.float64).reshape(-1)),
            pend_hist=(c.pend_hist + jnp.asarray(dhist, jnp.float64)
                       if use_hist else None))


def _zeroed_pending(c, use_hist: bool) -> dict:
    """The pending slots of a query or slot carry ``c``, emptied by a
    merge."""
    return dict(
        pend_sums=jnp.zeros_like(c.pend_sums),
        pend_vmin=jnp.full_like(c.pend_vmin, jnp.inf),
        pend_vmax=jnp.full_like(c.pend_vmax, -jnp.inf),
        pend_hist=jnp.zeros_like(c.pend_hist) if use_hist else None)


def _probe_cost(flags: jax.Array, pos: jax.Array, nb: int, window: int,
                budget: int, lookahead: int, cover_cap: int) -> jax.Array:
    """Device twin of the reference probe-metric loop (the per-lookahead
    batched probing in ``engine._fused_accounting``): count the window
    positions the reference path would have probed this round."""
    i32 = jnp.int32
    win_len = jnp.minimum(i32(window), i32(nb) - pos)
    csum = jnp.cumsum(flags.astype(i32))
    csum_excl = jnp.concatenate([jnp.zeros(1, i32), csum[:-1]])
    n_batches = -(-window // lookahead)
    starts = jnp.arange(n_batches, dtype=i32) * lookahead
    # each batch's first position: a strided slice (indexing with
    # ``starts`` would lower to a gather)
    at_starts = jax.lax.slice(csum_excl, (0,), (window,), (lookahead,))
    probed = ((at_starts < budget) & (starts < win_len)
              & (starts < cover_cap))
    ends = jnp.minimum(starts + lookahead, win_len)
    return jnp.where(probed, ends - starts, 0).sum().astype(jnp.int64)


# -- the round's window as slices ---------------------------------------------
#
# Every scan order is a rotation, ``order = (start + arange(nb)) % nb``, so
# a round's window of block ids is ``(b0 + i) % nb`` for every in-range
# lane ``i``, ``b0`` being the block at the cursor: one contiguous range
# of block ids that wraps at most once. The loops' block-id buffers
# (static verdicts, bitmap words, presence) are uploaded with ``window``
# rows appended, row ``nb + i`` repeating row ``i % nb`` (:func:`pad_blocks`),
# so each window read is one ``dynamic_slice`` at ``b0`` and the
# ``processed`` update one ``dynamic_update_slice``. Lanes past the
# cursor limit read some other block's row; every use of them is masked
# by ``ok`` / ``take`` / ``covmask``, which are false there. At most
# ``nb`` lanes are in range, so in-range lanes never repeat a block.


def pad_blocks(x: np.ndarray, window: int) -> np.ndarray:
    """Host-side block-id buffer (leading axis ``nb``) with ``window``
    rows appended, row ``nb + i`` repeating row ``i % nb`` (tiled, since
    ``window`` can exceed ``nb``): rows ``[b0, b0 + window)`` are then
    the rows of blocks ``(b0 + i) % nb`` for any ``b0 < nb``."""
    x = np.asarray(x)
    return np.concatenate([x, x[np.arange(window) % x.shape[0]]])


def rotation_order_pad(start: int, nb: int, window: int) -> np.ndarray:
    """The scan order ``(start + arange(nb)) % nb`` as the loops' i32
    ``order_pad``, wrap-filled to ``nb + window`` entries. Only a
    rotation keeps each round's window one contiguous range of block
    ids, so this is the one way to build a round loop's order."""
    if not 0 <= start < nb:
        raise ValueError(f"scan start {start} outside [0, {nb})")
    return pad_blocks((start + np.arange(nb, dtype=np.int64)) % nb,
                      window).astype(np.int32)


def _window_rows(buf: jax.Array, b0, window: int) -> jax.Array:
    """The window's rows of a :func:`pad_blocks` buffer, in scan order."""
    return jax.lax.dynamic_slice_in_dim(buf, b0, window, axis=0)


def _pad_processed(processed: jax.Array, window: int) -> jax.Array:
    """A carry's ``(nb,)`` processed mask in the loop's padded form: the
    ``window`` rows past ``nb`` start empty."""
    with jax.named_scope("account"):
        return jnp.concatenate([processed,
                                jnp.zeros(window, processed.dtype)])


def _fold_processed(processed: jax.Array, nb: int) -> jax.Array:
    """The padded processed mask back in its ``(nb,)`` form: row
    ``nb + i`` is block ``i % nb``."""
    with jax.named_scope("account"):
        tail = processed[nb:]
        laps = -(-tail.shape[0] // nb)
        tail = jnp.pad(tail, (0, laps * nb - tail.shape[0]))
        return processed[:nb] | tail.reshape(laps, nb).any(axis=0)


class QueryLoopBuffers(NamedTuple):
    """Device-resident inputs of the single-query loop (constant across
    rounds; passed as jit arguments so reuse never retraces). The
    block-id buffers ``words``, ``static_ok`` and ``presence`` are
    :func:`pad_blocks`-padded, ``order_pad`` is
    :func:`rotation_order_pad`'s."""

    values: jax.Array          # (nb, block_rows) f32 value column
    gids: jax.Array            # (nb, block_rows) i32 group codes
    mask: jax.Array            # (nb, block_rows) f32 predicate*valid
    words: jax.Array           # (nb + window, W) uint32 group-bitmap words
                               # (a (1, 1) placeholder without the probe)
    order_pad: jax.Array       # (nb + window,) i32 scan order (rotation)
    static_ok: jax.Array       # (nb + window,) bool static prefilter
    presence: jax.Array        # (nb + window, G) bool view presence
    presence_total: jax.Array  # (G,) i32 blocks containing each view
    cum_rows: jax.Array        # (nb,) i64 cumulative valid rows in order


class QueryLoopCarry(NamedTuple):
    """``lax.while_loop`` carry: every piece of per-query round state the
    host loop keeps in numpy, device-resident across rounds."""

    pos: jax.Array             # i32 scan cursor
    rounds: jax.Array          # i32 completed OptStop rounds (k)
    it: jax.Array              # i32 rounds inside the current dispatch
    live: jax.Array            # bool: some view still active
    stopped_early: jax.Array   # bool: stop fired before exhaustion
    state: MomentState         # f64 (G,) running moments
    hist: Optional[jax.Array]  # f64 (G, K) running histogram (or None)
    processed: jax.Array       # (nb,) bool
    seen_presence: jax.Array   # (G,) i32 processed blocks per view
    tainted: jax.Array         # (G,) bool
    exact: jax.Array           # (G,) bool
    lo: jax.Array              # (G,) f64 running interval
    hi: jax.Array              # (G,) f64
    est: jax.Array             # (G,) f64
    refreshed: jax.Array       # (G,) bool
    active: jax.Array          # (G,) bool
    blocks_fetched: jax.Array  # i64 scan metrics
    skipped_static: jax.Array  # i64
    skipped_active: jax.Array  # i64
    probes: jax.Array          # i64
    # -- collective-cadence slots (``ShardInfo.merge_every > 1`` only;
    # None otherwise, so the K=1 carry pytree — and its trace — is
    # unchanged). The pending slots hold this shard's raw additive fold
    # delta accumulated since the last full merge; they are zeroed by
    # every merge and every dispatch exits freshly merged (flush), so
    # the out-spec replication of the carry still holds.
    pend_sums: Optional[jax.Array] = None    # (3, G) f64 local delta
    pend_vmin: Optional[jax.Array] = None    # (G,) f64 local extremes
    pend_vmax: Optional[jax.Array] = None    # (G,) f64
    pend_hist: Optional[jax.Array] = None    # (G, K) f64 local hist delta
    pend_rounds: Optional[jax.Array] = None  # i32 rounds since last merge
                                             # (replicated: the merge
                                             # schedule is deterministic)


def _round_scan(bufs, pos, flags_src, *, nb: int, window: int,
                budget: int, bound: Optional[int] = None,
                wrap: bool = False):
    """Shared per-round cursor/selection plumbing: window slice, static
    verdicts, caller-supplied activity flags, budgeted selection and the
    covered-range accounting masks. ``flags_src(ok, b0)`` returns the
    activity-tested flags for this round, ``b0`` being the window's
    first block id (its rows of a padded buffer: :func:`_window_rows`).

    ``bound`` overrides the cursor limit (a carousel pass's horizon can
    exceed ``nb``); ``wrap`` slices the order at ``pos % nb``."""
    with jax.named_scope("select"):
        offs = jnp.arange(window, dtype=jnp.int32)
        lim = nb if bound is None else bound
        in_range = (pos + offs) < lim
        start = jax.lax.rem(pos, jnp.int32(nb)) if wrap else pos
        win = jax.lax.dynamic_slice(bufs.order_pad, (start,), (window,))
        b0 = win[0]
        ok = _window_rows(bufs.static_ok, b0, window) & in_range
        flags = flags_src(ok, b0)
        take, csum, new_pos = _budget_select(flags, pos, lim, window,
                                             budget)
        covmask = offs < (new_pos - pos)
    return win, ok, flags, take, csum, new_pos, covmask


def _account(c, presence, presence_total, scan, *, lim, probe: bool,
             window: int, budget: int, lookahead: int, cover_cap: int
             ) -> dict:
    """One round's skip / taint / coverage / metric accounting of a query
    or slot carry ``c`` (the device twin of ``engine._fused_accounting``,
    ``_ScanViews.ingest_delta`` and ``_ScanViews.update_exact``) from
    ``_round_scan``'s tuple ``scan``; ``lim`` is the cursor limit.
    ``presence`` and ``c.processed`` are in their padded forms
    (:func:`pad_blocks`, :func:`_pad_processed`). Returns the updated
    carry fields."""
    win, ok, flags, take, _, new_pos, covmask = scan
    i64 = jnp.int64
    with jax.named_scope("account"):
        b0 = win[0]
        act_skip = ok & covmask & ~(flags & covmask)
        pres_win = _window_rows(presence, b0, window)
        tainted = c.tainted | (pres_win & act_skip[:, None]).any(axis=0)
        seen_presence = c.seen_presence + (
            pres_win & take[:, None]).sum(axis=0, dtype=jnp.int32)
        cov = seen_presence >= presence_total
        cov = cov | ((new_pos >= lim) & ~tainted)
        probes = c.probes
        if probe:
            probes = probes + _probe_cost(flags, c.pos, lim, window,
                                          budget, lookahead, cover_cap)
        return dict(
            seen_presence=seen_presence, tainted=tainted,
            exact=c.exact | cov,
            processed=jax.lax.dynamic_update_slice(
                c.processed, _window_rows(c.processed, b0, window) | take,
                (b0,)),
            blocks_fetched=c.blocks_fetched + take.sum(dtype=i64),
            skipped_static=(c.skipped_static
                            + (~ok & covmask).sum(dtype=i64)),
            skipped_active=c.skipped_active + act_skip.sum(dtype=i64),
            probes=probes)


def _query_carry_spec(use_hist: bool, cadence: bool = False
                      ) -> "QueryLoopCarry":
    """Fully-replicated shard_map partition spec of the query carry.
    The cadence pending slots are per-shard state, but every dispatch
    exits with them zeroed (flush), so they too are replicated at the
    shard_map boundary."""
    rep = P()
    pend = rep if cadence else None
    return QueryLoopCarry(
        pos=rep, rounds=rep, it=rep, live=rep, stopped_early=rep,
        state=MomentState(rep, rep, rep, rep, rep),
        hist=(rep if use_hist else None), processed=rep,
        seen_presence=rep, tainted=rep, exact=rep, lo=rep, hi=rep,
        est=rep, refreshed=rep, active=rep, blocks_fetched=rep,
        skipped_static=rep, skipped_active=rep, probes=rep,
        pend_sums=pend, pend_vmin=pend, pend_vmax=pend,
        pend_hist=(rep if cadence and use_hist else None),
        pend_rounds=pend)


def build_query_loop(*, nb: int, window: int, budget: int, center: float,
                     a: float, b: float, num_groups: int, nbins: int,
                     use_hist: bool, probe: bool, n_words: int, impl: str,
                     lookahead: int, cover_cap: int, max_rounds: int,
                     chunk: Optional[int], refresh_fn: Callable,
                     shard: Optional[ShardInfo] = None) -> Callable:
    """Build the jitted device-resident round loop for one query.

    Returns ``chunk_fn(bufs: QueryLoopBuffers, carry: QueryLoopCarry) ->
    QueryLoopCarry`` executing up to ``chunk`` OptStop rounds (``None`` =
    until the stop test fires, the scramble is exhausted or
    ``max_rounds`` is hit) in a single ``lax.while_loop`` dispatch. Each
    round is the exact device twin of the host round: ``fused_round``'s
    scan/fold, the f64 state merge, ``_fused_accounting``'s skip/taint/
    probe bookkeeping, ``_ScanViews.update_exact`` and the caller's
    ``refresh_fn`` (CI refresh + stopping condition; see
    ``engine._make_device_refresh``).

    ``refresh_fn(k, r, state, hist, tainted, exact, lo, hi, est,
    refreshed, active)`` returns the updated ``(lo, hi, est, refreshed,
    active)``.

    With ``shard`` the whole loop runs under ``shard_map`` on
    ``shard.mesh``: the within-block row axis of
    ``bufs.values/gids/mask`` is sliced over the mesh (equal-shape
    padded slabs, see :class:`ShardInfo`) while every other buffer AND
    the entire carry stay replicated. Each shard runs the IDENTICAL
    round body on its own row slice — global block ids index the local
    slab directly, so the gather materializes and the fold touches only
    ``1/n_shards`` of each selected block's rows. Selection, the
    cursor, coverage/taint accounting and the CI refresh are replicated
    computations over replicated inputs — identical on every device and
    identical to the single-device loop — and only the fold delta
    crosses the mesh (``psum``/``pmin``/``pmax`` inside :func:`_fold`,
    one collective set per round, no host sync).

    ``shard.merge_every = K > 1`` amortizes that collective set over K
    rounds (the *collective cadence*; see ``docs/architecture.md``).
    Each round folds only into the carry's f64 pending slots (this
    shard's raw additive delta since the last merge) and the reported
    intervals / active mask stay frozen at their last fully-merged
    values — stale by at most K rounds but still anytime-valid (frozen
    intersected CIs can only be supersets of the fresher ones, the same
    trick the host uses with ``sync_every``). The full merge fires at
    the START of a round — on data the current round's scan does not
    depend on, so XLA can overlap the collective with the gather/fold —
    on a DETERMINISTIC schedule: exactly when K rounds of delta are
    pending, decided from the replicated ``pend_rounds`` counter. No
    per-round hint, no scalar ``pmax`` — between merges there is zero
    cross-shard communication. Termination is merge-then-confirm
    (decisions only ever read fully-merged stats). It never comes
    before the K=1 loop's, but can come more than K-1 rounds after it:
    the intervals are intersected only at merges, a subset of the K=1
    looks, and a later look's interval need not be narrower than an
    earlier one (RangeTrim's lower bound on a few rows can sit above
    its lower bound on more), so the K=1 loop may stop on a look the
    cadence skips. Every dispatch flushes its pending delta on exit, so host
    syncs, ``on_sync`` snapshots and termination always observe
    fully-merged state. With ``merge_every=1`` (default) this path is
    not even traced — the per-round-merge loop above survives bitwise
    as the oracle.
    """
    cadence = shard is not None and shard.merge_every > 1
    acct_kw = dict(lim=nb, probe=probe, window=window, budget=budget,
                   lookahead=lookahead, cover_cap=cover_cap)

    def scan_round(bufs, pos, sel_active):
        def flags_src(ok, b0):
            if not probe:
                return ok
            aw = pack_active_device(sel_active, n_words)
            act = kops.active_blocks(_window_rows(bufs.words, b0, window),
                                     aw, impl=impl) > 0
            return ok & act

        return _round_scan(bufs, pos, flags_src, nb=nb, window=window,
                           budget=budget)

    def refresh(bufs, c: QueryLoopCarry, k, pos, state, hist, tainted,
                exact) -> dict:
        """CI refresh + stopping condition (engine-supplied) at round
        ``k`` with the cursor at ``pos``."""
        with jax.named_scope("refresh"):
            r = jnp.where(pos > 0, bufs.cum_rows[jnp.maximum(pos - 1, 0)],
                          0).astype(jnp.float64)
            lo, hi, est, refreshed, active = refresh_fn(
                k, r, state, hist, tainted, exact, c.lo, c.hi, c.est,
                c.refreshed, c.active)
            live = active.any()
            stopped_early = c.stopped_early | (~live & (pos < nb))
        return dict(lo=lo, hi=hi, est=est, refreshed=refreshed,
                    active=active, live=live, stopped_early=stopped_early)

    def body(bufs, c: QueryLoopCarry) -> QueryLoopCarry:
        k = c.rounds + 1
        scan = scan_round(bufs, c.pos, c.active)
        win, ok, flags, take, csum, new_pos, covmask = scan
        # Under shard_map the local slab is this shard's row slice of
        # every block, so the global block ids gather exactly the
        # shard's 1/n_shards of the selection — no translation needed.
        v, g, m = _gather_rows(bufs.values, bufs.gids, bufs.mask, csum, win,
                               window, budget)
        dstate, dhist = _fold(v, g, m, center, a, b, num_groups, nbins,
                              use_hist, impl,
                              shard_axes=shard.axes if shard else None)
        state, hist = _merge_round(c.state, c.hist, dstate, dhist, use_hist)
        acct = _account(c, bufs.presence, bufs.presence_total, scan,
                        **acct_kw)
        return c._replace(
            pos=new_pos, rounds=k, it=c.it + 1, state=state, hist=hist,
            **acct, **refresh(bufs, c, k, new_pos, state, hist,
                              acct["tainted"], acct["exact"]))

    # -- collective cadence (shard.merge_every = K > 1) ------------------

    def _merge_refresh(bufs, c: QueryLoopCarry) -> QueryLoopCarry:
        """Fire the collective set on the pending multi-round delta,
        fold it into the merged running state and re-evaluate the CIs /
        stopping condition on fully-merged stats. Valid both at a round
        start (delta-schedule index ``c.rounds`` — the rounds whose data
        the merged state now covers) and at the dispatch-exit flush;
        merges zero the pending slots, so each index is consumed at most
        once (the schedule stays a subset of the K=1 one and the union
        bound over ``delta`` holds)."""
        with jax.named_scope("merge"):
            sums = jax.lax.psum(c.pend_sums, shard.axes)
            vmin, vmax = _pmin_pmax_f64(c.pend_vmin, c.pend_vmax,
                                        shard.axes)
            dstate = kops.moments_from_sums(sums, vmin, vmax, center)
            state = merge_moments(c.state, dstate)
            hist = (c.hist + jax.lax.psum(c.pend_hist, shard.axes)
                    if use_hist else c.hist)
        return c._replace(
            state=state, hist=hist, pend_rounds=jnp.asarray(0, jnp.int32),
            **_zeroed_pending(c, use_hist),
            **refresh(bufs, c, c.rounds, c.pos, state, hist, c.tainted,
                      c.exact))

    def cadence_body(bufs, c: QueryLoopCarry) -> QueryLoopCarry:
        # Selection runs on the PRE-merge active mask, so this round's
        # scan/gather/fold has no data dependence on the merge and XLA
        # is free to overlap the collective with the compute (the merge
        # gates round k+1). The merge schedule is deterministic — fire
        # exactly when K rounds of delta are pending — and pend_rounds
        # is replicated, so every shard takes the same branch and the
        # collectives inside the cond rendezvous; between merges no
        # cross-shard communication happens at all.
        sel_active = c.active
        c = jax.lax.cond(c.pend_rounds >= shard.merge_every,
                         functools.partial(_merge_refresh, bufs),
                         lambda x: x, c)
        scan = scan_round(bufs, c.pos, sel_active)
        win, ok, flags, take, csum, new_pos, covmask = scan
        v, g, m = _gather_rows(bufs.values, bufs.gids, bufs.mask, csum, win,
                               window, budget)
        dsums, dvmin, dvmax, dhist = _fold_local(
            v, g, m, center, a, b, num_groups, nbins, use_hist, impl)
        # accounting: replicated, every round (same as the K=1 body)
        return c._replace(
            pos=new_pos, rounds=c.rounds + 1, it=c.it + 1,
            pend_rounds=c.pend_rounds + 1,
            **_pend_round(c, dsums, dvmin, dvmax, dhist, use_hist),
            **_account(c, bufs.presence, bufs.presence_total, scan,
                       **acct_kw))

    def flush(bufs, carry: QueryLoopCarry) -> QueryLoopCarry:
        # every dispatch exits fully merged: termination / sync_every
        # snapshots never see stale stats, and the pending slots leave
        # the shard_map as replicated zeros. pend_rounds == 0 implies
        # the pending slots are already zero.
        return jax.lax.cond(carry.pend_rounds > 0,
                            functools.partial(_merge_refresh, bufs),
                            lambda x: x, carry)

    loop_body = cadence_body if cadence else body

    def cond(c: QueryLoopCarry):
        go = c.live & (c.pos < nb) & (c.rounds < max_rounds)
        if chunk is not None:
            go = go & (c.it < chunk)
        return go

    def chunk_body(bufs: QueryLoopBuffers,
                   carry: QueryLoopCarry) -> QueryLoopCarry:
        carry = carry._replace(
            it=jnp.asarray(0, jnp.int32),
            processed=_pad_processed(carry.processed, window))
        carry = jax.lax.while_loop(cond,
                                   functools.partial(loop_body, bufs),
                                   carry)
        if cadence:
            carry = flush(bufs, carry)
        return carry._replace(processed=_fold_processed(carry.processed,
                                                        nb))

    if shard is None:
        return jax.jit(chunk_body)

    rep = P()
    data = P(None, shard.axes)  # row-axis sliced, block axis whole
    bufs_spec = QueryLoopBuffers(
        values=data, gids=data, mask=data, words=rep, order_pad=rep,
        static_ok=rep, presence=rep, presence_total=rep, cum_rows=rep)
    carry_spec = _query_carry_spec(use_hist, cadence)
    # check_vma=False: replication of the carry holds by construction
    # (replicated inputs -> replicated selection/accounting; the fold
    # delta is re-replicated by its psum) but the checker cannot see
    # through while_loop + axis_index.
    return jax.jit(jax.shard_map(
        chunk_body, mesh=shard.mesh, in_specs=(bufs_spec, carry_spec),
        out_specs=carry_spec, check_vma=False))


class SlotSpec(NamedTuple):
    """Static per-slot configuration of the multi-query pass loop."""

    num_groups: int
    nbins: int
    use_hist: bool
    a: float
    b: float
    center: float
    probe: bool
    n_words: int


class PassLoopBuffers(NamedTuple):
    """Device-resident inputs of the multi-query pass loop; the per-slot
    fields are length-S tuples."""

    mask: jax.Array            # (nb, block_rows) shared predicate mask
    order_pad: jax.Array       # (nb + window,) i32 (rotation_order_pad)
    static_ok: jax.Array       # (nb + window,) bool (pad_blocks)
    cum_rows: jax.Array        # (nb,) i64
    values: Tuple[jax.Array, ...]          # per-slot value columns
    gids: Tuple[jax.Array, ...]            # per-slot group codes
    words: Tuple[jax.Array, ...]           # per-slot (nb + window, W_s)
    presence: Tuple[jax.Array, ...]        # per-slot (nb + window, G_s)
    presence_total: Tuple[jax.Array, ...]  # per-slot (G_s,) i32


class SlotCarry(NamedTuple):
    """Per-slot scan state inside the pass carry. Every slot owns its
    cursor, selection, fold, coverage and metrics — the device twin of a
    solo query-loop carry — so a slot's scan replays its solo run
    exactly regardless of what else is co-resident in the pass (the
    slot-level bitwise co-residency contract; see docs/serving.md)."""

    pos: jax.Array             # i32 slot cursor (pass coordinates; the
                               # slot's lap is [anchor, anchor + nb))
    state: MomentState         # f64 (G_s,)
    hist: Optional[jax.Array]  # f64 (G_s, K) or None
    seen_presence: jax.Array   # (G_s,) i32
    tainted: jax.Array         # (G_s,) bool
    exact: jax.Array           # (G_s,) bool
    processed: jax.Array       # (nb,) bool blocks this slot fetched
    blocks_fetched: jax.Array  # i64 scan metrics (slot-local)
    skipped_static: jax.Array  # i64
    skipped_active: jax.Array  # i64
    probes: jax.Array          # i64
    lap_rounds: jax.Array      # i32 round the slot's lap ended (-1 while
                               # still inside the lap)
    # collective-cadence pending slots (merge_every > 1 only, else None;
    # see QueryLoopCarry — this shard's raw additive delta since the
    # last full merge, zeroed by every merge)
    pend_sums: Optional[jax.Array] = None    # (3, G_s) f64
    pend_vmin: Optional[jax.Array] = None    # (G_s,) f64
    pend_vmax: Optional[jax.Array] = None    # (G_s,) f64
    pend_hist: Optional[jax.Array] = None    # (G_s, K) f64


class PassQueryCarry(NamedTuple):
    """Per-query OptStop state + finish-time snapshots. A query's result
    is a consistent snapshot of the slot state at the round it finished
    (the slot keeps scanning for the pass's remaining queries), so the
    carry records the slot/metric state the moment ``finished`` flips."""

    lo: jax.Array              # (G_s,) f64
    hi: jax.Array              # (G_s,) f64
    est: jax.Array             # (G_s,) f64
    refreshed: jax.Array       # (G_s,) bool
    active: jax.Array          # (G_s,) bool
    finished: jax.Array        # bool scalar
    stopped_early: jax.Array   # bool scalar
    finish_rounds: jax.Array   # i32
    finish_pos: jax.Array      # i32
    finish_blocks_fetched: jax.Array   # i64
    finish_skipped_static: jax.Array   # i64
    finish_skipped_active: jax.Array   # i64
    finish_probes: jax.Array           # i64
    snap_counts: jax.Array     # (G_s,) f64 slot counts at finish
    snap_exact: jax.Array      # (G_s,) bool slot exact at finish
    snap_tainted: jax.Array    # (G_s,) bool slot tainted at finish


class PassCarry(NamedTuple):
    """``lax.while_loop`` carry of the multi-query pass loop. All
    per-scan state lives in the per-slot :class:`SlotCarry` entries —
    the pass itself only keeps the shared round clock and liveness."""

    rounds: jax.Array          # i32 pass rounds (shared clock)
    it: jax.Array              # i32 rounds inside the current dispatch
    n_live: jax.Array          # i32 unfinished queries across slots
    slots: Tuple[SlotCarry, ...]
    queries: Tuple[Tuple[PassQueryCarry, ...], ...]  # [slot][query]
    # collective-cadence shared state (merge_every > 1 only, else None)
    pend_rounds: Optional[jax.Array] = None  # i32 rounds since last merge
                                             # (replicated: the merge
                                             # schedule is deterministic)


def _pass_carry_spec(slot_specs: Sequence[SlotSpec],
                     n_queries: Sequence[int],
                     cadence: bool = False) -> "PassCarry":
    """Fully-replicated shard_map partition spec of the pass carry (the
    cadence pending slots leave every dispatch zeroed — see
    :func:`_query_carry_spec`)."""
    rep = P()
    pend = rep if cadence else None
    qspec = PassQueryCarry(*([rep] * len(PassQueryCarry._fields)))
    return PassCarry(
        rounds=rep, it=rep, n_live=rep,
        slots=tuple(SlotCarry(pos=rep,
                              state=MomentState(rep, rep, rep, rep, rep),
                              hist=(rep if spec.use_hist else None),
                              seen_presence=rep, tainted=rep, exact=rep,
                              processed=rep, blocks_fetched=rep,
                              skipped_static=rep, skipped_active=rep,
                              probes=rep, lap_rounds=rep,
                              pend_sums=pend, pend_vmin=pend,
                              pend_vmax=pend,
                              pend_hist=(rep if cadence and spec.use_hist
                                         else None))
                    for spec in slot_specs),
        queries=tuple(tuple(qspec for _ in range(nq))
                      for nq in n_queries),
        pend_rounds=pend)


def carry_nonfinite_slots(carry: PassCarry) -> Tuple[bool, ...]:
    """Host-side NaN sentinel over a fetched pass carry: one flag per
    slot, True when that slot's folded state is poisoned (non-finite
    count/mean/m2, NaN min/max, or NaN histogram mass).

    ``vmin``/``vmax`` are legitimately ``±inf`` for groups no row has
    touched yet, so only NaN counts as poison there. The serving layer
    uses this to quarantine a poison query's slot at a chunk boundary
    without inspecting co-resident slots (membership independence)."""
    import numpy as np

    flags = []
    for slot in carry.slots:
        count, mean, m2, vmin, vmax = (
            np.asarray(jax.device_get(f)) for f in slot.state)
        bad = (~np.isfinite(count) | ~np.isfinite(mean)
               | ~np.isfinite(m2) | np.isnan(vmin) | np.isnan(vmax))
        if slot.hist is not None:
            hist = np.asarray(jax.device_get(slot.hist))
            bad = bad | ~np.isfinite(hist).all(axis=-1)
        flags.append(bool(np.any(bad)))
    return tuple(flags)


def build_pass_loop(*, nb: int, window: int, budget: int, impl: str,
                    lookahead: int, cover_cap: int, max_rounds: int,
                    chunk: Optional[int],
                    slot_specs: Sequence[SlotSpec],
                    refresh_fns: Sequence[Sequence[Callable]],
                    shard: Optional[ShardInfo] = None,
                    anchors: Optional[Sequence[int]] = None,
                    round_offsets: Optional[Sequence[int]] = None,
                    row_offsets: Optional[Sequence[int]] = None
                    ) -> Callable:
    """Build the jitted device-resident loop for one FrameServer pass
    (S slots, each with its own queries and its OWN cursor walk).

    Every slot advances independently each pass round: its own window
    slice at its own cursor, its own activity flags (the union over the
    slot's queries only), its own budgeted selection, gather, fold and
    coverage/taint/metric accounting — the exact device twin of a solo
    :func:`build_query_loop` run on the scan order rotated to the slot's
    anchor. Per-query CI refresh / stop tests use slot-local round/row
    counts, with finish-time snapshots recorded in the carry (the host
    materializes each query's result after the loop from the snapshot
    taken the round it finished). ``refresh_fns[s][q]`` has the
    :func:`build_query_loop` ``refresh_fn`` signature.

    Because nothing is shared between slots but the round clock, a
    slot's selection/fold/refresh sequence is bitwise identical to its
    solo run whatever else is co-resident — including probe slots,
    whose activity words never leak into another slot's selection (the
    slot-level bitwise co-residency contract, docs/serving.md). A slot
    whose lap ended (``pos >= anchor + nb``) or whose queries all
    finished is frozen in place; the loop exits when no slot can make
    progress.

    ``anchors[s]`` is the slot's static admission position in pass
    coordinates (``None`` = all zero, the static-batch case): the slot's
    lap is ``[anchor, anchor + nb)``, the order pad must be wrap-filled
    (``order[:window]``) so the window slice at ``pos % nb`` is a
    rotation of the scan order, and refreshes subtract the static
    ``round_offsets[s]`` (pass rounds already elapsed at admission) and
    ``row_offsets[s]`` (rows before the anchor, in pass coordinates;
    per-position rows are periodic with period ``nb`` so ``cum_rows``
    needs no extension). Mid-scan admission is therefore just another
    anchor — carousel passes, sharded or not, run this same loop.

    ``shard`` shards the pass exactly like :func:`build_query_loop`:
    every slot's value/group columns and the shared mask are
    row-slice-sharded slabs, each slot's selection / accounting /
    refreshes stay replicated, each shard gathers and folds only its
    ``1/n_shards`` row slice of the slot's selected blocks, and the
    per-round fold delta merges across the mesh inside :func:`_fold`
    (one collective set per slot per round). ``shard.merge_every = K >
    1`` applies the deterministic collective cadence of
    :func:`build_query_loop` to the whole pass: one shared
    ``pend_rounds`` schedule (merges fire at a round start exactly when
    K rounds of delta are pending — zero cross-shard communication
    between merges), per-slot pending delta slots, per-query intervals /
    finished flags frozen between merges (selection gates on the stale
    flags — at most K rounds of extra blocks for a query that just
    finished), and finish-time snapshots recorded at merges. The cadence
    requires all anchors at zero: a mid-lap joiner's observable round
    boundaries would be merge boundaries, up to K rounds apart, so its
    delta schedule could not match its solo run.
    """
    S = len(slot_specs)
    anchors = tuple(anchors) if anchors is not None else (0,) * S
    round_offsets = (tuple(round_offsets) if round_offsets is not None
                     else (0,) * S)
    row_offsets = (tuple(row_offsets) if row_offsets is not None
                   else (0,) * S)
    cadence = shard is not None and shard.merge_every > 1
    if cadence and any(a != 0 for a in anchors):
        raise ValueError(
            "mid-scan admission (anchor > 0) does not compose with the "
            "collective cadence (merge_every > 1): a joiner's refresh "
            "schedule would be quantized to merge boundaries, up to K "
            "rounds apart from its solo run's; admit onto a fresh pass "
            "or a merge_every=1 pass")
    lap_ends = tuple(a + nb for a in anchors)
    i32 = jnp.int32
    i64 = jnp.int64

    def _slot_select(bufs, sc, s, spec, sel_queries):
        """One slot's round selection at its own cursor: window slice,
        the slot's activity flags (union over its queries), budgeted
        take. Returns ``_round_scan``'s tuple."""

        def flags_src(ok, b0):
            if spec.probe:
                rows = [pack_active_device(qc.active, spec.n_words)
                        for qc in sel_queries[s]]
            else:
                rows = [(~qc.finished).astype(jnp.uint32).reshape(1)
                        for qc in sel_queries[s]]
            stack = jnp.stack(rows)
            act = kops.active_blocks_multi(
                _window_rows(bufs.words[s], b0, window), stack,
                impl=impl) > 0
            return (ok[None, :] & act).any(axis=0)

        return _round_scan(bufs, sc.pos, flags_src, nb=nb, window=window,
                           budget=budget, bound=lap_ends[s], wrap=True)

    def _slot_account(bufs, sc, s, spec, k, scan):
        """Slot-local coverage / taint / metric accounting for one round
        (twin of the solo loop's accounting block); returns the updated
        SlotCarry fields as a dict."""
        le = lap_ends[s]
        acct = _account(sc, bufs.presence[s], bufs.presence_total[s], scan,
                        lim=le, probe=spec.probe, window=window,
                        budget=budget, lookahead=lookahead,
                        cover_cap=cover_cap)
        new_pos = scan[5]
        with jax.named_scope("account"):
            acct["lap_rounds"] = jnp.where(
                (sc.pos < le) & (new_pos >= le), k, sc.lap_rounds)
        return acct

    def _slot_rows(bufs, s, p_end):
        """Rows the slot's cursor has covered, as the f64 ``r`` of its
        refresh: rows over pass positions are periodic with period
        ``nb`` (one lap = the whole scramble), so laps + ``cum_rows``
        suffice; ``row_offsets[s]`` rebases to the slot's own lap."""
        p_end = jnp.minimum(p_end, lap_ends[s])
        pm1 = p_end - 1
        rows_abs = jnp.where(
            p_end > 0,
            (pm1 // nb).astype(i64) * bufs.cum_rows[nb - 1]
            + bufs.cum_rows[pm1 % nb],
            jnp.asarray(0, i64))
        return (rows_abs - row_offsets[s]).astype(jnp.float64)

    def _slot_refresh(bufs, s, queries, k_s, p_end, state, hist, tainted,
                      exact, metrics, live, n_live):
        """Every query of slot ``s``: CI refresh / stop test at slot round
        ``k_s`` with the slot's cursor at ``p_end``, and the finish-time
        snapshots (``metrics``: the slot's scan counters) of queries that
        finish now. A frozen slot (``live`` False) stops refreshing: a
        lapped slot's solo twin exited the loop at exhaustion, and its
        still-active queries await the host recovery pass. Returns the
        new query carries and the pass's live-query count."""
        le = lap_ends[s]
        with jax.named_scope("refresh"):
            r_s = _slot_rows(bufs, s, p_end)
            out = []
            for qi, qc in enumerate(queries):
                nlo, nhi, nest, nrefr, nact = refresh_fns[s][qi](
                    k_s, r_s, state, hist, tainted, exact, qc.lo, qc.hi,
                    qc.est, qc.refreshed, qc.active)
                fin = qc.finished
                skip = fin | ~live
                keep = lambda old, new: jnp.where(skip, old, new)
                active = keep(qc.active, nact)
                now_fin = live & ~fin & ~active.any()
                n_live = n_live - now_fin.astype(i32)
                snap = lambda new, old: jnp.where(now_fin, new, old)
                out.append(qc._replace(
                    lo=keep(qc.lo, nlo), hi=keep(qc.hi, nhi),
                    est=keep(qc.est, nest),
                    refreshed=keep(qc.refreshed, nrefr),
                    active=active, finished=fin | now_fin,
                    stopped_early=snap(p_end < le, qc.stopped_early),
                    finish_rounds=snap(k_s, qc.finish_rounds),
                    finish_pos=snap(p_end, qc.finish_pos),
                    finish_blocks_fetched=snap(
                        metrics["blocks_fetched"],
                        qc.finish_blocks_fetched),
                    finish_skipped_static=snap(
                        metrics["skipped_static"],
                        qc.finish_skipped_static),
                    finish_skipped_active=snap(
                        metrics["skipped_active"],
                        qc.finish_skipped_active),
                    finish_probes=snap(metrics["probes"],
                                       qc.finish_probes),
                    snap_counts=snap(state.count, qc.snap_counts),
                    snap_exact=snap(exact, qc.snap_exact),
                    snap_tainted=snap(tainted, qc.snap_tainted)))
        return tuple(out), n_live

    def body(bufs, c: PassCarry) -> PassCarry:
        k = c.rounds + 1
        new_slots = []
        new_queries = []
        n_live = c.n_live
        for s, spec in enumerate(slot_specs):
            sc = c.slots[s]
            le = lap_ends[s]
            any_unfin = functools.reduce(
                jnp.logical_or, [~qc.finished for qc in c.queries[s]])
            # a slot whose lap ended or whose queries all finished is
            # frozen in place: its solo twin would have exited its loop,
            # so letting the cursor run on would diverge the slot's
            # metrics (and, with every query finished, cover ground
            # without selecting — spuriously tainting the views)
            slot_live = (sc.pos < le) & any_unfin
            scan = _slot_select(bufs, sc, s, spec, c.queries)
            win, ok, flags, take, csum, new_pos, covmask = scan
            # Under shard_map the local slab is this shard's row slice
            # of every block, so the slot's global block ids gather
            # exactly the shard's 1/n_shards of its selection.
            v, g, m = _gather_rows(bufs.values[s], bufs.gids[s], bufs.mask,
                                   csum, win, window, budget)
            dstate, dhist = _fold(v, g, m, spec.center, spec.a, spec.b,
                                  spec.num_groups, spec.nbins,
                                  spec.use_hist, impl,
                                  shard_axes=shard.axes if shard else None)
            state, hist = _merge_round(sc.state, sc.hist, dstate, dhist,
                                       spec.use_hist)
            acct = _slot_account(bufs, sc, s, spec, k, scan)
            tainted, exact = acct["tainted"], acct["exact"]

            frz = lambda new, old: jnp.where(slot_live, new, old)
            with jax.named_scope("account"):
                new_slots.append(SlotCarry(
                    pos=frz(new_pos, sc.pos),
                    state=jax.tree.map(frz, state, sc.state),
                    hist=(frz(hist, sc.hist) if spec.use_hist else None),
                    seen_presence=frz(acct["seen_presence"],
                                      sc.seen_presence),
                    tainted=frz(tainted, sc.tainted),
                    exact=frz(exact, sc.exact),
                    processed=frz(acct["processed"], sc.processed),
                    blocks_fetched=frz(acct["blocks_fetched"],
                                       sc.blocks_fetched),
                    skipped_static=frz(acct["skipped_static"],
                                       sc.skipped_static),
                    skipped_active=frz(acct["skipped_active"],
                                       sc.skipped_active),
                    probes=frz(acct["probes"], sc.probes),
                    lap_rounds=frz(acct["lap_rounds"], sc.lap_rounds)))

            slot_queries, n_live = _slot_refresh(
                bufs, s, c.queries[s], k - round_offsets[s], new_pos, state,
                hist, tainted, exact, acct, slot_live, n_live)
            new_queries.append(slot_queries)

        return PassCarry(
            rounds=k, it=c.it + 1, n_live=n_live,
            slots=tuple(new_slots), queries=tuple(new_queries))

    # -- collective cadence (shard.merge_every = K > 1) ------------------

    def _merge_refresh_pass(bufs, c: PassCarry) -> PassCarry:
        """Pass twin of build_query_loop's ``_merge_refresh``: one
        collective set per slot on the pending multi-round deltas, then
        every unfinished query's CI refresh / stop test on fully-merged
        stats (delta-schedule index ``c.rounds``), with finish-time
        snapshots taken from the merged values. Frozen slots carry
        zeroed pending deltas (they stopped folding when they froze),
        so their collectives are no-ops and their queries are already
        finished or awaiting the dispatch-exit flush."""
        new_slots = []
        new_queries = []
        n_live = c.n_live
        for s, spec in enumerate(slot_specs):
            sc = c.slots[s]
            with jax.named_scope("merge"):
                sums = jax.lax.psum(sc.pend_sums, shard.axes)
                vmin, vmax = _pmin_pmax_f64(sc.pend_vmin, sc.pend_vmax,
                                            shard.axes)
                dstate = kops.moments_from_sums(sums, vmin, vmax,
                                                spec.center)
                state = merge_moments(sc.state, dstate)
                hist = (sc.hist + jax.lax.psum(sc.pend_hist, shard.axes)
                        if spec.use_hist else sc.hist)
            new_slots.append(sc._replace(
                state=state, hist=hist,
                **_zeroed_pending(sc, spec.use_hist)))
            slot_queries, n_live = _slot_refresh(
                bufs, s, c.queries[s], c.rounds - round_offsets[s], sc.pos,
                state, hist, sc.tainted, sc.exact, sc._asdict(),
                jnp.asarray(True), n_live)
            new_queries.append(slot_queries)
        return c._replace(
            n_live=n_live, slots=tuple(new_slots),
            queries=tuple(new_queries),
            pend_rounds=jnp.asarray(0, i32))

    def cadence_body(bufs, c: PassCarry) -> PassCarry:
        # see build_query_loop.cadence_body: the merge fires at the
        # round start on the replicated pend_rounds counter (a
        # deterministic schedule — no per-round hint, no pmax, zero
        # cross-shard communication between merges); selection gates on
        # the PRE-merge per-query flags so the merge collective overlaps
        # the scan, and intervals / finished flags only change at
        # merges.
        sel_queries = c.queries
        c = jax.lax.cond(c.pend_rounds >= shard.merge_every,
                         functools.partial(_merge_refresh_pass, bufs),
                         lambda x: x, c)
        k = c.rounds + 1
        new_slots = []
        for s, spec in enumerate(slot_specs):
            sc = c.slots[s]
            any_unfin = functools.reduce(
                jnp.logical_or, [~qc.finished for qc in c.queries[s]])
            slot_live = (sc.pos < lap_ends[s]) & any_unfin
            scan = _slot_select(bufs, sc, s, spec, sel_queries)
            win, ok, flags, take, csum, new_pos, covmask = scan
            v, g, m = _gather_rows(bufs.values[s], bufs.gids[s], bufs.mask,
                                   csum, win, window, budget)
            dsums, dvmin, dvmax, dhist = _fold_local(
                v, g, m, spec.center, spec.a, spec.b, spec.num_groups,
                spec.nbins, spec.use_hist, impl)
            pend = _pend_round(sc, dsums, dvmin, dvmax, dhist,
                               spec.use_hist)
            acct = _slot_account(bufs, sc, s, spec, k, scan)

            frz = lambda new, old: jnp.where(slot_live, new, old)
            with jax.named_scope("account"):
                new_slots.append(sc._replace(
                    pos=frz(new_pos, sc.pos),
                    seen_presence=frz(acct["seen_presence"],
                                      sc.seen_presence),
                    tainted=frz(acct["tainted"], sc.tainted),
                    exact=frz(acct["exact"], sc.exact),
                    processed=frz(acct["processed"], sc.processed),
                    blocks_fetched=frz(acct["blocks_fetched"],
                                       sc.blocks_fetched),
                    skipped_static=frz(acct["skipped_static"],
                                       sc.skipped_static),
                    skipped_active=frz(acct["skipped_active"],
                                       sc.skipped_active),
                    probes=frz(acct["probes"], sc.probes),
                    lap_rounds=frz(acct["lap_rounds"], sc.lap_rounds),
                    pend_sums=frz(pend["pend_sums"], sc.pend_sums),
                    pend_vmin=frz(pend["pend_vmin"], sc.pend_vmin),
                    pend_vmax=frz(pend["pend_vmax"], sc.pend_vmax),
                    pend_hist=(frz(pend["pend_hist"], sc.pend_hist)
                               if spec.use_hist else None)))

        return c._replace(
            rounds=k, it=c.it + 1, slots=tuple(new_slots),
            pend_rounds=c.pend_rounds + 1)

    def flush(bufs, carry: PassCarry) -> PassCarry:
        # see build_query_loop.flush
        return jax.lax.cond(carry.pend_rounds > 0,
                            functools.partial(_merge_refresh_pass, bufs),
                            lambda x: x, carry)

    loop_body = cadence_body if cadence else body

    def cond(c: PassCarry):
        progressable = jnp.asarray(False)
        for s in range(S):
            unfin = functools.reduce(
                jnp.logical_or, [~qc.finished for qc in c.queries[s]])
            progressable = progressable | (
                (c.slots[s].pos < lap_ends[s]) & unfin)
        go = progressable & (c.rounds < max_rounds) & (c.n_live > 0)
        if chunk is not None:
            go = go & (c.it < chunk)
        return go

    def chunk_body(bufs: PassLoopBuffers, carry: PassCarry) -> PassCarry:
        carry = carry._replace(
            it=jnp.asarray(0, jnp.int32),
            slots=tuple(sc._replace(
                processed=_pad_processed(sc.processed, window))
                for sc in carry.slots))
        carry = jax.lax.while_loop(cond,
                                   functools.partial(loop_body, bufs),
                                   carry)
        if cadence:
            carry = flush(bufs, carry)
        return carry._replace(slots=tuple(
            sc._replace(processed=_fold_processed(sc.processed, nb))
            for sc in carry.slots))

    if shard is None:
        return jax.jit(chunk_body)

    rep = P()
    data = P(None, shard.axes)  # row-axis sliced, block axis whole
    ns = len(slot_specs)
    bufs_spec = PassLoopBuffers(
        mask=data, order_pad=rep, static_ok=rep, cum_rows=rep,
        values=(data,) * ns, gids=(data,) * ns, words=(rep,) * ns,
        presence=(rep,) * ns, presence_total=(rep,) * ns)
    carry_spec = _pass_carry_spec(slot_specs,
                                  [len(fns) for fns in refresh_fns],
                                  cadence)
    # check_vma=False: see build_query_loop — carry replication holds by
    # construction but is opaque to the checker.
    return jax.jit(jax.shard_map(
        chunk_body, mesh=shard.mesh, in_specs=(bufs_spec, carry_spec),
        out_specs=carry_spec, check_vma=False))
