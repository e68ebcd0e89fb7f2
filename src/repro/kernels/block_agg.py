"""Pallas TPU kernel: fused masked per-group moment aggregation.

This is the scan hot loop of the paper's system (FastFrame's per-tuple
``update_state``).  A GPU port would scatter-add into per-group
accumulators; on TPU we reformulate the segment reduction as **one-hot
matmuls on the MXU** (DESIGN.md §3):

    count[g] = sum_r 1[gid_r == g] * mask_r
    dsum[g]  = sum_r (v_r - c) * 1[gid_r == g] * mask_r
    dsq[g]   = sum_r (v_r - c)^2 * 1[gid_r == g] * mask_r

computed as one ``(8, R) x (Gt, R)^T`` MXU matmul per (row-tile,
group-tile) (rows 0-2 carry ones, ``v - c`` and ``(v - c)^2``; the rest
are zero padding to a full sublane tile), plus lane-axis min/max
reductions for the RangeTrim extremes.  ``c`` is a fixed centering
constant (the catalog midpoint) so f32 accumulation does not cancel; the
exact shifted-moment identity recovers Welford ``(mean, m2)`` downstream
(``ops.grouped_moments``).

Tile layout (what Mosaic accepts): every row tile is loaded as a
``(1, R)`` vector with the rows on the lane axis, and the group one-hot
is built *transposed*, ``(Gt, R)``, from a sublane iota.  Nothing is
ever reshaped from lanes into a column, and both matmuls contract over
the last (lane) dims.  The matmuls run at ``Precision.HIGHEST`` so the
f32 moment sums are not demoted to one bf16 pass.

Grid = (group_tiles, row_tiles) with row_tiles minor: TPU grids execute
sequentially, so each group tile's output block is revisited across row
tiles and accumulated in place (`@pl.when(r == 0)` initializes).

VMEM at the defaults (ROW_TILE=2048, GROUP_TILE=256): the one-hot is
256 * 2048 * 4 B = 2 MiB of f32, and a v5e compile accepts the kernel
with 1.56 MiB of scoped VMEM (``docs/kernels.md``) — well under the
16 MiB scoped default of TPU v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 2048   # rows per grid step (must be a multiple of 128)
GROUP_TILE = 256  # groups per grid step (must be a multiple of 128)

# contract the lane (row) axis of both operands: (M, R) x (N, R) -> (M, N)
NT_DIMS = (((1,), (1,)), ((), ()))


def block_index(*idx):
    """An index map's block indices as int32. Under ``jax_enable_x64``
    (the device round loop's mode) a literal ``0`` traces as int64, and
    Mosaic refuses an index map that returns int64."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


def tile_moments(v, gid, m, center, gbase, gt):
    """Per-tile moment math shared by this kernel and the fused scan
    superkernel (:mod:`repro.kernels.fused_scan`).

    Inputs are ``(1, R)`` tile rows (rows on lanes); returns the MXU
    partial ``(3, gt)`` = (count, dsum, dsq), the min/max partials
    ``(gt, 1)``, and the masked transposed group one-hot ``(gt, R)`` so
    callers can reuse it (the fused kernel feeds it to the histogram
    matmul).
    """
    rt = v.shape[1]
    group_ids = gbase + jax.lax.broadcasted_iota(jnp.int32, (gt, rt), 0)
    onehot = (gid == group_ids).astype(jnp.float32) * m         # (Gt, R)

    dv = v - center
    row = jax.lax.broadcasted_iota(jnp.int32, (8, rt), 0)
    rows = jnp.where(row == 0, 1.0,
                     jnp.where(row == 1, dv,
                               jnp.where(row == 2, dv * dv, 0.0)))  # (8, R)
    partial = jax.lax.dot_general(
        rows, onehot, NT_DIMS, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:3]                 # (3, Gt) MXU

    sel = onehot > 0.0
    vmin_p = jnp.min(jnp.where(sel, v, jnp.inf), axis=1, keepdims=True)
    vmax_p = jnp.max(jnp.where(sel, v, -jnp.inf), axis=1, keepdims=True)
    return partial, vmin_p, vmax_p, onehot


def _kernel(center_ref, values_ref, gids_ref, mask_ref,
            sums_ref, vmin_ref, vmax_ref):
    r = pl.program_id(1)
    g = pl.program_id(0)
    gt = sums_ref.shape[1]

    c = center_ref[0, 0]
    m = mask_ref[...].astype(jnp.float32)
    partial, vmin_p, vmax_p, _ = tile_moments(
        values_ref[...], gids_ref[...], m, c, g * gt, gt)

    @pl.when(r == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        vmin_ref[...] = jnp.full_like(vmin_ref, jnp.inf)
        vmax_ref[...] = jnp.full_like(vmax_ref, -jnp.inf)

    sums_ref[...] += partial
    vmin_ref[...] = jnp.minimum(vmin_ref[...], vmin_p)
    vmax_ref[...] = jnp.maximum(vmax_ref[...], vmax_p)


@functools.partial(jax.jit, static_argnames=("num_groups", "row_tile",
                                             "group_tile", "interpret"))
def block_agg(values: jax.Array, gids: jax.Array, mask: jax.Array,
              center: jax.Array, *, num_groups: int,
              row_tile: int = ROW_TILE, group_tile: int = GROUP_TILE,
              interpret: bool = False):
    """Raw kernel launch. Inputs are 1-D and already padded:
    ``values.shape[0] % row_tile == 0`` and ``num_groups % group_tile == 0``
    (padding rows carry mask=0). Returns (sums(3,G), vmin(1,G), vmax(1,G)).
    """
    n = values.shape[0]
    assert n % row_tile == 0 and num_groups % group_tile == 0
    v2 = values.astype(jnp.float32).reshape(1, n)
    g2 = gids.astype(jnp.int32).reshape(1, n)
    m2 = mask.astype(jnp.float32).reshape(1, n)
    grid = (num_groups // group_tile, n // row_tile)
    c = jnp.asarray(center, jnp.float32).reshape(1, 1)
    row_spec = pl.BlockSpec((1, row_tile), lambda g, r: block_index(0, r))
    col_spec = pl.BlockSpec((group_tile, 1), lambda g, r: block_index(g, 0))

    sums, vmin, vmax = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda g, r: block_index(0, 0)),
                  row_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((3, group_tile), lambda g, r: block_index(0, g)),
                   col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((3, num_groups), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 1), jnp.float32),
        ],
        interpret=interpret,
        name="block_agg",
    )(c, v2, g2, m2)
    return sums, vmin.reshape(1, num_groups), vmax.reshape(1, num_groups)
