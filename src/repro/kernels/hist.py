"""Pallas TPU kernel: per-group bucketized histogram (Anderson/DKW state).

hist[g, k] = sum_r mask_r * 1[gid_r == g] * 1[bin(v_r) == k]

Reformulated for the MXU as a product of two one-hots per tile, both
built transposed with the rows on the lane axis (the layout Mosaic
accepts; see :mod:`repro.kernels.block_agg`):

    hist_tile = onehot_groups @ onehot_bins.T     # (Gt, R) x (Kt, R)^T

Grid = (group_tiles, bin_tiles, row_tiles), row minor; the (g, k) output
block is revisited across row tiles and accumulated in place.

VMEM per program (ROW_TILE=1024, GROUP_TILE=128, BIN_TILE=512):
  onehot_bins 512*1024*4 = 2 MiB, onehot_groups 128*1024*4 = 0.5 MiB;
  a v5e compile accepts the kernel with 2.8 MiB of scoped VMEM
  (``docs/kernels.md``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_agg import NT_DIMS, block_index

ROW_TILE = 1024
GROUP_TILE = 128
BIN_TILE = 512


def tile_hist(v, onehot_g, a, inv_width, nbins, kbase, kt):
    """Per-tile histogram matmul shared by this kernel and the fused scan
    superkernel.

    ``v`` is the ``(1, R)`` value row and ``onehot_g`` the masked
    transposed ``(Gt, R)`` group one-hot (the same matrix the moment
    matmul consumes, so the fused kernel builds it once); returns the
    ``(Gt, kt)`` partial for bin tile ``[kbase, kbase + kt)``.
    """
    bin_idx = jnp.clip(((v - a) * inv_width), 0.0, nbins - 1.0
                       ).astype(jnp.int32)                       # (1, R)
    bins_tile = kbase + jax.lax.broadcasted_iota(
        jnp.int32, (kt, v.shape[1]), 0)
    onehot_b = (bin_idx == bins_tile).astype(jnp.float32)        # (Kt, R)
    return jax.lax.dot_general(onehot_g, onehot_b, NT_DIMS,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(scale_ref, values_ref, gids_ref, mask_ref, hist_ref):
    r = pl.program_id(2)
    g = pl.program_id(0)
    k = pl.program_id(1)
    gt, kt = hist_ref.shape

    a = scale_ref[0, 0]
    inv_width = scale_ref[0, 1]
    nbins = scale_ref[0, 2]

    v = values_ref[...]
    m = mask_ref[...].astype(jnp.float32)
    gids_tile = g * gt + jax.lax.broadcasted_iota(
        jnp.int32, (gt, v.shape[1]), 0)
    onehot_g = (gids_ref[...] == gids_tile).astype(jnp.float32) * m
    partial = tile_hist(v, onehot_g, a, inv_width, nbins, k * kt, kt)

    @pl.when(r == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += partial


@functools.partial(jax.jit, static_argnames=(
    "a", "b", "num_groups", "nbins", "nbins_data", "row_tile", "group_tile",
    "bin_tile", "interpret"))
def grouped_hist(values: jax.Array, gids: jax.Array, mask: jax.Array,
                 a: float, b: float, *, num_groups: int, nbins: int,
                 nbins_data: int = 0,
                 row_tile: int = ROW_TILE, group_tile: int = GROUP_TILE,
                 bin_tile: int = BIN_TILE, interpret: bool = False):
    """Raw launch; 1-D padded inputs; returns hist (num_groups, nbins).

    ``nbins`` is the (tile-padded) output width; ``nbins_data`` (default
    ``nbins``) is the *logical* bin count that defines the bucketization —
    bins >= nbins_data stay empty when the output is padded.
    """
    n = values.shape[0]
    assert n % row_tile == 0
    assert num_groups % group_tile == 0 and nbins % bin_tile == 0
    nbins_data = nbins_data or nbins
    v2 = values.astype(jnp.float32).reshape(1, n)
    g2 = gids.astype(jnp.int32).reshape(1, n)
    m2 = mask.astype(jnp.float32).reshape(1, n)
    inv_width = float(nbins_data) / max(float(b) - float(a), 1e-30)
    scale = jnp.asarray([[a, inv_width, float(nbins_data)]], jnp.float32)
    grid = (num_groups // group_tile, nbins // bin_tile, n // row_tile)
    row_spec = pl.BlockSpec((1, row_tile), lambda g, k, r: block_index(0, r))

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 3), lambda g, k, r: block_index(0, 0)),
                  row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((group_tile, bin_tile),
                               lambda g, k, r: block_index(g, k)),
        out_shape=jax.ShapeDtypeStruct((num_groups, nbins), jnp.float32),
        interpret=interpret,
        name="grouped_hist",
    )(scale, v2, g2, m2)
