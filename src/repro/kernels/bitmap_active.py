"""Pallas TPU kernel: packed-bitmap active-block scan (FastFrame lookahead).

Given a block x group bitmap packed into uint32 words (``bitmap[i, w]`` has
bit ``j`` set iff block ``i`` contains tuples of group ``32*w + j``) and the
packed active-group mask, mark blocks containing any active group:

    active_block[i] = any_w( bitmap[i, w] & active[w] ) != 0

This is the §4.3 "async lookahead" check: the paper batches 1024 blocks per
lookahead step for cache locality; here a whole tile of blocks is evaluated
per grid step out of VMEM, and the host uses the result to gather only
active blocks for the next scan round.

The words enter the kernel bitcast to int32 (same bits) and the "any"
is a lane max over ``!= 0`` flags: Mosaic has no reduction over
unsigned integers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_agg import block_index

BLOCK_TILE = 1024  # blocks per grid step


def tile_hit_any(bm, act):
    """(Bt, W) int32 words AND the (1, W) active mask -> (Bt, 1) int32
    flags (1 iff any word shares a set bit)."""
    hit = (jnp.bitwise_and(bm, act) != 0).astype(jnp.int32)
    return jnp.max(hit, axis=1, keepdims=True)


def _kernel(bitmap_ref, active_ref, out_ref):
    out_ref[...] = tile_hit_any(bitmap_ref[...], active_ref[...])


def _as_i32(words):
    return jax.lax.bitcast_convert_type(words.astype(jnp.uint32), jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_tile", "interpret"))
def active_blocks(bitmap: jax.Array, active_words: jax.Array, *,
                  block_tile: int = BLOCK_TILE, interpret: bool = False):
    """bitmap (nblocks, W) uint32, active_words (W,) uint32 ->
    int32 (nblocks, 1) flags. nblocks must be a multiple of block_tile."""
    nblocks, w = bitmap.shape
    assert nblocks % block_tile == 0
    return pl.pallas_call(
        _kernel,
        grid=(nblocks // block_tile,),
        in_specs=[
            pl.BlockSpec((block_tile, w), lambda i: block_index(i, 0)),
            pl.BlockSpec((1, w), lambda i: block_index(0, 0)),
        ],
        out_specs=pl.BlockSpec((block_tile, 1), lambda i: block_index(i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        interpret=interpret,
        name="active_blocks",
    )(_as_i32(bitmap), _as_i32(active_words).reshape(1, w))
