"""Mesh construction + sharding specs for the sharded fused round loop.

This module is deliberately thin: the *computation* of the sharded scan
lives in :mod:`repro.kernels.fused_scan` (the round body runs under
``shard_map`` with the per-round fold delta merged by ``psum`` / ``pmin``
/ ``pmax`` inside the ``lax.while_loop`` carry — see
:func:`repro.kernels.fused_scan.build_query_loop`). What lives here is
everything the engine needs to *feed* that path:

  * :func:`make_aqp_mesh` — flatten the local devices (or an explicit
    ``EngineConfig.mesh_shape``) into the mesh the scan is divided over;
  * :class:`BlockShards` — the divided-scan layout: the *within-block
    row axis* of every ``(nb, block_rows)`` column slab is split into
    ``n_shards`` equal row slices (zero-padded so ``block_rows`` divides
    evenly), the block axis stays whole on every device, plus the
    ``device_put`` helpers that place the engine's device-resident
    column slabs (row-slice-sharded) and its small per-block metadata
    (replicated);
  * :func:`make_sharded_fold` — the standalone one-round collective fold
    (per-shard :func:`repro.kernels.ops.grouped_sums` + ``psum`` of the
    raw additive sums + ``pmin``/``pmax`` extremes), the building block
    the launch dry-run lowers and the bitwise merge tests pin down.

The layout invariants (also asserted by ``tests/test_sharded_scan.py``):

  * every shard sees the FULL block axis, so block selection, the
    cursor, and all accounting run on replicated inputs and never need
    translation to shard-local coordinates — the round body inside
    ``shard_map`` is the unsharded round body, applied to this shard's
    ``block_rows / n_shards`` row slice of every block;
  * rows within a block are exchangeable (the scramble shuffles rows
    into blocks), so slicing the row axis preserves uniformity exactly
    as block-axis slicing did; padding rows carry ``mask == 0`` /
    ``values == 0`` / ``gids == 0`` and contribute exact zeros to the
    additive fold;
  * each shard gathers and folds only ``1 / n_shards`` of every selected
    block's rows — the scan compute itself divides across the mesh;
  * the collective payload per merge is O(groups) bytes (raw moment sums
    + extremes + optional histogram), and on a cadence
    (``merge_every=K``) there is *zero* cross-shard communication
    between merges — no per-round rendezvous of any kind.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.state import MomentState
from repro.kernels import fused_scan as kfused
from repro.kernels import ops as kops

DEFAULT_AXIS = "shards"

__all__ = ["BlockShards", "DEFAULT_AXIS", "build_block_shards",
           "make_aqp_mesh", "make_sharded_fold", "place_replicated",
           "shard_rows"]


def make_aqp_mesh(mesh_shape: Optional[Tuple[int, ...]] = None
                  ) -> Optional[Mesh]:
    """Build the device mesh the scramble's block axis is sharded over.

    ``mesh_shape=None`` uses every local device as a 1-D ``"shards"``
    axis; an explicit shape (e.g. ``EngineConfig.mesh_shape=(2, 4)``)
    gets axes ``("shard0", "shard1", ...)`` — the block axis is sharded
    over ALL axes (flattened), so the shape only controls device
    placement. Returns ``None`` when the mesh would have a single device
    (sharding is pure overhead there).

    Raises:
        ValueError: when ``mesh_shape`` asks for more devices than the
            platform provides.
    """
    devices = jax.devices()
    if mesh_shape is None:
        if len(devices) < 2:
            return None
        return Mesh(np.asarray(devices), (DEFAULT_AXIS,))
    n = math.prod(mesh_shape)
    if n > len(devices):
        raise ValueError(
            f"EngineConfig.mesh_shape={mesh_shape} needs {n} devices but "
            f"only {len(devices)} are visible (on CPU hosts use "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "jax initializes)")
    if n == 1:
        return None
    if len(mesh_shape) == 1:
        return Mesh(np.asarray(devices[:n]), (DEFAULT_AXIS,))
    axes = tuple(f"shard{i}" for i in range(len(mesh_shape)))
    return Mesh(np.asarray(devices[:n]).reshape(mesh_shape), axes)


@dataclasses.dataclass(frozen=True)
class BlockShards:
    """Divided-scan layout of a scramble's column slabs over a mesh.

    The within-block row axis (axis 1 of every ``(nb, block_rows, ...)``
    slab) is split into ``n_shards`` equal slices of ``shard_rows`` rows
    each; ``block_rows`` is zero-padded up to ``n_shards * shard_rows``
    so every device holds an equal-shape slab (padding rows carry
    ``mask == 0`` and fold to exact zeros). The block axis is whole on
    every shard, so selection and the cursor need no per-shard
    translation.
    """

    mesh: Mesh
    axes: Tuple[str, ...]
    nb: int               # global block count (whole on every shard)
    block_rows: int       # real rows per block
    n_shards: int
    shard_rows: int       # padded per-shard rows per block
    merge_every: int = 1  # collective cadence K (1 = merge every round)

    @property
    def padded_block_rows(self) -> int:
        return self.n_shards * self.shard_rows

    @property
    def info(self) -> kfused.ShardInfo:
        """The kernel-layer view of this layout."""
        return kfused.ShardInfo(mesh=self.mesh, axes=self.axes,
                                n_shards=self.n_shards,
                                shard_rows=self.shard_rows,
                                merge_every=self.merge_every)

    def pad_rows(self, arr: np.ndarray) -> np.ndarray:
        """Zero-pad a ``(nb, block_rows, ...)`` slab's row axis to
        ``padded_block_rows``."""
        pad = self.padded_block_rows - arr.shape[1]
        if pad == 0:
            return arr
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2)
        return np.pad(arr, widths)

    def put_blocks(self, arr) -> jax.Array:
        """Pad + place a column slab with its row axis sharded over the
        mesh (block axis replicated)."""
        return jax.device_put(
            self.pad_rows(np.asarray(arr)),
            NamedSharding(self.mesh, P(None, self.axes)))

    def put_replicated(self, arr) -> jax.Array:
        """Place an array fully replicated on every mesh device."""
        return jax.device_put(np.asarray(arr),
                              NamedSharding(self.mesh, P()))


def place_replicated(shards: Optional[BlockShards], arr) -> jax.Array:
    """Device placement for a buffer every mesh device reads whole:
    replicated over the mesh when ``shards`` is set, a plain
    (single-device) array otherwise — the one placement dispatch shared
    by the engine's and the serving layer's buffer assembly."""
    if shards is not None:
        return shards.put_replicated(arr)
    return jnp.asarray(arr)


def build_block_shards(nb: int, mesh: Optional[Mesh], block_rows: int,
                       merge_every: int = 1) -> Optional[BlockShards]:
    """Divided-scan layout of ``nb`` scramble blocks of ``block_rows``
    rows each over ``mesh`` (None passes through: single-device frames
    carry no shard layout). ``merge_every`` is the collective cadence
    the sharded round loops run at (``EngineConfig.merge_every``; 1 =
    the per-round-merge oracle path)."""
    if mesh is None:
        return None
    if merge_every < 1:
        raise ValueError(
            f"merge_every must be >= 1, got {merge_every} (1 merges the "
            "shard folds every round; K > 1 amortizes the collective "
            "over K rounds)")
    n_shards = mesh.devices.size
    return BlockShards(mesh=mesh, axes=tuple(mesh.axis_names), nb=nb,
                       block_rows=block_rows, n_shards=n_shards,
                       shard_rows=-(-block_rows // n_shards),
                       merge_every=merge_every)


def make_sharded_fold(mesh: Mesh, dp_axes: Sequence[str], num_groups: int,
                      center: float, impl: Optional[str] = None,
                      with_hist: bool = False, hist_bins: int = 1024,
                      hist_range: Tuple[float, float] = (0.0, 1.0)):
    """Build the jitted one-round collective fold for a mesh.

    Each device folds its local rows with
    :func:`repro.kernels.ops.grouped_sums` (the raw additive
    (count, dsum, dsq) form about ``center``); the tiny per-group payload
    then crosses the mesh — ``psum`` for the sums (and histogram),
    ``pmin``/``pmax`` for the extremes — before the shifted-moment
    conversion. This is exactly the merge the sharded round loop performs
    inside its ``lax.while_loop`` (:mod:`repro.kernels.fused_scan`),
    exposed standalone for the launch dry-run and the bitwise merge
    tests: on exactly-representable data it equals the single-device
    :func:`~repro.kernels.ops.grouped_moments` fold bit for bit.

    Inputs (sharded over ``dp_axes`` on their leading axis):
      values, gids, mask: ``(rows,)`` row-major flattened blocks.
    Output: replicated merged :class:`~repro.core.state.MomentState`
    ``(num_groups,)`` [+ replicated histogram when ``with_hist``].
    """
    dp = tuple(dp_axes)
    spec = P(dp)

    def round_fn(values, gids, mask):
        sums, vmin, vmax = kops.grouped_sums(values, gids, mask,
                                             num_groups, center, impl=impl)
        sums = jax.lax.psum(sums, dp)
        vmin = jax.lax.pmin(vmin, dp)
        vmax = jax.lax.pmax(vmax, dp)
        out = kops.moments_from_sums(sums, vmin, vmax, center)
        if not with_hist:
            return out
        h = kops.grouped_hist(values, gids, mask, num_groups,
                              hist_range[0], hist_range[1],
                              nbins=hist_bins, impl=impl)
        return out, jax.lax.psum(h.hist, dp)

    rep_state = jax.tree.map(lambda _: P(), MomentState(0, 0, 0, 0, 0))
    sharded = jax.shard_map(
        round_fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(rep_state if not with_hist else (rep_state, P())),
        check_vma=False)
    return jax.jit(sharded)


def shard_rows(mesh: Mesh, dp_axes: Sequence[str], *arrays):
    """Place row-major arrays with their leading axis sharded over dp."""
    sharding = NamedSharding(mesh, P(tuple(dp_axes)))
    return tuple(jax.device_put(a, sharding) for a in arrays)
