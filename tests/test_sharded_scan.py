"""Sharded fused round loop: in-process multi-device suite + the
single-device-safe pieces (layout regression tests, config guards).

The mesh scenarios need a multi-device platform at jax init time — the
CI multi-device job runs pytest under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so they execute
in-process here (granular reporting); on single-device machines they
skip and tier-1 coverage comes from the subprocess workers in
``tests/test_distributed.py``.
"""

import jax
import numpy as np
import pytest

from repro.aqp import EngineConfig, build_scramble
from repro.aqp.distributed import build_block_shards, make_aqp_mesh
from repro.core.lru import LRUCache
from repro.data import flights

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs a multi-device platform (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=N before jax init)")


# -- mesh scenarios (in-process twins of the subprocess worker) --------------


def _scenarios():
    from tests.helpers import sharded_scenarios
    return sharded_scenarios


@multidevice
@pytest.mark.parametrize("name", [
    "scenario_groupby_topk", "scenario_filtered_sum", "scenario_taint",
    "scenario_exhaustion_bitwise", "scenario_early_stop_bitwise",
    "scenario_uneven_tail", "scenario_last_block_start",
    "scenario_server_pass",
    "scenario_carousel_sharded_lap",
    "scenario_cadence_superset_sync", "scenario_cadence_merge_confirm",
    "scenario_cadence_exhaustion", "scenario_cadence_early_stop",
    "scenario_cadence_server_pass",
    "scenario_cadence_slot_queries_finish_apart",
])
def test_sharded_scenario(name, x64_module):
    getattr(_scenarios(), name)()


@multidevice
@pytest.mark.skipif(jax.device_count() < 4,
                    reason="2-D mesh scenario needs >= 4 devices")
def test_sharded_2d_mesh(x64_module):
    _scenarios().scenario_groupby_threshold_2d_mesh()


# -- config guards (single-device safe) --------------------------------------


def test_shard_rows_requires_multiple_devices():
    if jax.device_count() >= 2:
        pytest.skip("guard only fires on a single-device platform")
    with pytest.raises(ValueError, match="2 devices"):
        EngineConfig(shard_rows=True, device_loop=True).resolve_shard_rows()


def test_shard_rows_auto_off_on_one_device():
    cfg = EngineConfig(shard_rows=None, mesh_shape=(1,))
    assert cfg.resolve_shard_rows() is False


def test_shard_rows_requires_device_loop(x64):
    with pytest.raises(ValueError, match="device-resident round loop"):
        EngineConfig(shard_rows=True, device_loop=False,
                     mesh_shape=(max(jax.device_count(), 2),)
                     ).resolve_shard_rows()


def test_mesh_shape_larger_than_platform_raises():
    with pytest.raises(ValueError, match="devices"):
        make_aqp_mesh((jax.device_count() + 1,))


@pytest.mark.parametrize("bad", [0, -1])
def test_merge_every_must_be_positive(bad):
    with pytest.raises(ValueError, match="merge_every"):
        EngineConfig(merge_every=bad)
    with pytest.raises(ValueError, match="merge_every"):
        build_block_shards(64, _FakeMesh(4), 256, merge_every=bad)


def test_merge_every_threads_through_layout():
    shards = build_block_shards(64, _FakeMesh(4), 256, merge_every=4)
    assert shards.merge_every == 4
    assert shards.info.merge_every == 4
    # default stays the per-round-merge oracle
    assert build_block_shards(64, _FakeMesh(4), 256).info.merge_every == 1


# -- block-shard layout (single-device safe) ---------------------------------


class _FakeMesh:
    def __init__(self, n):
        self.devices = np.empty(n, dtype=object)
        self.axis_names = ("shards",)


@pytest.mark.parametrize("block_rows,n_shards", [(157, 8), (61, 4), (8, 8),
                                                 (5, 8), (64, 8)])
def test_block_shards_layout(block_rows, n_shards):
    """Row-slice layout: equal-length contiguous row slices covering
    [0, block_rows) exactly once; padding only past block_rows; the
    block axis whole on every shard."""
    nb = 16
    shards = build_block_shards(nb, _FakeMesh(n_shards), block_rows)
    assert shards.nb == nb            # block axis is never split
    R = shards.shard_rows
    assert R == -(-block_rows // n_shards)
    assert shards.padded_block_rows >= block_rows
    # padding is strictly less than one row slice per shard
    assert shards.padded_block_rows - block_rows < n_shards
    # every real row owned by exactly one shard
    owner = np.full(block_rows, -1)
    for d in range(n_shards):
        lo, hi = d * R, min((d + 1) * R, block_rows)
        assert (owner[lo:hi] == -1).all()
        owner[lo:hi] = d
    assert (owner >= 0).all()
    # pad_rows appends zeros only, on the row axis; blocks untouched
    arr = np.arange(nb * block_rows, dtype=np.float32).reshape(
        nb, block_rows) + 1.0
    padded = shards.pad_rows(arr)
    assert padded.shape == (nb, shards.padded_block_rows)
    np.testing.assert_array_equal(padded[:, :block_rows], arr)
    assert (padded[:, block_rows:] == 0).all()


# -- Scramble.device_shard uneven-tail regression ----------------------------


@pytest.mark.parametrize("nb,n_shards", [(157, 8), (61, 4), (13, 5),
                                         (7, 8), (64, 8)])
def test_device_shard_uneven_tail(nb, n_shards):
    """n_blocks not divisible by n_shards: no block dropped, none
    duplicated, shard sizes differ by <= 1, rows conserved."""
    rng = np.random.default_rng(0)
    n_rows = nb * 32 - 7           # ragged final block too
    cols = {"v": rng.normal(size=n_rows).astype(np.float32),
            "g": rng.integers(0, 4, n_rows).astype(np.int32)}
    sc = build_scramble(cols, block_rows=32, seed=1)
    assert sc.n_blocks == nb
    shards = [sc.device_shard(i, n_shards) for i in range(n_shards)]
    sizes = [s.n_blocks for s in shards]
    assert sum(sizes) == sc.n_blocks
    assert max(sizes) - min(sizes) <= 1
    assert sum(s.n_rows for s in shards) == sc.n_rows
    # exact partition: concatenated shard columns == the scramble's
    got = np.concatenate([s.columns["v"] for s in shards])
    np.testing.assert_array_equal(got, sc.columns["v"])
    got_valid = np.concatenate([s.valid for s in shards])
    np.testing.assert_array_equal(got_valid, sc.valid)


def test_device_shard_full_dataset_roundtrip():
    """Values survive sharding exactly (sorted multiset equality over
    valid rows), uneven shard count included."""
    ds = flights.generate(n_rows=10_000, n_airports=12, seed=0)
    sc = build_scramble(ds.columns, block_rows=256, seed=1)
    assert sc.n_blocks % 3 != 0
    shards = [sc.device_shard(i, 3) for i in range(3)]
    got = np.concatenate([s.columns["dep_delay"][s.valid] for s in shards])
    np.testing.assert_allclose(np.sort(got),
                               np.sort(ds.columns["dep_delay"]))


# -- LRUCache (the promoted public helper) -----------------------------------


def test_lru_cache_semantics():
    cache = LRUCache(2)
    built = []

    def make(v):
        def build():
            built.append(v)
            return v
        return build

    assert cache.get_or_build("a", make(1)) == 1
    assert cache.get_or_build("b", make(2)) == 2
    assert cache.get_or_build("a", make(99)) == 1       # hit, no rebuild
    assert cache.get_or_build("c", make(3)) == 3        # evicts "b" (LRU)
    assert "b" not in cache and "a" in cache and "c" in cache
    assert built == [1, 2, 3]
    assert len(cache) == 2
    assert cache["a"] == 1
    with pytest.raises(KeyError):
        cache["b"]
    cache.clear()
    assert len(cache) == 0
    with pytest.raises(ValueError):
        LRUCache(0)
