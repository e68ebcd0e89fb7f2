"""Compile-only tests for a TPU v5e chip: the main path's Pallas kernels
at the widths ``chip_smoke.py`` runs (a 64-block x 1024-row round, 128
and ~10k groups, 1024 histogram bins) and the single-chip device round
loop, compiled for a described -- not attached -- ``v5e:2x2`` topology.

Nothing runs: a passing test says the chip's compiler accepts the
program (Mosaic tile layouts, VMEM, the f64 carry), not that it is right
or fast. Interpret-mode and ``ref`` tests check the numbers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.aqp import EngineConfig, FastFrame, build_scramble
from repro.aqp import engine
from repro.aqp import flights_queries as fq
from repro.data import flights
from repro.kernels import fused_scan
from repro.kernels import ops as kops

ROWS = 64 * 1024      # round_blocks x block_rows of one scan round
WINDOW = 4096         # probe window: lookahead 1024, cover cap 64 x 64
NBINS = 1024
GROUPS = [128, 10240]  # one group tile; composite origin x airline
CENTER, A, B = 870.0, -60.0, 1800.0   # dep_delay catalog range


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology, with the persistent
    compilation cache off (a TPU compile written here cannot be read
    back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(params=[False, True], ids=["x32", "x64"])
def x64_mode(request):
    """Both type modes: under x64 (the device loop's mode) a literal
    block index traces as int64, which Mosaic refuses."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", request.param)
    yield
    jax.config.update("jax_enable_x64", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


FOLDS = {
    "block_agg": lambda G: lambda v, g, m: kops.grouped_sums(
        v, g, m, G, CENTER, impl="pallas"),
    "grouped_hist": lambda G: lambda v, g, m: kops.grouped_hist(
        v, g, m, G, A, B, nbins=NBINS, impl="pallas").hist,
    "fused_fold": lambda G: lambda v, g, m: fused_scan.fused_fold(
        v, g, m, jnp.float32(CENTER), a=A, b=B, num_groups=G,
        nbins=NBINS),
}


@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("kernel", sorted(FOLDS))
def test_fold_kernel_compiles(one_chip, x64_mode, kernel, groups):
    _compile_kernel(FOLDS[kernel](groups),
                    _shape(one_chip, (ROWS,), jnp.float32),
                    _shape(one_chip, (ROWS,), jnp.int32),
                    _shape(one_chip, (ROWS,), jnp.float32))


@pytest.mark.parametrize("groups", GROUPS)
def test_active_blocks_compiles(one_chip, x64_mode, groups):
    words = groups // 32
    _compile_kernel(
        lambda bm, act: kops.active_blocks(bm, act, impl="pallas"),
        _shape(one_chip, (WINDOW, words), jnp.uint32),
        _shape(one_chip, (words,), jnp.uint32))


def _solo_loop(frame, q, slot, qci):
    """``FastFrame.run``'s device loop of ``q`` (one slot, one query,
    with the probe) from scan start 0, and its first carry."""
    loop = engine._DeviceLoop(frame, q, slot, qci, probe=True,
                              lookahead=1024, max_rounds=100_000)
    order = np.arange(frame.scramble.n_blocks)
    _, carry = loop.start(slot, qci, 0, np.cumsum(frame._valid_counts[order]))
    return loop, carry


@pytest.mark.parametrize("bounder", ["bernstein", "anderson_dkw"])
def test_query_loop_compiles(one_chip, x64, bounder):
    """The f64 device round loop of one GROUP BY query with the bitmap
    probe, under ``impl='pallas'``: Bernstein+RangeTrim folds moments
    only, Anderson/DKW folds the histogram through ``fused_fold``."""
    ds = flights.generate(n_rows=150 * 1024, seed=0)
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=1024,
                        seed=1)
    frame = FastFrame(sc, EngineConfig(impl="pallas", shard_rows=False))
    q = fq.f_q9(bounder=bounder, rangetrim=bounder == "bernstein")
    slot = engine._ScanViews(frame, q)
    qci = engine._QueryIntervals(frame, q, slot)
    loop, carry = _solo_loop(frame, q, slot, qci)
    as_shapes = lambda tree: jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    compiled = loop._chunk_fn.lower(as_shapes(loop.bufs),
                                    as_shapes(carry)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "f64" in text


@pytest.mark.parametrize("kernel", sorted(FOLDS))
def test_fold_kernel_carries_its_name(one_chip, kernel):
    """Each Pallas kernel names itself (as ``FOLDS`` keys it) in the
    lowered program, so the chip's trace and its compiler logs tell the
    kernels apart."""
    low = jax.jit(FOLDS[kernel](GROUPS[0])).lower(
        _shape(one_chip, (ROWS,), jnp.float32),
        _shape(one_chip, (ROWS,), jnp.int32),
        _shape(one_chip, (ROWS,), jnp.float32))
    assert f'kernel_name = "{kernel}"' in low.as_text()


@pytest.mark.parametrize("bounder, kernels", [
    ("bernstein", ("active_blocks", "block_agg")),
    ("anderson_dkw", ("active_blocks", "fused_fold")),
])
def test_query_loop_names_scopes_and_kernels(one_chip, x64, bounder,
                                             kernels):
    """The lowered f64 round loop, as the chip runs it: every operation's
    name stack holds its phase (``fused_scan.SCOPES``), and the Pallas
    kernels it calls carry their names."""
    ds = flights.generate(n_rows=64 * 1024, seed=0)
    sc = build_scramble(ds.columns, catalog=ds.catalog, block_rows=1024,
                        seed=1)
    frame = FastFrame(sc, EngineConfig(impl="pallas", shard_rows=False))
    q = fq.f_q9(bounder=bounder, rangetrim=bounder == "bernstein")
    slot = engine._ScanViews(frame, q)
    qci = engine._QueryIntervals(frame, q, slot)
    loop, carry = _solo_loop(frame, q, slot, qci)
    as_shapes = lambda tree: jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    text = loop._chunk_fn.lower(as_shapes(loop.bufs),
                                as_shapes(carry)).as_text(debug_info=True)
    for scope in fused_scan.SCOPES:
        assert f"/{scope}/" in text, scope
    for name in kernels:
        assert f'kernel_name = "{name}"' in text, name
