"""The engine's own tracing: the host spans of ``FastFrame.run`` and the
named scopes of the device round loops.

Spans are ``jax.profiler.TraceAnnotation`` events, recorded here under a
CPU profiler trace and read back with ``jax.profiler.ProfileData``.
Scopes are ``jax.named_scope`` components of every HLO ``op_name``,
checked in the lowered text of each round loop the engine builds: the
single-query loop, its collective-cadence body (over a one-device mesh)
and the serving pass loop.
"""

import re
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.aqp import EngineConfig, FastFrame, build_scramble, engine
from repro.aqp import flights_queries as fq
from repro.aqp.distributed import build_block_shards
from repro.data import flights
from repro.kernels import fused_scan as kfused
from repro.serve import FrameServer


@pytest.fixture(scope="module", autouse=True)
def _x64(x64_module):
    yield


@pytest.fixture(scope="module")
def scramble():
    ds = flights.generate(n_rows=48 * 1024, seed=3)
    return build_scramble(ds.columns, catalog=ds.catalog, block_rows=1024,
                          seed=5)


def _frame(scramble, **cfg):
    return FastFrame(scramble, EngineConfig(round_blocks=4, **cfg))


def _aqp_spans(log_dir):
    """``[(name, start_ns, end_ns)]`` of every ``aqp:`` and ``test:``
    event of the profile under ``log_dir``, by start."""
    from jax.profiler import ProfileData

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("aqp:", "test:")):
                    out.append((e.name, e.start_ns, e.end_ns))
    return sorted(out, key=lambda x: x[1])


@pytest.mark.parametrize("mode, children", [
    ("device_loop", ("aqp:views", "aqp:upload", "aqp:loop", "aqp:writeback",
                     "aqp:recovery", "aqp:result")),
    ("host_loop", ("aqp:views", "aqp:recovery", "aqp:result")),
    ("exact", ("aqp:views", "aqp:result")),
])
def test_run_records_its_host_spans(scramble, tmp_path, mode, children):
    frame = _frame(scramble, device_loop=mode == "device_loop")
    q = fq.f_q9()
    sampling = "exact" if mode == "exact" else "active_peek"
    frame.run(q, sampling=sampling, start_block=0)   # compile untraced
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test:caller"):
            frame.run(q, sampling=sampling, start_block=7)
    spans = _aqp_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert names == ["test:caller", "aqp:run", *children]
    (_, c0, c1), (_, r0, r1) = spans[:2]
    assert c0 <= r0 <= r1 <= c1
    prev = r0
    for _, s, e in spans[2:]:
        assert prev <= s <= e <= r1
        prev = e


def _spy(monkeypatch, build_fn: str, dialect: str = "stablehlo",
         compiled: bool = False) -> list:
    """Replace ``kfused.<build_fn>`` with one whose loops record their
    lowered text (with the name stacks) at their first call, and with
    ``compiled`` their optimized HLO after it."""
    texts = []
    build = getattr(kfused, build_fn)

    def spied(**kw):
        fn = build(**kw)

        def call(*args):
            if not texts:
                low = fn.lower(*args)
                texts.append(low.as_text(dialect=dialect, debug_info=True))
                if compiled:
                    texts.append(low.compile().as_text())
            return fn(*args)
        return call

    monkeypatch.setattr(kfused, build_fn, spied)
    return texts


def _scopes_in(text: str) -> set:
    return {s for s in kfused.SCOPES if re.search(f"/{s}/", text)}


def test_query_loop_carries_every_scope(scramble, monkeypatch):
    texts = _spy(monkeypatch, "build_query_loop")
    _frame(scramble, device_loop=True).run(fq.f_q9(), start_block=0)
    assert _scopes_in(texts[0]) == set(kfused.SCOPES)


def _sharded_lowered(scramble, q, merge_every: int):
    """The sharded round loop of ``q`` over a one-device mesh, lowered."""
    frame = _frame(scramble, device_loop=True)
    slot = engine._ScanViews(frame, q)
    qci = engine._QueryIntervals(frame, q, slot)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    shards = build_block_shards(scramble.n_blocks, mesh, 1024,
                                merge_every=merge_every)
    loop = engine._DeviceLoop(frame, q, slot, qci, probe=True,
                              lookahead=1024, max_rounds=100_000,
                              shards=shards)
    order = np.arange(scramble.n_blocks)
    loop.set_order(0, np.cumsum(frame._valid_counts[order]))
    return loop._chunk_fn.lower(loop.bufs, loop.init_carry(slot, qci))


def test_cadence_loop_carries_every_scope(scramble):
    """The collective-cadence body (``merge_every`` > 1) over a
    one-device mesh: the same scopes, ``merge`` around the pending-slot
    collectives."""
    text = _sharded_lowered(scramble, fq.f_q9(), 2).as_text(debug_info=True)
    assert _scopes_in(text) == set(kfused.SCOPES)


_COLLECTIVE = re.compile(
    r"= .*?\b(?:all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-start|-done)?\(.*")


@pytest.mark.parametrize("merge_every", [1, 2])
@pytest.mark.parametrize("bounder", ["bernstein", "anderson_dkw"])
def test_sharded_collectives_are_in_the_merge_scope(scramble, merge_every,
                                                    bounder):
    """Every collective of the sharded round loop, in the optimized HLO
    (the all-reduces stay there over a one-device mesh), carries the
    ``merge`` scope, so a trace's collective time is the merge's: at
    ``merge_every`` 1 one ``psum``, ``pmin`` and ``pmax`` a round, the
    histogram's ``psum`` combined with the sums'."""
    q = fq.f_q9(bounder=bounder, rangetrim=bounder == "bernstein")
    text = _sharded_lowered(scramble, q, merge_every).compile().as_text()
    found = _COLLECTIVE.findall(text)
    assert found
    if merge_every == 1:
        assert len(found) == 3
    for line in found:
        assert re.search(r'op_name="[^"]*/merge/', line), line


def test_pass_loop_carries_every_scope(scramble, monkeypatch):
    texts = _spy(monkeypatch, "build_pass_loop")
    server = FrameServer(_frame(scramble, device_loop=True))
    server.run_batch([fq.f_q9(), fq.f_q2(8.0)], start_block=0)
    assert _scopes_in(texts[0]) == set(kfused.SCOPES)


_SCATTER_OP_NAME = re.compile(r' scatter\(.*op_name="([^"]*)"')


@pytest.mark.parametrize("build_fn", ["build_query_loop", "build_pass_loop"])
def test_gather_scope_holds_no_scatter(scramble, monkeypatch, build_fn):
    """The round's selected blocks are compacted without a scatter: the
    TPU runs a scatter's colliding updates one by one, and
    ``jnp.nonzero``'s scatter-add over the window cost more per round
    than the fold. No HLO scatter of either round loop carries the
    ``gather`` scope."""
    texts = _spy(monkeypatch, build_fn, dialect="hlo")
    frame = _frame(scramble, device_loop=True)
    if build_fn == "build_query_loop":
        frame.run(fq.f_q9(), start_block=0)
    else:
        FrameServer(frame).run_batch([fq.f_q9(), fq.f_q2(8.0)],
                                     start_block=0)
    assert "/gather/" in texts[0]
    in_gather = [n for n in _SCATTER_OP_NAME.findall(texts[0])
                 if "/gather/" in n]
    assert in_gather == []


_GATHER_OP_NAME = re.compile(r' gather\(.*op_name="([^"]*)"')


@pytest.mark.parametrize("loop", ["build_query_loop", "build_pass_loop",
                                  "cadence"])
def test_select_and_account_read_the_window_as_slices(scramble, monkeypatch,
                                                      loop):
    """Every scan order is a rotation, so each window read of a block-id
    buffer is one ``dynamic_slice`` and the ``processed`` update one
    ``dynamic_update_slice``: no HLO gather of the query loop, its
    collective-cadence body or the pass loop carries the ``select`` or
    ``account`` scope, and no scatter carries ``account``. The TPU runs a
    window-long gather at tens of GB/s and a scatter one update at a
    time."""
    if loop == "cadence":
        low = _sharded_lowered(scramble, fq.f_q9(), 2)
        texts = [low.as_text(dialect="hlo", debug_info=True),
                 low.compile().as_text()]
    else:
        texts = _spy(monkeypatch, loop, dialect="hlo", compiled=True)
        frame = _frame(scramble, device_loop=True)
        if loop == "build_query_loop":
            frame.run(fq.f_q9(), start_block=0)
        else:
            FrameServer(frame).run_batch([fq.f_q9(), fq.f_q2(8.0)],
                                         start_block=0)
    for text in texts:   # as lowered, and as compiled
        assert "/select/" in text and "/account/" in text
        gathers = _GATHER_OP_NAME.findall(text)
        assert any("/gather/" in n for n in gathers)
        assert [n for n in gathers
                if re.search("/(select|account)/", n)] == []
        assert [n for n in _SCATTER_OP_NAME.findall(text)
                if "/account/" in n] == []
