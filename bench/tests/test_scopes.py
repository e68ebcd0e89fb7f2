"""The program's own spans and named scopes read from a profiler trace
(``bench/scopes.py``): on synthetic traces, on a CPU profile, and on two
small traces recorded on a TPU v5e. ``trace.xplane.pb.gz`` comes from a
program without spans or scopes (a 2M-row table, F-q2 and F-q9);
``trace_scopes.xplane.pb.gz`` from the program with them, recorded with
HLO protos by ``bench/scope_report.py`` (a 1M-row table, F-q9 and
F-q9/dkw, 16 rounds and 977 blocks each)."""

import gzip
import importlib
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import scopes, tracing
from bench.tests.conftest import ROOT

DATA = Path(__file__).parent / "data"
PLAIN_SPANS = ["bench:answer 0 F-q2", "bench:answer 1 F-q9"]
SCOPED_SPANS = ["bench:answer 0 F-q9", "bench:answer 1 F-q9/dkw"]
RUN = ["aqp:run", "aqp:views", "aqp:upload", "aqp:loop", "aqp:writeback",
       "aqp:recovery", "aqp:result"]


def _answer(span, rounds):
    from bench.harness import Answer

    return Answer(template="F-q8", spec={}, start=0, cycle=1, span=span,
                  t_asked=0.0, t_done=1.0, blocks_fetched=1, rounds=rounds,
                  stopped_early=False, bytes_needed=1)


def _window(tmp_path_factory, name, spans, load):
    path = tmp_path_factory.mktemp("trace") / "trace.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    trace = load(path)
    kind = scopes.Window if load is scopes.load else tracing.Window
    return kind(trace, trace.span(spans[0])[0], trace.span(spans[-1])[1])


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    """The trace without spans or scopes, read as ``bench/tracing.py``
    reads it and as this module does."""
    return {load: _window(tmp_path_factory, "trace.xplane.pb.gz",
                          PLAIN_SPANS, load)
            for load in (tracing.load, scopes.load)}


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """The window over the scoped trace's two answers, and the traced
    answers with the rounds the recording reported."""
    win = _window(tmp_path_factory, "trace_scopes.xplane.pb.gz",
                  SCOPED_SPANS, scopes.load)
    return win, [_answer(n, 16) for n in SCOPED_SPANS]


# -- a trace read with the program's spans and scopes reads as before ---------

def _plain_run(window):
    """A run record over the plain trace's two traced answers, with
    round and block counts fixed for the test."""
    from bench.harness import RunRecord

    traced = [_answer(PLAIN_SPANS[0], 4), _answer(PLAIN_SPANS[1], 31)]
    for a, blocks in zip(traced, (256, 1954)):
        a.blocks_fetched, a.bytes_needed = blocks, blocks * 1024 * 8
    return RunRecord(answers=traced, traced=traced, window=window,
                     compiles_in_window=0, peak_bytes=123456789,
                     hbm_bytes_per_s=819e9)


# what the benchmark's readers give on the plain trace
BEFORE = {"host_ms_per_answer": 21.5429445, "blocks_per_answer": 1105.0,
          "device_us_per_round": 495.0651142857143,
          "round_hbm_share": 0.12757569624980833, "compiles_in_window": 0,
          "hbm_peak_gb": 0.123456789,
          "device_idle_share": 71.37261641465133}


@pytest.mark.parametrize("metric", sorted(BEFORE))
def test_benchmark_readers_read_the_same_over_a_scoped_window(plain,
                                                              metric):
    read = importlib.import_module(f"bench.metrics.{metric}").read
    got = [read(_plain_run(win)) for win in plain.values()]
    assert got[0] == got[1] == pytest.approx(BEFORE[metric], rel=1e-12)


def test_a_program_without_spans_or_scopes_gives_nothing(plain):
    win = plain[scopes.load]
    assert win.trace.spans == plain[tracing.load].trace.spans
    assert win.trace.aqp_spans == []
    assert win.trace.op_scopes
    assert all((sc < 0).all() for sc in win.trace.op_scopes)
    assert win.scope_s() == {}
    assert {sc for _, sc, _ in win.scoped_ops()} == {None}
    assert win.idle_gaps() == plain[tracing.load].idle_gaps()
    traced = _plain_run(win).traced
    assert scopes.numbers(win, traced) == {}


# -- program spans and scopes, on synthetic traces ---------------------------

def test_idle_gaps_take_the_innermost_span_of_either_kind():
    s = 1e6
    spans = [("bench:answer 0 F-q8", 0.0, 100 * s)]
    aqp = [("aqp:run", 1 * s, 99 * s), ("aqp:views", 2 * s, 30 * s),
           ("aqp:loop", 31 * s, 85 * s)]
    # device busy 0-2, 30-80 and 95-100 ms: idle 2-30 ms, inside
    # aqp:views, and 80-95 ms, whose middle lies after aqp:loop
    starts, ends = np.array([0, 30, 95]) * s, np.array([2, 80, 100]) * s
    trace = scopes.Trace(ops=[(starts, ends, np.zeros(3, np.int64))],
                         labels=["fusion.1 fusion"], spans=spans,
                         aqp_spans=aqp)
    gaps = scopes.Window(trace, 0.0, 100 * s).idle_gaps()
    assert gaps == [["aqp:views", pytest.approx(0.028)],
                    ["aqp:run", pytest.approx(0.015)]]
    # without the program's spans the benchmark's label stays
    bare = scopes.Trace(ops=trace.ops, labels=trace.labels, spans=spans)
    assert [g[0] for g in scopes.Window(bare, 0.0, 100 * s).idle_gaps()
            ] == ["bench:answer 0 F-q8"] * 2


@pytest.mark.parametrize("op_name, scope", [
    ("jit(chunk_body)/while/body/select/jit(active_blocks)/pallas_call",
     "select"),
    ("jit(chunk_body)/while/body/account/gather", "account"),
    ("jit(chunk_body)/while/body/gather/gather", "gather"),
    ("jit(chunk_body)/while/body/gather", None),
    ("jit(chunk_body)/while/body/fold/merge/psum", "merge"),
    ("jit(chunk_body)/while/body/refresh/jit(_where)/select_n", "refresh"),
    ("gather", None),
])
def test_scope_of_reads_the_name_stack(op_name, scope):
    want = -1 if scope is None else scopes.SCOPES.index(scope)
    assert scopes.scope_of(op_name) == want


def test_scope_names_are_the_programs():
    from repro.kernels import fused_scan

    assert scopes.SCOPES == fused_scan.SCOPES


def test_operations_map_to_scopes_through_their_program():
    """The same instruction name in two programs takes each program's
    op_name; an operation outside every ``XLA Modules`` event, or whose
    instruction the HLO does not name, has no scope."""
    labels = ["fusion.7 fusion", "block_agg.2 custom-call", "while.1 while"]
    starts = np.array([10.0, 20.0, 30.0, 110.0, 120.0, 500.0])
    lab = np.array([0, 1, 2, 0, 1, 0])
    modules = ([0.0, 100.0], [90.0, 190.0], ["jit_a(1)", "jit_b(2)"])
    op_names = {
        "jit_a(1)": {"fusion.7": "jit(a)/while/body/account/and",
                     "block_agg.2": "jit(a)/fold/jit(block_agg)/pallas_call",
                     "while.1": "jit(a)/while"},
        "jit_b(2)": {"fusion.7": "jit(b)/while/body/select/cumsum"}}
    got = scopes.scope_ids((starts, starts + 5, lab), modules, labels,
                           op_names)
    idx = scopes.SCOPES.index
    assert got.tolist() == [idx("account"), idx("fold"), -1, idx("select"),
                            -1, -1]


def test_scope_totals_leave_out_loop_containers():
    labels = ["fusion.7 fusion", "block_agg.2 custom-call", "while.1 while",
              "copy.3 copy"]
    starts = np.array([0.0, 10.0, 0.0, 30.0])
    ends = np.array([10.0, 25.0, 40.0, 40.0])
    lab = np.array([0, 1, 2, 3])
    sc = np.array([scopes.SCOPES.index("account"),
                   scopes.SCOPES.index("fold"),
                   scopes.SCOPES.index("select"), -1])
    trace = scopes.Trace(ops=[(starts * 1e6, ends * 1e6, lab)],
                         labels=labels, spans=[], op_scopes=[sc])
    win = scopes.Window(trace, 0.0, 40e6)
    got = win.scope_s()
    assert got["account"] == pytest.approx(0.010)
    assert got["fold"] == pytest.approx(0.015)
    assert got["select"] == 0.0 and sum(got.values()) < win.busy_s()
    assert win.scoped_ops() == [["block_agg.2 custom-call", "fold",
                                 pytest.approx(0.015)],
                                ["fusion.7 fusion", "account",
                                 pytest.approx(0.010)],
                                ["copy.3 copy", None, pytest.approx(0.010)]]


def test_program_span_numbers_average_over_traced_answers():
    s = 1e6
    spans = [("bench:answer 0 F-q2", 0.0, 100 * s),
             ("bench:answer 1 F-q8", 100 * s, 300 * s)]
    aqp = [("aqp:views", 1 * s, 11 * s), ("aqp:upload", 11 * s, 12 * s),
           ("aqp:views", 101 * s, 131 * s), ("aqp:upload", 131 * s,
                                             134 * s)]
    trace = scopes.Trace(ops=[(np.array([0.0]), np.array([1.0]),
                               np.zeros(1, np.int64))],
                         labels=["fusion.1 fusion"], spans=spans,
                         aqp_spans=aqp)
    traced = [_answer(n, 10) for n, _, _ in spans]
    win = scopes.Window(trace, 0.0, 300 * s)
    # prepare: (10 + 1 + 30 + 3) ms over two answers; nothing else
    assert scopes.numbers(win, traced) == {
        "prepare_ms_per_answer": pytest.approx(22.0)}
    assert scopes.ms_per_answer(win, traced, ("aqp:views",)
                                ) == pytest.approx(20.0)


def test_hlo_op_names_read_from_a_cpu_profile(tmp_path):
    """With ``scopes.options()`` the profile's metadata plane holds each
    program's HLO; its instructions carry the scopes of the code."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("select"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("fold"):
            return (y @ y.T).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=scopes.options()):
        f(x).block_until_ready()
    names = scopes.hlo_op_names(tracing.find_xplane(tmp_path))
    mine = {k: v for k, v in names.items() if k.startswith("jit_f(")}
    assert len(mine) == 1
    found = {scopes.scope_of(op) for op in next(iter(mine.values())).values()}
    assert {scopes.SCOPES.index("select"),
            scopes.SCOPES.index("fold")} <= found


# -- the program's spans and scopes, end to end on a chip trace ---------------

def test_program_spans_land_in_their_own_list(scoped):
    """Each answer holds one ``FastFrame.run``: ``aqp:run`` and its six
    phases in order, inside the answer's benchmark span."""
    trace = scoped[0].trace
    assert [n for n, _, _ in trace.spans] == SCOPED_SPANS
    assert all(n.startswith("aqp:") for n, _, _ in trace.aqp_spans)
    for name in SCOPED_SPANS:
        s, e = trace.span(name)
        inside = sorted((a, n, b) for n, a, b in trace.aqp_spans
                        if s <= a <= e)
        assert [n for _, n, _ in inside] == RUN
        assert all(s <= a <= b <= e for a, _, b in inside)


def test_scopes_hold_most_of_the_device_time(scoped):
    win = scoped[0]
    seconds = win.scope_s()
    assert set(seconds) == set(scopes.SCOPES)
    assert all(v > 0 for v in seconds.values())
    assert sum(seconds.values()) >= 0.9 * win.busy_s()


def test_kernels_fall_in_their_scopes(scoped):
    scope = {}
    for label, sc, _ in scoped[0].scoped_ops(top=10_000):
        scope.setdefault(label.partition(".")[0], set()).add(sc)
    assert scope["fused_fold"] == scope["block_agg"] == {"fold"}
    assert scope["active_blocks"] == {"select"}


def test_idle_gaps_are_named_by_the_program_phase(scoped):
    gaps = scoped[0].idle_gaps()
    assert all(g[0].startswith("aqp:") for g in gaps)
    assert gaps[0][0] in ("aqp:upload", "aqp:writeback")


def test_numbers_on_the_scoped_trace(scoped):
    from bench.harness import RunRecord
    from bench.metrics import device_us_per_round, host_ms_per_answer

    win, traced = scoped
    got = scopes.numbers(win, traced)
    assert set(got) == set(scopes.METRICS)
    assert all(v > 0 for v in got.values())
    run = RunRecord(answers=traced, traced=traced, window=win,
                    compiles_in_window=0, peak_bytes=None,
                    hbm_bytes_per_s=819e9)
    # the round's device time splits into the scopes and the rest
    round_us = device_us_per_round.read(run)
    scoped_us = sum(v for k, v in got.items() if k.endswith("_per_round"))
    assert 0.9 * round_us <= scoped_us <= round_us
    # the host's time: the phases before and after the loop, the loop's
    # own idle time and the time outside FastFrame.run
    trace = win.trace
    rest = 0.0
    for name in SCOPED_SPANS:
        s, e = trace.span(name)
        ls, le = next((a, b) for n, a, b in trace.aqp_spans
                      if n == "aqp:loop" and s <= a <= e)
        rest += (trace.aqp_s("aqp:loop", s, e) - win.busy_s(ls, le)
                 + (e - s) * 1e-9 - trace.aqp_s("aqp:run", s, e))
    parts = (got["prepare_ms_per_answer"] + got["finish_ms_per_answer"]
             + 1e3 * rest / len(SCOPED_SPANS))
    assert parts == pytest.approx(host_ms_per_answer.read(run), rel=0.05)


def test_report_breaks_each_answer_down(scoped):
    win, traced = scoped
    lines = scopes.report(win, traced)
    assert len(lines) == len(traced) + 3
    for line, a in zip(lines, traced):
        assert repr(a.span) in line
        assert all(f" {n[4:]}_ms=" in line for n in RUN)
    assert "unscoped_share=0." in lines[len(traced)]


def test_scope_report_without_a_tpu_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/scope_report.py", "--workload",
         "flights-151m.suite-solo", "--seed", str(2**31 + 9)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr
