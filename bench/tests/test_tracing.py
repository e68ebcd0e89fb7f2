"""The reduction from a profiler trace to device numbers, on a small
trace recorded on a TPU v5e by ``bench/record_trace.py``: the harness's
traced run of a 2M-row table, F-q2 and F-q9 traced in one cycle."""

import gzip
from pathlib import Path

import numpy as np
import pytest

from bench import tracing

DATA = Path(__file__).parent / "data" / "trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return tracing.load(path)


SPANS = ["bench:answer 0 F-q2", "bench:answer 1 F-q9"]


@pytest.fixture(scope="module")
def window(trace):
    """The traced span as the harness reads it: from the first traced
    answer's start to the last one's end."""
    return tracing.Window(trace, trace.span(SPANS[0])[0],
                          trace.span(SPANS[-1])[1])


def _plain_union(starts, ends, a, b):
    """Busy nanoseconds by a plain sweep over the sorted intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(zip(starts, ends)):
        s, e = max(s, a), min(e, b)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def test_one_device_and_the_benchmark_spans(trace):
    assert len(trace.ops) == 1
    assert [n for n, _, _ in trace.spans] == SPANS
    (s0, e0), (s1, e1) = (trace.span(n) for n in SPANS)
    assert s0 < e0 <= s1 < e1


def test_busy_is_the_union_of_operation_intervals(trace, window):
    s, e, _ = trace.ops[0]
    want = _plain_union(s, e, window.a, window.b) * 1e-9
    assert window.busy_s() == pytest.approx(want, rel=1e-12)
    assert 0 < window.busy_s() < window.window_s
    gaps = tracing.idle(window.merged[0], window.a, window.b)
    idle_s = float((gaps[:, 1] - gaps[:, 0]).sum()) * 1e-9
    assert idle_s + window.busy_s() == pytest.approx(window.window_s)


def test_busy_inside_an_answer(trace, window):
    parts = [window.busy_s(*trace.span(n)) for n in SPANS]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= window.busy_s() * (1 + 1e-12)


def test_idle_gaps_are_labelled_by_the_host_span(window):
    gaps = window.idle_gaps()
    assert len(gaps) == tracing.TOP
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    # the host's own work inside an answer leaves the longest gaps
    assert gaps[0][0] in SPANS and gaps[0][1] > 0.005
    assert {g[0] for g in gaps} <= {*SPANS, "no benchmark span"}


def test_operation_totals_leave_out_loop_containers(trace, window):
    ops = window.device_ops()
    assert len(ops) == tracing.TOP
    assert all(label.split(" ")[1] not in tracing.CONTAINERS
               for label, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    s, e, lab = trace.ops[0]
    body = np.array([trace.labels[i].split(" ")[1] not in tracing.CONTAINERS
                     for i in lab])
    # body operations do not overlap one another; the loops around them
    # add only the gaps between them
    assert ((e - s)[body]).sum() * 1e-9 <= window.busy_s()
    assert any(label.startswith("block_agg") for label, _ in ops)


@pytest.mark.parametrize("text, label", [
    ("%fusion.12 = (u32[64]{0}, u32[64]{0}) fusion(u32[64]{0} %b), "
     "kind=kCustom", "fusion.12 fusion"),
    ("%block_agg.14 = (f32[3,256]{1,0}, f32[256,1]) custom-call(f32[1,1] "
     "%g), custom_call_target=\"tpu_custom_call\"", "block_agg.14 custom-call"),
    ("%custom-call.1 = u32[]{:T(128)} custom-call(s64[] %a), "
     "custom_call_target=\"X64SplitLow\"",
     "custom-call.1 custom-call X64SplitLow"),
    ("%while.3 = (s32[], f32[]) while((s32[], f32[]) %t), condition=%c",
     "while.3 while"),
    ("jit_chunk_body(1234)", "jit_chunk_body(1234)"),
])
def test_op_label(text, label):
    assert tracing.op_label(text) == label


def _answer(span, rounds):
    from bench.harness import Answer

    return Answer(template="F-q8", spec={}, start=0, cycle=1, span=span,
                  t_asked=0.0, t_done=1.0, blocks_fetched=1, rounds=rounds,
                  stopped_early=False, bytes_needed=1)


def test_trace_gap_finds_dropped_device_work():
    from bench.harness import trace_gap

    # three answers of 1 s each; the device ran through the first, and only
    # the first 0.2 s of the second, as when the profiler's buffer fills
    s = 1e9
    spans = [("bench:answer 0 F-q2", 0.0, s), ("bench:answer 1 F-q8", s, 2 * s),
             ("bench:answer 2 F-q5", 2 * s, 3 * s)]
    starts = np.arange(0.0, 1.2 * s, 1e6)
    trace = tracing.Trace(ops=[(starts, starts + 1e6,
                                np.zeros(starts.size, np.int64))],
                          labels=["fusion.1 fusion"], spans=spans)
    win = tracing.Window(trace, 0.0, 3 * s)
    assert trace_gap(win, [_answer(spans[0][0], 5000)]) is None
    # a short answer may leave the device idle most of its span
    assert trace_gap(win, [_answer(spans[1][0], 10)]) is None
    assert "busy" in trace_gap(win, [_answer(spans[1][0], 5000)])
    assert "no device operation" in trace_gap(win, [_answer(spans[2][0], 10)])
    assert "not in the trace" in trace_gap(win, [_answer("bench:answer 9 x",
                                                         10)])
