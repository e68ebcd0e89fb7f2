"""The merge collective's readers, ``merge_us_per_round`` and
``collectives_per_round``, on a synthetic trace of four chips."""

import types

import numpy as np
import pytest

from bench import harness, tracing
from bench.metrics import collectives_per_round, merge_us_per_round

LABELS = ["all-reduce.1 all-reduce", "all-reduce-start.2 all-reduce-start",
          "all-reduce-done.2 all-reduce-done", "all-gather.3 all-gather",
          "reduce-scatter.4 reduce-scatter", "fusion.5 fusion",
          "block_agg.16 custom-call", "while.7 while",
          "collective-permute-done.8 collective-permute-done"]
COLLECTIVE = [True, True, True, True, True, False, False, False, True]
A, B = 1_000.0, 2_000.0      # the traced span, ns


def _ops(events):
    """``(starts, ends, label ids)`` of ``[(label id, start, end)]``."""
    lab, s, e = zip(*events)
    return (np.array(s, np.float64), np.array(e, np.float64),
            np.array(lab, np.int64))


def _run(ops, rounds=(3, 7)):
    trace = tracing.Trace(ops=ops, labels=list(LABELS),
                          spans=[("bench:answer 0 F-q2", A, 1_400.0),
                                 ("bench:answer 1 F-q9", 1_500.0, B)])
    traced = [types.SimpleNamespace(rounds=r) for r in rounds]
    return harness.RunRecord(answers=traced, traced=traced,
                             window=tracing.Window(trace, A, B),
                             compiles_in_window=0, peak_bytes=None,
                             hbm_bytes_per_s=819e9)


def test_labels_that_count():
    got = [merge_us_per_round.opcode(lab) in merge_us_per_round.OPCODES
           for lab in LABELS + ["block_agg.16 custom-call tpu_custom_call"]]
    assert got == COLLECTIVE + [False]


def _chip(k):
    """Chip ``k``: a plain all-reduce of 10 + k ns, a start/done pair of
    5 and 20 ns, an all-gather of 8 ns, a reduce-scatter of 4 ns; a
    fusion, a kernel and the loop that holds them (not collectives); one
    all-reduce before the span and one that runs past its end."""
    return _ops([(0, 1_100, 1_110 + k), (1, 1_200, 1_205),
                 (2, 1_300, 1_320), (3, 1_600, 1_608), (4, 1_700, 1_704),
                 (5, 1_100, 1_900), (6, 1_150, 1_950), (7, 1_050, 1_990),
                 (0, 900, 950), (0, 1_995, 2_100)])


def test_time_is_averaged_over_the_chips():
    run = _run([_chip(k) for k in range(4)])
    # per chip: (10 + k) + 5 + 20 + 8 + 4 + 5 (clipped at the span's end)
    per_chip = [52.0 + k for k in range(4)]
    want = np.mean(per_chip) * 1e-3 / 10
    assert merge_us_per_round.read(run) == pytest.approx(want, rel=1e-12)


def test_count_leaves_the_done_half_out():
    run = _run([_chip(k) for k in range(4)])
    # all-reduce, start, all-gather, reduce-scatter, the one past the end
    assert collectives_per_round.read(run) == pytest.approx(5 / 10)


def test_chips_with_different_counts_are_averaged():
    ops = [_chip(0), _chip(1), _chip(2),
           _ops([(0, 1_100, 1_110), (6, 1_150, 1_950)])]
    run = _run(ops)
    assert collectives_per_round.read(run) == pytest.approx(
        (5 + 5 + 5 + 1) / 4 / 10)
    assert merge_us_per_round.read(run) == pytest.approx(
        (52 + 53 + 54 + 10) / 4 * 1e-3 / 10)


@pytest.mark.parametrize("reader", [merge_us_per_round,
                                    collectives_per_round])
def test_nothing_to_read(reader):
    no_collective = [_ops([(5, 1_100, 1_900), (6, 1_150, 1_950)])
                     for _ in range(4)]
    assert reader.read(_run(no_collective)) is None
    outside = [_ops([(0, 900, 950), (6, 1_150, 1_950)]) for _ in range(4)]
    assert reader.read(_run(outside)) is None
    assert reader.read(_run([_chip(0)] * 4, rounds=(0,))) is None
    run = _run([_chip(0)] * 4)
    run.window = None
    assert reader.read(run) is None
