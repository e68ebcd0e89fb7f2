"""Merge collective: device microseconds of the cross-chip collectives
per round over the traced answers.

A collective is an ``XLA Ops`` event whose opcode (``tracing.op_label``)
is one of ``COLLECTIVES``, or its asynchronous ``-start`` or ``-done``
half. Their time inside the traced span is averaged over the chips and
divided by the rounds the traced answers report. A trace with no
collective (one chip, or a program that merges nothing) gives nothing to
read."""

import numpy as np

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OPCODES = frozenset(c + half for c in COLLECTIVES
                    for half in ("", "-start", "-done"))


def opcode(label: str) -> str:
    """The opcode of an operation label, ``'<instruction> <opcode>'``."""
    return label.partition(" ")[2].partition(" ")[0]


def collective_ops(run):
    """Per chip, ``(starts, ends, labels)`` of the collective operations
    that start inside the traced span, or ``None`` where there are none
    or no traced rounds."""
    if run.window is None:
        return None
    rounds = sum(a.rounds for a in run.traced)
    trace, win = run.window.trace, run.window
    hit = np.array([opcode(lab) in OPCODES for lab in trace.labels], bool)
    per_chip = []
    for s, e, lab in trace.ops:
        keep = hit[lab] & (s >= win.a) & (s <= win.b)
        per_chip.append((s[keep], e[keep], lab[keep]))
    if not rounds or not any(s.size for s, _, _ in per_chip):
        return None
    return per_chip


def read(run):
    per_chip = collective_ops(run)
    if per_chip is None:
        return None
    b = run.window.b
    ns = np.mean([float(np.clip(np.minimum(e, b) - s, 0, None).sum())
                  for s, e, _ in per_chip])
    return ns * 1e-3 / sum(a.rounds for a in run.traced)
