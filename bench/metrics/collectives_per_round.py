"""Merge collective: cross-chip collectives per round over the traced
answers, counted per chip (averaged over the chips) and divided by the
rounds the traced answers report.

The events are those ``merge_us_per_round`` times; an asynchronous
collective's ``-done`` half ends what its ``-start`` began and is not
counted again. Guards the merge's structure: the round loop merges its
fold with one ``psum``, ``pmin`` and ``pmax`` a round."""

import numpy as np

from bench.metrics.merge_us_per_round import collective_ops, opcode


def read(run):
    per_chip = collective_ops(run)
    if per_chip is None:
        return None
    done = np.array([opcode(lab).endswith("-done")
                     for lab in run.window.trace.labels], bool)
    count = np.mean([float((~done[lab]).sum()) for _, _, lab in per_chip])
    return count / sum(a.rounds for a in run.traced)
