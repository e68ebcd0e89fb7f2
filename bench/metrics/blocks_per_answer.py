"""Bounders and selection: blocks fetched per answer (mean of
``QueryResult.blocks_fetched`` over the window's answers)."""


def read(run):
    if not run.answers:
        return None
    return sum(a.blocks_fetched for a in run.answers) / len(run.answers)
