"""Round loop: device-busy microseconds per OptStop round over the traced
answers (busy time / the rounds they report)."""


def read(run):
    if run.window is None:
        return None
    rounds = sum(a.rounds for a in run.traced)
    return run.window.busy_s() / rounds * 1e6 if rounds else None
