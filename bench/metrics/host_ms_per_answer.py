"""Engine host path: milliseconds per answer in which the device was idle.

Each traced answer's benchmark span minus the device-busy time inside
it, averaged over the traced answers."""


def read(run):
    if run.window is None or not run.traced:
        return None
    host = []
    for a in run.traced:
        s, e = run.window.trace.span(a.span)
        host.append((e - s) * 1e-9 - run.window.busy_s(s, e))
    return 1e3 * sum(host) / len(host)
