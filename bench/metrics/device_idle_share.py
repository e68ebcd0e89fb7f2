"""Device: the share of the traced span (from the first traced answer's
start to the last one's end) with no operation on the chip."""


def read(run):
    if run.window is None or run.window.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.window.busy_s() / run.window.window_s)
