"""Materialization: the device's peak bytes in use after the window, in GB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
