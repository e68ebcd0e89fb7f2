"""Compile: backend compiles JAX reports inside the measured window."""


def read(run):
    return run.compiles_in_window
