"""Seconds from the process's start to the window's: imports, JAX start,
data made from the seed, the store built and fitted, its history
committed, and every program the window runs compiled or loaded."""


def read(run):
    return run.setup_s
