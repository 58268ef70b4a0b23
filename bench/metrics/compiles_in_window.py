"""Programs JAX traced inside the window's commits (each a jit cache
miss of this process): today one ``sim_topk`` program a commit, since
the index it searches grows with every commit."""


def read(run):
    return run.compiles_in_window if run.commits else None
