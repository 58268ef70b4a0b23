"""Milliseconds JAX spent tracing, lowering and compiling inside the
window's commits, per MiB committed: today one ``sim_topk`` program a
commit, traced at the index size that commit met."""


def read(run):
    if not run.commits:
        return None
    return (1e3 * run.compile_s_in_window
            / (sum(r.bytes_in for r, _ in run.commits) / 2**20))
