"""MiB served over the window's wall seconds, up to the last answer."""


def read(run):
    if not run.requests or run.window_s <= 0:
        return None
    return sum(n for _, n in run.requests) / 2**20 / run.window_s
