"""MiB committed in the window over the wall seconds of those commits.
Making the next version is not timed; all the store's work is."""


def read(run):
    if not run.commits:
        return None
    return (sum(r.bytes_in for r, _ in run.commits) / 2**20
            / sum(s for _, s in run.commits))
