"""Bytes in over bytes stored, record overhead included, over the
window's commits: a faster detector that finds less redundancy shows."""


def read(run):
    if not run.commits:
        return None
    return (sum(r.bytes_in for r, _ in run.commits)
            / sum(r.bytes_stored for r, _ in run.commits))
