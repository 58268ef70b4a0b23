"""Milliseconds of container reads per MiB served in the window, from
the restores' ``RestoreReport.read_seconds`` (summed over the pooled
readers, so it is time per MiB and never a share of the wall)."""


def read(run):
    mib = run.restore.get("bytes_out", 0) / 2**20
    return 1000.0 * run.restore["read_s"] / mib if mib else None
