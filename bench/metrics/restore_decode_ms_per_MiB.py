"""Milliseconds of delta-chain decoding per MiB served in the window,
from the restores' ``RestoreReport.decode_seconds``."""


def read(run):
    mib = run.restore.get("bytes_out", 0) / 2**20
    return 1000.0 * run.restore["decode_s"] / mib if mib else None
