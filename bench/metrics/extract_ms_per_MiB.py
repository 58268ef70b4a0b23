"""Milliseconds of the store's extract stage per MiB committed, from the
``IngestReport.extract_seconds`` of the window's commits (host clock
around a call that ends on the host)."""


def read(run):
    if not run.commits:
        return None
    mib = sum(r.bytes_in for r, _ in run.commits) / 2**20
    return 1000.0 * sum(r.extract_seconds for r, _ in run.commits) / mib
