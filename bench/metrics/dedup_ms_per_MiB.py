"""Milliseconds of the store's exact dedup (pass 1: digest lookups and id
assignment) per MiB committed, from the ``IngestReport.dedup_seconds`` of
the window's commits: the program's ``ingest.dedup`` span. Silent for a
program that does not time the pass."""


def read(run):
    if not run.commits or not hasattr(run.commits[0][0], "dedup_seconds"):
        return None
    mib = sum(r.bytes_in for r, _ in run.commits) / 2**20
    return 1000.0 * sum(r.dedup_seconds for r, _ in run.commits) / mib
