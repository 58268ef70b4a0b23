"""Milliseconds of base lookups in the delta pass per MiB committed, from
the ``IngestReport.base_read_seconds`` of the window's commits: the
sums the program accumulates inside its ``ingest.delta`` span around
each base it fetches (this commit's own chunk, or a read of the store
that walks a delta chain through the decode cache), outside the encodes
of ``delta_ms_per_MiB``. The note gives the lookups and the share taken
from the commit's own chunks. Silent for a program that does not time
them."""


def read(run):
    if not run.commits or not hasattr(run.commits[0][0],
                                      "base_read_seconds"):
        return None
    reads = sum(r.base_reads for r, _ in run.commits)
    own = sum(r.base_read_hits for r, _ in run.commits)
    run.notes.append(f"base_read_ms_per_MiB: {reads} base lookups, "
                     f"{100.0 * own / max(1, reads):.2f}% from the "
                     f"commit's own chunks")
    mib = sum(r.bytes_in for r, _ in run.commits) / 2**20
    return 1000.0 * sum(r.base_read_seconds for r, _ in run.commits) / mib
