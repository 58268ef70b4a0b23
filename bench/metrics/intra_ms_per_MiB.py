"""Milliseconds of the detector's intra-stream score per MiB committed,
from the ``IngestReport.intra_seconds`` of the window's commits: the
program's ``ingest.intra`` span around CARD's host [n, n] score of each
new chunk against the stream's earlier chunks (the product, its mask and
argmax), a part of ``score_ms_per_MiB`` beside ``search_ms_per_MiB``.
The note gives how many chosen bases came from the index and how many
from the stream's earlier chunks. Silent for a program that does not
time it."""


def read(run):
    if not run.commits or not hasattr(run.commits[0][0], "intra_seconds"):
        return None
    index = sum(r.index_bases for r, _ in run.commits)
    stream = sum(r.stream_bases for r, _ in run.commits)
    run.notes.append(f"intra_ms_per_MiB: {index} bases from the index, "
                     f"{stream} from the stream's earlier chunks")
    mib = sum(r.bytes_in for r, _ in run.commits) / 2**20
    return 1000.0 * sum(r.intra_seconds for r, _ in run.commits) / mib
