"""Milliseconds of the detector's index search per MiB committed, from
the ``IngestReport.search_seconds`` of the window's commits: the
program's ``ingest.search`` span around ``index.query`` (the index's copy
to the device, the ``sim_topk`` call and its compile, the answer's
fetch), a part of ``score_ms_per_MiB``. Silent for a program that does
not time it."""


def read(run):
    if not run.commits or not hasattr(run.commits[0][0], "search_seconds"):
        return None
    mib = sum(r.bytes_in for r, _ in run.commits) / 2**20
    return 1000.0 * sum(r.search_seconds for r, _ in run.commits) / mib
