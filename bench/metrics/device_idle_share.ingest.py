"""Percent of the traced ingest window in which no operation ran on the
chip: 100 * (1 - busy union / window), from the profiler's trace."""
from bench import trace


def read(run):
    if not run.commits or not run.events:
        return None
    busy, window = trace.busy_seconds(run.events)
    return 100.0 * (1.0 - busy / window)
