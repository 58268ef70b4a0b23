"""Percent of the decode cache's lookups in the window that hit:
100 * hits / (hits + misses), from the restores' reports."""


def read(run):
    hits, misses = run.restore.get("hits", 0), run.restore.get("misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
