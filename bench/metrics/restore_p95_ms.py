"""95th percentile of the latency of every request the window answered,
in milliseconds. A request that failed is counted in ``failed`` and makes
the run not correct."""
import numpy as np


def read(run):
    if not run.requests:
        return None
    lat = [s for s, _ in run.requests]
    run.notes.append(f"restore_p95_ms over {len(lat)} requests")
    return float(np.percentile(lat, 95)) * 1000.0
