"""Record the profiler trace that the program-span readers' tests read.

    python -m bench.record_spans [--out bench/fixtures/ingest_trace_spans.json.gz]

Runs the ``sql_backup.ingest`` cell on the chip with its versions cut to
16 MiB and a one-second window, traced, as ``bench.record_trace`` does,
and keeps the reduced event list with the window commits' program spans
(``IngestReport.spans``) appended as events of the plane ``PLANE``:
``(PLANE, "spans", "repro.<op>", start_ns, duration_ns)``, their starts
on the program's realtime clock, not the trace's.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from bench import run as bench_run

PLANE = "/host:program"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        bench_run.BENCH, "fixtures", "ingest_trace_spans.json.gz"))
    ap.add_argument("--seed", type=int, default=20261018)
    args = ap.parse_args()
    started = time.perf_counter()
    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = bench_run.cell_spec(spec, "sql_backup.ingest")
    config = bench_run.load_json(bench_run.BENCH, "configs",
                                 cell["config"] + ".json")
    traffic = bench_run.load_json(bench_run.BENCH, "traffic",
                                  cell["traffic"] + ".json")
    config["generator_args"]["size"] = 16 << 20
    sys.path[:0] = [os.path.join(bench_run.ROOT, "src")]
    import jax
    from repro import api
    api.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench.record_spans: needs a TPU", file=sys.stderr)
        return 2
    from bench import trace
    result, run = bench_run.execute(cell["name"], args.seed, 1.0, True,
                                    spec=spec, config=config,
                                    traffic=traffic, started=started)
    spans = [(PLANE, "spans", "repro." + op, t0, round(s * 1e9))
             for r, _ in run.commits for op, t0, s in r.spans]
    trace.save(run.events + spans, args.out)
    for line in run.notes:
        print(line, file=sys.stderr)
    print(f"{len(run.events)} events and {len(spans)} program spans, "
          f"{os.path.getsize(args.out)} bytes in {args.out}; metrics "
          f"{result['metrics']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
