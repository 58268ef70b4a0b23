"""Record the small profiler trace that the trace reduction's tests read.

    python -m bench.record_trace [--out bench/fixtures/ingest_trace.json.gz]

Runs the ``sql_backup.ingest`` cell on the chip with its versions cut to
16 MiB and a one-second window, traced, and keeps the reduced event list
(``bench.trace``'s format) beside a summary on stderr of the planes,
lines and most frequent event names the trace held.
"""
from __future__ import annotations

import argparse
import collections
import os
import sys
import time

from bench import run as bench_run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        bench_run.BENCH, "fixtures", "ingest_trace.json.gz"))
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
        print("bench.record_trace: needs a TPU", file=sys.stderr)
        return 2
    from bench import trace
    result, run = bench_run.execute(cell["name"], args.seed, 1.0, True,
                                    spec=spec, config=config,
                                    traffic=traffic, started=started)
    trace.save(run.events, args.out)
    lines = collections.Counter((e[0], e[1]) for e in run.events)
    for (plane, line), n in sorted(lines.items()):
        names = collections.Counter(e[2] for e in run.events
                                    if e[0] == plane and e[1] == line)
        print(f"{plane} | {line} | {n} events | "
              f"{names.most_common(12)}", file=sys.stderr)
    print(f"{len(run.events)} events, {os.path.getsize(args.out)} bytes "
          f"in {args.out}; result {result}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
