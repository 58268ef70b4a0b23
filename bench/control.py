"""The control of each cell's comparison: what the check reads when the
program's answers are replaced by a lower-precision or stale reference.

    python -m bench.control --workload <cell> --seconds <s> --seeds <n> ...

For each seed this runs the cell once as ``bench.run`` does (set-up,
window, comparison) and prints the numbers the comparison read for the
program beside what it reads for the control:

* ingest cells: the reference put in the program's place, computed in
  the precision below the one the configuration states: bfloat16 for the
  float32 initial features (``feature_gap``), and float8 (e4m3) operands
  for the cosine top-1 choice (``top1_gap``) and for each chunk's choice
  of a base (``base_gap``), which the configuration states at the chip's
  default matmul precision (bfloat16 operands, float32 sums). The mix
  file names the types (``control``);
* restore cells: every kept answer served from the other stored version
  at the same range, a stale read that breaks the configuration's
  guarantee (``answers_wrong``).

A limit holds only if every control reads above it. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from bench import reference
from bench import run as bench_run


def control_readings(run, checks=None) -> dict:
    """The comparison's numbers with the control in the program's place;
    of ``checks`` alone where given."""
    p = run.produced
    if "kept" in p:
        versions = p["versions"]
        stale = 0
        for i, off, n, got in p["kept"]:
            other = versions[(i + 1) % len(versions)]
            stale += other[off:off + n] != versions[i][off:off + n]
        return {"answers_wrong": stale}
    import ml_dtypes
    low = {k: getattr(ml_dtypes, v) for k, v in run.traffic["control"].items()
           if checks is None or k in checks}
    out = {}
    if "feature_gap" in low:
        feat = run.config["store"]["detector_args"]["feat"]
        out["feature_gap"] = 0.0
        for v, (b, want) in zip(p["versions"], p["reference"]):
            h = reference.gear_hashes(v)
            got = reference.initial_features(h, b[:-1], np.diff(b), **feat,
                                             dtype=low["feature_gap"])
            out["feature_gap"] = max(out["feature_gap"], float(
                np.abs(got.astype(np.float64) - want).max()))
    if "top1_gap" in low:
        out["top1_gap"] = 0.0
        for c in p["captured"].topk:
            if c.arg is None:
                continue
            rows = p["index_rows"][:c.rows]
            _, arg = reference.top1(c.queries, rows, dtype=low["top1_gap"])
            out["top1_gap"] = max(out["top1_gap"],
                                  reference.top1_gap(c.queries, rows, arg))
    if "base_gap" in low:
        out["base_gap"] = 0.0
        for c in p["captured"].choices:
            rows = p["index_rows"][:c.rows]
            ids = p["index_ids"][:c.rows]
            chosen, _ = reference.best_bases(
                c.feats, c.ids, c.is_new, rows, ids, p["threshold"],
                dtype=low["base_gap"])
            out["base_gap"] = max(out["base_gap"], reference.base_gap(
                c.feats, c.ids, c.is_new, rows, ids, chosen, p["threshold"]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--checks", nargs="+",
                    help="read the control of these checks only")
    args = ap.parse_args()
    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell = bench_run.cell_spec(spec, args.workload)
    config = bench_run.load_json(bench_run.BENCH, "configs",
                                 cell["config"] + ".json")
    traffic = bench_run.load_json(bench_run.BENCH, "traffic",
                                  cell["traffic"] + ".json")
    sys.path[:0] = [os.path.join(bench_run.ROOT, "src")]
    import jax
    from repro import api
    api.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("bench.control: needs a TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        result, run = bench_run.execute(
            args.workload, seed, args.seconds, False, spec=spec,
            config=config, traffic=traffic, started=time.perf_counter())
        line = {"seed": seed, "correct": result["correct"],
                "program": {k: c["value"]
                            for k, c in result["checks"].items()},
                "control": control_readings(run, args.checks),
                "metrics": {k: m["value"]
                            for k, m in result["metrics"].items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
