"""Benchmark entry point: one section per paper table/figure + the
framework benches. Prints ``name,...`` CSV sections.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only dcr,time,...]
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workloads (CI-speed)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workloads, every section, fail on first "
                         "raise (perf-plumbing CI gate; implies --quick)")
    ap.add_argument("--only", default=None,
                    help="comma list: dcr,time,dims,kernels,ckpt,ablation,"
                         "roofline,gc,ingest,restore,serve,objstore,cache")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    quick = args.quick or args.smoke

    from repro import api
    api.enable_compile_cache()

    from benchmarks import (bench_ablation, bench_cache, bench_ckpt_store,
                            bench_dcr, bench_dims, bench_gc, bench_ingest,
                            bench_kernels, bench_objstore, bench_restore,
                            bench_roofline, bench_time, common)

    base = (1 << 20) if args.smoke else (2 << 20) if quick else (6 << 20)
    sizes = common.CHUNK_SIZES[:3] if quick else common.CHUNK_SIZES[:4]

    sections = {
        "dcr": lambda: bench_dcr.run(chunk_sizes=sizes, base_size=base),
        "time": lambda: bench_time.run(chunk_sizes=sizes, base_size=base),
        "dims": lambda: bench_dims.run(base_size=base),
        "kernels": bench_kernels.run,
        "ckpt": bench_ckpt_store.run,
        "ablation": lambda: bench_ablation.run(base_size=min(base, 4 << 20)),
        "roofline": bench_roofline.run,
        "gc": lambda: bench_gc.run(base_size=base,
                                   versions=4 if quick else 6,
                                   retain=2 if quick else 3),
        "ingest": lambda: bench_ingest.run(base_size=base,
                                           versions=3 if quick else 4),
        # each restore row also dumps its store's metrics snapshot
        # (DESIGN.md §12) under bench_metrics/ during the smoke gate, so
        # a BENCH regression ships with its own explanation (CI uploads
        # the directory as a workflow artifact)
        "restore": lambda: bench_restore.run(base_size=base,
                                             versions=3 if quick else 4,
                                             range_reads=100 if quick
                                             else 1000,
                                             repeats=1 if quick else 3,
                                             metrics_dir="bench_metrics"
                                             if args.smoke else None),
        # concurrent serving engine (DESIGN.md §10.7): threaded restore
        # throughput + latency; part of the smoke gate so the reader
        # pool / sharded cache / readahead plumbing cannot silently rot
        "serve": lambda: bench_restore.run_threaded(
            base_size=base, versions=3 if quick else 4,
            threads_list=(2,) if args.smoke else (1, 2, 4),
            warm_reps=2 if quick else 6, repeats=1 if quick else 3),
        # object-store serving (DESIGN.md §11.3): coalesced ranged GETs
        # vs the per-chunk baseline under injected latency; the errors
        # column (SHA1 mismatches after retried faults) feeds the smoke
        # gate below, so restores over the object API must stay
        # byte-identical
        "objstore": lambda: bench_objstore.run(
            base_size=min(base, 2 << 20), versions=3,
            workloads=("sql_dump",) if quick else bench_objstore.WORKLOADS,
            latencies=(0.0, 0.002) if args.smoke else (0.0, 0.01),
            repeats=1 if quick else 2),
        # cache hierarchy (DESIGN.md §14): scan resistance lru vs arc,
        # cold-race singleflight collapse, disk tier over the object
        # store; the singleflight section's errors column (SHA1 checks
        # under the thread race) feeds the smoke gate below
        "cache": lambda: (
            bench_cache.run_scan(base_size=min(base, 1 << 20), versions=3,
                                 range_reads=60, scan_rounds=2, scan_mb=6,
                                 repeats=1, guard=False)
            + bench_cache.run_singleflight(base_size=min(base, 2 << 20),
                                           versions=4, repeats=1)
            + bench_cache.run_tier(base_size=min(base, 1 << 20),
                                   versions=3, repeats=1)
        ) if quick else (bench_cache.run_scan()
                         + bench_cache.run_singleflight()
                         + bench_cache.run_tier()),
    }

    for name, fn in sections.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        rows = fn()
        common.emit(rows, name)
        if args.smoke:
            # rows with an errors column count corrupt/failed restores
            # (the serve section's SHA1 mismatches); the gate must go red
            # on them, not just record a nonzero cell — the pre-§10 code
            # corrupts concurrent restores while exiting 0
            bad = sum(r.get("errors", 0) for r in rows)
            if bad:
                raise SystemExit(
                    f"{name}: {bad} corrupt/failed restores — the smoke "
                    f"gate requires error-free serving")
        print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s",
              flush=True)


if __name__ == "__main__":
    main()
