"""Observability smoke gate (``make observe-smoke``, DESIGN.md §12).

Runs a tiny ingest + warm restore + delete/compact cycle with tracing
on, then fails loudly unless the whole observability surface holds up:

  * the Prometheus exposition parses under the strict validator
    (``repro.api.observe.parse_prometheus_text``: name/label syntax,
    escaping, TYPE lines for every family, cumulative buckets that
    agree with ``_count``) — including a store label value chosen to
    exercise backslash/quote/newline escaping;
  * counter/gauge/histogram families exist for stage timings, cache
    outcomes and request counts, and a warm restore's cache-hit series
    actually moved;
  * the JSON snapshot is ``json.loads``-clean and structurally
    consistent (histogram count == sum of buckets);
  * every ingest/restore stage produced at least one trace span, the
    ring and the JSONL sink agree, and each sink line round-trips
    through ``json.loads``;
  * the ``python -m repro.api.observe dump`` CLI renders the sink.

    PYTHONPATH=src python -m benchmarks.observe_smoke
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from repro import api
from repro.api import observe


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"observe-smoke FAILED: {what}")


def main() -> None:
    with tempfile.TemporaryDirectory() as td:
        trace = os.path.join(td, "trace.jsonl")
        cfg = api.DedupConfig.from_dict({
            "detector": "dedup-only",
            "chunker_args": {"avg_size": 4096},
            "backend": "file",
            "backend_args": {"path": os.path.join(td, "containers")},
            "trace_path": trace,
            "trace_ring_events": 512,
        })
        store = api.build_store(cfg)

        data = os.urandom(96 << 10) + b"tail" * 1024
        with store.open_stream() as s:
            s.write(data)
        handle = s.report.handle
        check(store.restore(handle) == data, "cold restore not byte-exact")
        check(store.restore(handle) == data, "warm restore not byte-exact")
        with store.open_stream() as s2:     # a second, deletable stream
            s2.write(data[: 32 << 10])
        store.delete(s2.report.handle)
        store.compact()

        # a label value that needs every escape the exposition defines
        nasty = 'a\\b"c\nd'
        store.metrics().counter("repro_smoke_escapes_total",
                                "exercises label escaping",
                                labels={"path": nasty}).inc(3)

        # --- Prometheus exposition ---------------------------------------
        text = store.metrics().to_prometheus()
        parsed = observe.parse_prometheus_text(text)
        types, samples = parsed["types"], parsed["samples"]
        wanted = {
            "repro_ingest_stage_seconds": "histogram",
            "repro_restore_stage_seconds": "histogram",
            "repro_restore_requests": "histogram",
            "repro_lock_wait_seconds": "histogram",
            "repro_reader_run_bytes": "histogram",
            "repro_gc_phase_seconds": "histogram",
            "repro_ingest_commits_total": "counter",
            "repro_restore_ops_total": "counter",
            "repro_reader_cache_lookups_total": "counter",
            "repro_reader_requests_total": "counter",
            "repro_store_dcr": "gauge",
            "repro_store_bytes": "gauge",
        }
        for fam, kind in wanted.items():
            check(types.get(fam) == kind, f"family {fam} missing or not "
                                          f"{kind} (got {types.get(fam)})")
        by_series = {(n, tuple(sorted(l.items()))): v
                     for n, l, v in samples}
        check(by_series[("repro_smoke_escapes_total",
                         (("path", nasty),))] == 3.0,
              "escaped label did not round-trip through the exposition")
        check(by_series[("repro_reader_cache_lookups_total",
                         (("outcome", "hit"),))] > 0,
              "warm restore recorded no cache hits")

        # --- JSON snapshot ------------------------------------------------
        snap = json.loads(store.metrics().to_json())
        for fam in wanted:
            check(fam in snap, f"{fam} missing from JSON snapshot")
        for fam, body in snap.items():
            if body["type"] != "histogram":
                continue
            for sample in body["samples"]:
                total = sum(n for _, n in sample["buckets"])
                check(total == sample["count"],
                      f"{fam}: histogram count {sample['count']} != "
                      f"bucket sum {total}")

        # --- trace ring + JSONL sink -------------------------------------
        ops = store.observe.tracer.ops()
        for op in ("ingest", "ingest.chunk", "ingest.store", "restore",
                   "restore.plan", "restore.fetch", "restore.join",
                   "gc.delete", "gc.compact"):
            check(ops.get(op, 0) >= 1, f"no trace span for {op}")
        ring_count = len(store.observe.tracer.events())
        store.close()   # flushes + closes the sink

        with open(trace, encoding="utf-8") as f:
            sink = [json.loads(line) for line in f if line.strip()]
        check(len(sink) == ring_count,
              f"sink has {len(sink)} spans, ring {ring_count}")
        check(all("op" in e and "id" in e and "tid" in e for e in sink),
              "sink span missing op/id/tid fields")

        out = subprocess.run(
            [sys.executable, "-m", "repro.api.observe", "dump", trace],
            capture_output=True, text=True,
            env=dict(os.environ,
                     PYTHONPATH="src" + os.pathsep
                     + os.environ.get("PYTHONPATH", "")))
        check(out.returncode == 0, f"observe dump CLI failed: {out.stderr}")
        check(f"# {len(sink)} spans" in out.stdout,
              "observe dump did not report the span roll-up")

    print(f"observe-smoke OK: {len(types)} metric families, "
          f"{len(samples)} samples, {len(sink)} trace spans")


if __name__ == "__main__":
    main()
