"""Quickstart: dedup + delta-compress a 3-version backup stream with CARD,
compare against Finesse / N-transform, verify byte-exact restore.

Pipelines are built declaratively through the repro.api registry
(`DedupConfig.from_dict` -> `build_store`), ingestion goes through stream
sessions, and each committed stream returns its own IngestReport.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro import api
from repro.data import make_workload, WorkloadConfig


def main():
    api.enable_compile_cache()
    versions = make_workload("sql_dump", WorkloadConfig(base_size=2 << 20, versions=3))
    print(f"workload: {len(versions)} versions x {len(versions[0]) >> 20} MiB")
    print(f"registered detectors: {api.available_detectors()}")

    for kind in ("dedup-only", "finesse", "n-transform", "card"):
        cfg = api.DedupConfig.from_dict({
            "detector": kind,
            "detector_args": {"use_kernel": False} if kind == "card" else {},
            "chunker_args": {"avg_size": 8192},
        })
        store = api.build_store(cfg)
        store.fit(versions[:1])
        handles = []
        for v in versions:
            with store.open_stream() as session:
                session.write(v)
            handles.append(session.report.handle)
        s = store.stats
        print(f"{store.detector.name:12s} DCR={s.dcr:5.2f}  dup={s.dup_chunks:4d} "
              f"delta={s.delta_chunks:4d} raw={s.raw_chunks:4d} "
              f"detect={s.detect_seconds:5.2f}s")
        assert store.restore(handles[1]) == versions[1], "restore must be byte-exact"
    print("restore verified byte-exact for every detector")


if __name__ == "__main__":
    main()
