"""End-to-end driver (the paper's scenario): a backup service ingesting
nightly versions of three datasets, with CARD's context model trained on
the first night, a per-night IngestReport from each committed stream
session, and full restore validation — optionally against the on-disk
container backend.

With ``--retain K`` the run continues into the retention phase
(DESIGN.md §7): the oldest nights are expired via ``store.delete``, the
mark-sweep ``collect()`` classifies what became reclaimable, and
``compact()`` rewrites the container — rebasing surviving patches whose
base night was expired — reporting the measured bytes given back.

The serving phase (DESIGN.md §9) then reads the newest night back the
way a restore service would: a full planned ``restore`` with its
``RestoreReport`` telemetry, a streaming ``restore_iter`` pass, and
random partial-object reads via ``restore_range`` (only the chunks the
range overlaps are decoded, via the recipe's persisted prefix sums).

    PYTHONPATH=src python examples/dedup_backup_run.py [--size-mb 8] \
        [--nights 5] [--backend file --store-dir /tmp/containers] \
        [--retain 3] [--policy never] [--range-reads 64]
"""
import argparse
import time

import numpy as np

from repro import api
from repro.data import make_workload, WorkloadConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=int, default=6)
    ap.add_argument("--nights", type=int, default=5)
    ap.add_argument("--avg-chunk", type=int, default=16384)
    ap.add_argument("--backend", choices=("memory", "file"), default="memory")
    ap.add_argument("--store-dir", default="/tmp/repro_containers")
    ap.add_argument("--retain", type=int, default=0,
                    help="keep only the newest K nights (0 = keep all)")
    ap.add_argument("--policy", default="never",
                    choices=("never", "eager", "threshold"),
                    help="auto-compaction policy consulted on each delete")
    ap.add_argument("--range-reads", type=int, default=64,
                    help="random 64 KiB partial reads in the serving phase")
    args = ap.parse_args()
    api.enable_compile_cache()

    for wl in ("sql_dump", "vmdk", "kernel"):
        versions = make_workload(wl, WorkloadConfig(
            base_size=args.size_mb << 20, versions=args.nights))
        cfg = api.DedupConfig.from_dict({
            "detector": "card",
            "detector_args": {"use_kernel": False},
            "chunker_args": {"avg_size": args.avg_chunk},
            "backend": args.backend,
            "backend_args": ({"path": f"{args.store_dir}/{wl}"}
                             if args.backend == "file" else {}),
            "policy": args.policy,
        })
        store = api.build_store(cfg)
        t0 = time.time()
        store.fit(versions[:1])           # offline context-model training
        fit_s = time.time() - t0
        print(f"\n=== {wl}: {args.nights} nights x {args.size_mb} MiB "
              f"({args.backend} backend, model fit {fit_s:.1f}s) ===")
        handles = []
        for night, v in enumerate(versions):
            session = store.open_stream()
            session.write(v)
            rep = session.commit()
            handles.append(rep.handle)
            print(f"night {night}: ingested {rep.bytes_in >> 20} MiB, "
                  f"stored {rep.bytes_stored >> 10} KiB new, "
                  f"night DCR {rep.dcr:.2f} / cumulative {store.stats.dcr:.2f} "
                  f"(dup {rep.dup_chunks} / delta {rep.delta_chunks} / "
                  f"raw {rep.raw_chunks})")
        for night, h in enumerate(handles):
            assert store.restore(h) == versions[night]
        print(f"restore: all {args.nights} nights byte-exact | "
              f"total detect {store.stats.detect_seconds:.2f}s "
              f"delta-io {store.stats.delta_seconds:.2f}s")

        if 0 < args.retain < args.nights:
            expire = handles[:args.nights - args.retain]
            t0 = time.time()
            for h in expire:
                store.delete(h)     # eager/threshold policies compact here
            marked = store.collect()
            print(f"retention: expired nights 0-{len(expire) - 1}, "
                  f"marked {marked.reclaimable_bytes >> 10} KiB reclaimable "
                  f"({marked.pinned_chunks} chunks pinned as delta bases)")
            if args.policy == "never":
                run = store.compact()
                print(f"compaction epoch {run.epoch}: swept "
                      f"{run.swept_chunks} chunks, rebased "
                      f"{run.rebased_delta} patches + {run.rebased_raw} to "
                      f"raw, reclaimed {store.stats.reclaimed_bytes >> 10} "
                      f"KiB in {time.time() - t0:.2f}s")
            elif store.backend.epoch > 0:
                print(f"policy '{args.policy}' compacted during deletes: "
                      f"epoch {store.backend.epoch}, reclaimed "
                      f"{store.stats.reclaimed_bytes >> 10} KiB "
                      f"in {time.time() - t0:.2f}s")
            else:
                print(f"policy '{args.policy}' did not trigger compaction "
                      f"({store.stats.dead_bytes >> 10} KiB still awaiting "
                      f"an explicit compact())")
            for night in range(args.nights - args.retain, args.nights):
                assert store.restore(handles[night]) == versions[night]
            post = store.collect()          # re-mark: post-compaction depths
            print(f"restore: surviving {args.retain} nights still byte-exact "
                  f"| live {store.stats.live_bytes >> 20} MiB on disk, "
                  f"chain depths {post.chain_depth_hist}")

        # serving phase (DESIGN.md §9): read the newest night back the
        # way a restore service would
        h, newest = handles[-1], versions[-1]
        full = store.restore(h)
        rep = store.last_restore
        print(f"serve: full restore {rep.bytes_out >> 20} MiB in "
              f"{rep.seconds:.3f}s (read {rep.read_seconds:.3f}s / decode "
              f"{rep.decode_seconds:.3f}s, cache {rep.cache_hits} hit / "
              f"{rep.cache_misses} miss, "
              f"read-amp {rep.read_amplification:.2f})")
        streamed = b"".join(store.restore_iter(h))
        assert full == streamed == newest
        rng = np.random.default_rng(0)
        t0 = time.time()
        for off in rng.integers(0, max(1, len(newest) - (64 << 10)),
                                args.range_reads):
            off = int(off)
            assert (store.restore_range(h, off, 64 << 10)
                    == newest[off:off + (64 << 10)])
        print(f"serve: {args.range_reads} random 64 KiB ranged reads "
              f"byte-exact in {time.time() - t0:.3f}s "
              f"(last touched {store.last_restore.chunks} of "
              f"{len(store.backend.recipe(h))} recipe chunks)")

        # concurrent serving phase (DESIGN.md §10): several clients
        # restoring the surviving nights at once against one store —
        # sharded decode cache, pread reader pool, per-thread telemetry
        import threading
        retained = args.retain if 0 < args.retain < args.nights \
            else args.nights                    # --retain 0 keeps all
        survivors = handles[args.nights - retained:]
        errors = []

        def client(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(3):
                    night = int(rng.integers(0, len(survivors)))
                    got = store.restore(survivors[night])
                    if got != versions[args.nights - retained + night]:
                        raise AssertionError("concurrent restore mismatch")
            except Exception as e:
                errors.append(e)

        t0 = time.time()
        clients = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for c in clients:
            c.start()
        for c in clients:
            c.join()
        assert not errors, errors
        stats = store.stats
        print(f"serve: 4 concurrent clients x 3 restores byte-exact in "
              f"{time.time() - t0:.3f}s (lifetime {stats.restores} "
              f"restores, {stats.restore_bytes_out >> 20} MiB served, "
              f"{stats.restore_prefetch_bytes >> 10} KiB read hidden "
              f"behind decode)")
        store.close()

        # object-store phase (DESIGN.md §11): the same newest nights kept
        # as immutable container objects behind a ranged-GET object API —
        # here the directory-backed fake with 2 ms injected per-request
        # latency and a scheduled transient GET fault, absorbed by the
        # backend's retry-with-backoff
        import tempfile
        with tempfile.TemporaryDirectory() as odir:
            ocfg = {"detector": "dedup-only",
                    "chunker_args": {"avg_size": args.avg_chunk},
                    "backend": "objectstore",
                    "backend_args": {"path": odir}}
            ostore = api.build_store(api.DedupConfig.from_dict(ocfg))
            for v in versions[-2:]:
                with ostore.open_stream() as s:
                    s.write(v)
            oh = s.report.handle
            ostore.close()
            # reopen against the surviving object tree (journal replay),
            # now with injected latency and a scheduled transient GET
            # fault, and serve the newest night cold
            ocfg["backend_args"] = {"path": odir, "latency": 0.002,
                                    "fault_hook":
                                        api.FaultSchedule({"get": [2]})}
            ostore = api.build_store(api.DedupConfig.from_dict(ocfg))
            assert ostore.restore(oh) == versions[-1]
            orep = ostore.last_restore
            print(f"objstore: newest night byte-exact over the object API "
                  f"in {orep.seconds:.3f}s — {orep.requests} coalesced "
                  f"ranged GETs for {len(ostore.backend.recipe(oh))} "
                  f"recipe chunks, {ostore.backend.retries} transient "
                  f"fault(s) retried, "
                  f"{ostore.backend.client.bytes_got >> 10} KiB fetched")
            ostore.close()

    # observability quickstart (DESIGN.md §12): every store carries a
    # metrics registry — Prometheus text via store.metrics()
    # .to_prometheus(), JSON via .to_json() — and setting
    # DedupConfig.trace_path / trace_ring_events turns on per-operation
    # trace spans (ring buffer + JSONL sink; pretty-print or follow the
    # sink with `python -m repro.api.observe dump|tail TRACE`).
    import tempfile
    versions = make_workload("sql_dump", WorkloadConfig(
        base_size=1 << 20, versions=2))
    with tempfile.TemporaryDirectory() as tdir:
        trace = f"{tdir}/trace.jsonl"
        tstore = api.build_store(api.DedupConfig.from_dict({
            "detector": "dedup-only",
            "chunker_args": {"avg_size": args.avg_chunk},
            "trace_path": trace, "trace_ring_events": 256}))
        for v in versions:
            with tstore.open_stream() as s:
                s.write(v)
        assert tstore.restore(s.report.handle) == versions[-1]
        text = tstore.metrics().to_prometheus()
        families = [ln.split()[2] for ln in text.splitlines()
                    if ln.startswith("# TYPE")]
        print(f"\n=== observability (DESIGN.md §12) ===")
        print(f"metrics: {len(families)} families, e.g.")
        picks = ("repro_ingest_stage_seconds_count",
                 "repro_restore_stage_seconds_count",
                 "repro_store_dcr", "repro_reader_requests_total")
        for ln in text.splitlines():
            if ln.startswith(picks):
                print(f"  {ln}")
        spans = tstore.observe.tracer.ops()
        print(f"trace: {sum(spans.values())} spans in the ring — " +
              ", ".join(f"{op} x{n}" for op, n in sorted(spans.items())
                        if "." not in op))
        tstore.close()
        with open(trace) as f:
            print(f"trace sink: {sum(1 for ln in f if ln.strip())} JSONL "
                  f"spans (follow live with "
                  f"`python -m repro.api.observe tail -f {{trace_path}}`)")

    # corruption drill (DESIGN.md §13): the operational failure mode the
    # integrity layer exists for — a bit rots in a stored container,
    # verified reads refuse to serve it, scrub prices the blast radius,
    # repair quarantines the damage, and the untouched night survives
    import tempfile
    from repro.api.faults import flip_bit
    versions = make_workload("sql_dump", WorkloadConfig(
        base_size=1 << 20, versions=2))
    with tempfile.TemporaryDirectory() as ddir:
        dcfg = {"detector": "dedup-only",
                "chunker_args": {"avg_size": args.avg_chunk},
                "backend": "file", "backend_args": {"path": ddir},
                "verify_reads": True}
        dstore = api.build_store(api.DedupConfig.from_dict(dcfg))
        handles = []
        for v in versions:
            with dstore.open_stream() as s:
                s.write(v)
            handles.append(s.report.handle)
        dstore.backend.flush()
        print(f"\n=== corruption drill (DESIGN.md §13) ===")
        rep = dstore.scrub()
        print(f"scrub (healthy): {rep.chunks} chunks, {rep.verified} "
              f"verified in {rep.seconds:.3f}s — clean={rep.clean}")

        # one bit rots in the chunk log
        log = f"{ddir}/chunks.log"
        import os as _os
        flip_bit(log, _os.path.getsize(log) // 2, bit=3)
        dstore.backend._cache.retain(lambda cid: False)
        try:
            dstore.restore(handles[-1])
            served = "SERVED CORRUPT BYTES"           # must not happen
        except api.CorruptChunkError as e:
            served = f"refused (cid {e.cid}, crc {e.actual:#010x} != "\
                     f"{e.expected:#010x})"
        print(f"verified read: {served}")

        rep = dstore.scrub()
        print(f"scrub (rotten): corrupt={list(rep.corrupt)} "
              f"lost={list(rep.lost)} blast_radius={rep.blast_radius} "
              f"streams_lost={list(rep.streams_lost)}")
        fix = dstore.scrub(repair=True)
        print(f"repair: quarantined {len(fix.quarantined)} chunk(s), "
              f"retired {len(fix.retired_streams)} stream(s) — "
              f"clean now: {dstore.scrub().clean}")
        survivors = [h for h in handles if h not in fix.retired_streams]
        for h in survivors:
            dstore.restore(h)       # raises if repair broke a good night
        print(f"survivors: {len(survivors)}/{len(handles)} nights still "
              f"byte-exact")
        dstore.close()


if __name__ == "__main__":
    main()
