"""Session-oriented dedup + delta-compression store (DESIGN.md §2.2).

The store composes the three pluggable seams — a (possibly staged)
detector, a chunker config, and a ``ContainerBackend`` — and owns the
policy between them: exact dedup by content digest, the delta-vs-raw
decision, and accounting.

Ingestion is transactional per stream:

    session = store.open_stream()
    session.write(part1); session.write(part2)   # stage bytes
    report = session.commit()                    # chunk/detect/store
    store.restore(report.handle)                 # byte-identical

``commit()`` returns an immutable per-stream ``IngestReport`` (handle,
per-stream DCR, chunk/dup/delta counts, detect time); the store-lifetime
``StoreStats`` aggregate is the running sum of all reports plus fit time.
Until ``commit()`` a session has only buffered bytes in memory — no
chunking, no detector state, no backend writes — so an *abandoned*
session leaves no trace. A commit that *fails mid-storage* is messier:
records already appended by it persist as unreferenced garbage (swept by
compaction, recovered from by the torn-tail scan), but it still admits
nothing to the detector index and registers no digests, because that
bookkeeping runs only after every backend write succeeded. Storage is a
group commit (DESIGN.md §8): delta decisions run over a worklist first,
then the whole stream lands as one batched backend write (``put_many``),
one recipe append and one flush.

The serving path (DESIGN.md §9) is ``restore(handle)`` plus the two
ranged primitives: ``restore_iter`` yields chunk-aligned views without
materializing the stream, and ``restore_range`` decodes only the chunks
a byte range overlaps (recipe prefix sums, persisted with the recipe).
All three go through the restore planner + ``ContainerBackend.get_many``
so shared base chains decode once per call, and record per-call
``RestoreReport`` telemetry (``store.last_restore``, aggregated on
``StoreStats``).

The v0 surface (``ingest``, integer stream indexes for ``restore``)
remains as thin wrappers: handles are assigned densely in commit order, so
v0 callers keep working unchanged.

Space reclamation (DESIGN.md §7) is delegated to ``repro.api.lifecycle``:
``delete(handle)`` retires a stream and decrefs its chunks (chunks another
stream's patch depends on stay pinned), ``collect()`` is the mark-sweep
accounting pass, ``compact()`` rewrites the container without dead
records, rebasing surviving patches whose base was evicted. The
``RefcountTable`` is rebuilt from the backend on open, so a store reopened
on an existing directory can delete/compact streams it did not ingest.
"""
from __future__ import annotations

import inspect
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Sequence

import numpy as np

from repro.api import containers, lifecycle
from repro.api.concurrency import (DeadlineExceededError, LockTimeout, RWLock,
                                   accumulate, check_deadline, remaining_time,
                                   zero_deltas)
from repro.api.detect import is_staged
from repro.api.refcount import RefcountTable
from repro.api.restore import RecipeLayout
from repro.api.types import DetectBatch, IngestReport, RestoreReport, StoreStats
from repro.core import chunking, delta


def _accepts_lengths(add_recipe: Any) -> bool:
    """Whether a backend's ``add_recipe`` takes the ``lengths`` argument
    (§9.3); conservatively False when the signature is uninspectable —
    the store then falls back to materialize-once for ranged reads."""
    try:
        params = inspect.signature(add_recipe).parameters
    except (TypeError, ValueError):
        return False
    return "lengths" in params or any(
        p.kind is inspect.Parameter.VAR_POSITIONAL
        for p in params.values())


def chunk_with(chunker: Any, stream: bytes):
    """Dispatch chunking through a registered chunker.

    Custom chunkers implement ``chunk(stream) -> (chunks, stream_hashes)``
    where chunks are ``repro.core.chunking.Chunk`` and stream_hashes are
    the per-position window hashes detectors reuse (may be the gear scan
    or the chunker's own). Anything without a ``chunk`` method is treated
    as a FastCDC ``ChunkerConfig`` (the "fastcdc" builtin) and goes
    through the device gear-scan program (kernels/ingest, DESIGN.md §8):
    bytes go up, bit-packed boundary-candidate maps come back, and the
    returned stream hashes are a device-resident ``StreamScan`` that
    fused detectors consume without a round-trip (legacy consumers can
    index it like the old numpy array).
    """
    if hasattr(chunker, "chunk"):
        return chunker.chunk(stream)
    buf = np.frombuffer(stream, dtype=np.uint8)
    n = len(buf)
    if n == 0:
        return [], np.zeros(0, np.uint32)
    from repro.kernels import ingest as kingest
    scan, cand_s, cand_l = kingest.scan_stream(
        buf, chunker.mask_s, chunker.mask_l)
    bounds = chunking.select_boundaries(n, cand_s, cand_l, chunker)
    return chunking.chunks_from_bounds(stream, bounds), scan


class StreamSession:
    """Write-then-commit handle for ingesting one stream. After a
    successful ``commit()`` (including via the context manager) the
    IngestReport is also available as ``session.report``."""

    def __init__(self, store: "DedupStore") -> None:
        self._store = store
        self._parts: list[bytes] = []
        self._closed = False
        self.report: IngestReport | None = None

    def write(self, data: bytes) -> None:
        if self._closed:
            raise RuntimeError("stream session already committed/aborted")
        self._parts.append(bytes(data))

    def commit(self) -> IngestReport:
        if self._closed:
            raise RuntimeError("stream session already committed/aborted")
        self._closed = True
        self.report = self._store._commit_stream(b"".join(self._parts))
        return self.report

    def abort(self) -> None:
        self._closed = True
        self._parts.clear()

    def __enter__(self) -> "StreamSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._closed:
            if exc_type is None:
                self.commit()
            else:
                self.abort()


class DedupStore:
    """Container store with exact dedup + detector-driven delta compression."""

    def __init__(self, detector: Any,
                 chunker_cfg: chunking.ChunkerConfig | None = None,
                 backend: containers.ContainerBackend | None = None,
                 policy: Any | None = None,
                 trace_path: str | None = None,
                 trace_ring_events: int | None = None):
        self.detector = detector
        self.cfg = chunker_cfg or chunking.ChunkerConfig()
        self.backend = backend if backend is not None else containers.InMemoryBackend()
        self.policy = policy if policy is not None else lifecycle.NeverPolicy()
        self.stats = StoreStats()
        self.reports: list[IngestReport] = []
        self._by_digest: dict[bytes, int] = {}
        # a reopened (file-backed) backend already holds chunk ids; start
        # past them so new chunks never shadow persisted records
        self._next_id = self.backend.max_chunk_id() + 1
        # capability probe, once: third-party backends may predate the
        # two-argument add_recipe (§9.3). Probing the signature up front
        # beats catching TypeError around the call — a TypeError raised
        # *inside* a new-signature backend after it mutated state must
        # propagate, not trigger a second (duplicating) append.
        self._recipe_lengths_ok = _accepts_lengths(self.backend.add_recipe)
        self._refs = RefcountTable.rebuild(self.backend)
        # ranged-restore prefix sums per handle (DESIGN.md §9.3), built
        # lazily; dropped on delete, *kept* across compaction (lengths
        # are invariant under rebasing)
        self._layouts: dict[int, RecipeLayout] = {}
        self.last_restore: RestoreReport | None = None
        # concurrent serving (DESIGN.md §10.4): restores and commits take
        # the shared side, lifecycle mutations (delete/collect/compact —
        # they swap the backend's index and reopen its read fds) the
        # exclusive side; commits are additionally serialized against
        # each other, and the aggregate stats/layout caches have their
        # own leaf mutex. The prefetch pool runs restore_iter's
        # next-batch fetches (§10.3), created on first use.
        # observability (DESIGN.md §12): every store owns a metrics
        # registry; the tracer exists only when tracing was configured.
        # Must be built before the lifecycle lock (its wait-time
        # observer) and before the backend binding below.
        self._init_observability(trace_path, trace_ring_events)
        self._lifecycle_lock = RWLock(observer=self._observe_lock_wait)
        self._commit_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._prefetch: ThreadPoolExecutor | None = None
        # two close flags (§10.4): _closed flips first (under the stats
        # lock) and stops prefetch-pool (re)creation; _backend_closed
        # flips under the exclusive lifecycle lock right before the
        # backend closes, so fetches that were in flight when close()
        # started — including the drained prefetch tasks — still finish,
        # while any fetch arriving after gets a clean RuntimeError
        self._closed = False
        self._backend_closed = False
        # bound once: per-thread backend telemetry hook (None -> the
        # global-attr fallback in _backend_counters)
        self._io_counters = getattr(self.backend, "io_counters", None)
        self._fold_io = getattr(self.backend, "fold_io_counters", None)
        # route the backend's own counters through the registry as
        # derived views (+ native run-width/request histograms there)
        bind = getattr(self.backend, "bind_observability", None)
        if bind is not None:
            bind(self.observe)
        self._refresh_lifecycle_stats()

    def _init_observability(self, trace_path: str | None,
                            trace_ring_events: int | None) -> None:
        from repro.api import observe as om   # local: keeps module import
        self.observe = om.Observability(      # light for the observe CLI
            trace_path=trace_path, trace_ring_events=trace_ring_events)
        m = self.observe.metrics
        # native ingest/restore instruments (recorded at the event);
        # handles are pre-created so every family appears in the
        # exposition from the first snapshot, zeros included
        self._c_ingest_commits = m.counter(
            "repro_ingest_commits_total", "Committed stream sessions")
        self._c_ingest_bytes = {
            d: m.counter("repro_ingest_bytes_total",
                         "Stream bytes in vs. container bytes stored",
                         labels={"dir": d}) for d in ("in", "stored")}
        self._c_ingest_chunks = {
            k: m.counter("repro_ingest_chunks_total",
                         "Chunk dispositions at commit (DESIGN.md §2.2)",
                         labels={"kind": k})
            for k in ("dup", "delta", "raw")}
        self._c_ingest_bases = {
            k: m.counter("repro_ingest_bases_total",
                         "Chosen delta bases by where the detector found "
                         "them: its index or the stream's earlier chunks",
                         labels={"source": k})
            for k in ("index", "stream")}
        self._h_ingest_stage = {
            s: m.histogram("repro_ingest_stage_seconds",
                           "Per-commit ingest phase timings (§8)",
                           labels={"stage": s}, bounds=om.SECONDS_BUCKETS)
            for s in ("chunk", "dedup", "extract", "score", "search",
                      "intra", "observe", "base_read", "delta", "store")}
        self._c_jax_compiles = m.counter(
            "repro_jax_compiles_total",
            "Programs JAX traced on the committing thread during commits")
        self._c_jax_compile_seconds = m.counter(
            "repro_jax_compile_seconds_total",
            "Trace, lower and compile seconds of those programs")
        self._c_restore_ops = {
            s: m.counter("repro_restore_ops_total",
                         "Restore calls by serving surface (§9)",
                         labels={"surface": s})
            for s in ("full", "iter", "range")}
        self._c_restore_bytes = {
            d: m.counter("repro_restore_bytes_total",
                         "Bytes served vs. physical payload bytes read",
                         labels={"dir": d}) for d in ("out", "read")}
        self._h_restore_stage = {
            s: m.histogram("repro_restore_stage_seconds",
                           "Per-restore wall/read/decode/join timings (§9)",
                           labels={"stage": s}, bounds=om.SECONDS_BUCKETS)
            for s in ("total", "read", "decode", "join")}
        self._h_restore_requests = m.histogram(
            "repro_restore_requests",
            "Physical payload reads (preads / ranged GETs) per restore",
            bounds=om.COUNT_BUCKETS)
        self._h_lock_wait = {
            s: m.histogram("repro_lock_wait_seconds",
                           "RWLock acquire wait time — the §10 "
                           "lock-contention signal",
                           labels={"lock": "lifecycle", "side": s},
                           bounds=om.SECONDS_BUCKETS)
            for s in ("read", "write")}
        # lifecycle gauges are derived views over StoreStats — the
        # authoritative aggregate — copied in at snapshot time
        g_bytes = {k: m.gauge("repro_store_bytes",
                              "Store accounting (live/dead per §7.2)",
                              labels={"kind": k})
                   for k in ("in", "stored", "live", "dead", "reclaimed")}
        g_dcr = m.gauge("repro_store_dcr",
                        "Lifetime data compression ratio (bytes_in / "
                        "bytes_stored)")
        g_streams = m.gauge("repro_store_streams", "Committed streams")

        def _export_store_views() -> None:
            with self._stats_lock:
                s = self.stats
                vals = {"in": s.bytes_in, "stored": s.bytes_stored,
                        "live": s.live_bytes, "dead": s.dead_bytes,
                        "reclaimed": s.reclaimed_bytes}
                dcr = s.dcr
                streams = len(self.reports)
            for k, v in vals.items():
                g_bytes[k].set(v)
            g_dcr.set(dcr)
            g_streams.set(streams)

        m.register_callback(_export_store_views)

    def _observe_lock_wait(self, side: str, seconds: float) -> None:
        self._h_lock_wait[side].observe(seconds)

    def metrics(self):
        """The store's ``MetricsRegistry`` (DESIGN.md §12) — call
        ``.to_prometheus()`` / ``.to_json()`` / ``.snapshot()`` on it.
        Also reachable as ``store.observe.metrics``."""
        return self.observe.metrics

    def cache_stats(self) -> dict:
        """Lifetime cache-hierarchy signals (DESIGN.md §14) as one flat
        dict: eviction-policy name plus ghost hits and evictions from the
        decode cache, cold-decode singleflight waits/collapsed and the
        total decode count, and the local-disk tier's hit/miss/byte/drop
        tallies when a tier is configured. Every key reads straight off
        the backend (derived view, never a second copy); backends without
        the §14 read engine (memory, third-party) report zeros."""
        b = self.backend
        cache = getattr(b, "_cache", None)
        out = {
            "policy": getattr(cache, "policy_name", None),
            "ghost_hits": getattr(cache, "ghost_hits", 0),
            "evictions": getattr(cache, "evictions", 0),
            "singleflight_waits": getattr(b, "_sf_waits", 0),
            "singleflight_collapsed": getattr(b, "_sf_collapsed", 0),
            "decoded_chunks": getattr(b, "decoded_chunks", 0),
        }
        tier = getattr(b, "_tier", None)
        out["tier"] = None if tier is None else {
            "bytes": tier.bytes, "entries": len(tier),
            "hits": tier.hits, "misses": tier.misses,
            "bytes_served": tier.bytes_served,
            "bytes_filled": tier.bytes_filled, "dropped": tier.dropped,
        }
        return out

    def fit(self, training_streams: Sequence[bytes]) -> None:
        t0 = time.perf_counter()
        self.detector.fit(training_streams, self.cfg)
        self.stats.fit_seconds += time.perf_counter() - t0

    def open_stream(self) -> StreamSession:
        return StreamSession(self)

    def ingest(self, stream: bytes) -> StoreStats:
        """v0 surface: one-shot session commit; returns the aggregate."""
        session = self.open_stream()
        session.write(stream)
        session.commit()
        return self.stats

    def _commit_stream(self, stream: bytes) -> IngestReport:
        # one commit at a time (id assignment, digest table, one group
        # commit in flight); commits run concurrently with restores but
        # are excluded from lifecycle mutations (DESIGN.md §10.4).
        # Under a deadline scope (§15.3) both lock waits are bounded:
        # shedding here — before any chunking work — is the cheap place.
        # The root span holds the lock waits; it ends, inside the locks,
        # just before the report is built from it.
        from repro.api import observe as om
        compiles = om.compile_tally()
        with om.Span("ingest", self.observe.tracer) as root:
            check_deadline("commit")
            t = remaining_time()
            if t is None:
                self._commit_lock.acquire()
            elif not self._commit_lock.acquire(timeout=max(0.0, t)):
                raise DeadlineExceededError("commit (commit-lock wait)")
            try:
                self._acquire_read_deadline("commit")
                try:
                    # post-close contract: fail here, before the chunk/
                    # detect passes run, instead of dying on the closed
                    # append handle after the work is done
                    self._check_open()
                    return self._commit_stream_locked(stream, root,
                                                      compiles)
                finally:
                    self._lifecycle_lock.release_read()
            finally:
                self._commit_lock.release()

    def _commit_stream_locked(self, stream: bytes, root: Any,
                              compiles: tuple[int, float]) -> IngestReport:
        from repro.api import observe as om
        # each pass is one span; a per-chunk stage (base reads, encodes)
        # is timed by accumulated sums inside its pass's span
        with om.Span("ingest.chunk") as sp:
            chunks, stream_hashes = chunk_with(self.cfg, stream)
        chunk_seconds = sp.seconds

        # pass 1: exact dedup; assign ids
        with om.Span("ingest.dedup") as sp:
            n = len(chunks)
            ids = np.empty(n, np.int64)
            is_new = np.zeros(n, bool)
            digests = [ck.digest for ck in chunks]
            seen_in_stream: dict[bytes, int] = {}
            for i, dig in enumerate(digests):
                ref = self._by_digest.get(dig)
                if ref is None:
                    ref = seen_in_stream.get(dig)
                if ref is not None:
                    ids[i] = ref
                else:
                    ids[i] = self._next_id
                    self._next_id += 1
                    is_new[i] = True
                    seen_in_stream[dig] = int(ids[i])
        dedup_seconds = sp.seconds

        # deadline probes (§15.3) run only in passes 0-3a — after the
        # first pass-3b backend write the commit must finish (aborting
        # mid-group-commit would orphan records the bookkeeping below
        # never learned about)
        check_deadline("commit")

        # pass 2: resemblance detection (batched, staged). For staged
        # detectors, index admission (`observe`) is deferred until the
        # backend writes succeed, so a commit that fails mid-storage
        # admits nothing to the detector index. Legacy single-call
        # detectors mutate inside detect() and can't make that promise.
        # A zero-chunk stream (``ingest(b"")``) never reaches a detector
        # at all — neither path is required to accept an empty batch.
        # The detector's index query and intra-stream score are the
        # ``ingest.search`` and ``ingest.intra`` children of the score
        # span.
        extract_seconds = score_seconds = observe_seconds = 0.0
        search_seconds = intra_seconds = 0.0
        index_bases = stream_bases = 0
        batch = DetectBatch(chunks=chunks, ids=ids, is_new=is_new,
                            stream_hashes=stream_hashes)
        staged = n > 0 and is_staged(self.detector)
        feats = None
        if n == 0:
            base_ids = np.empty(0, np.int64)
        else:
            if staged:
                with om.Span("ingest.extract") as sp:
                    feats = self.detector.extract(batch)
                extract_seconds = sp.seconds
            with om.Span("ingest.score") as sp:
                if staged:
                    base_ids = self.detector.score(feats, batch).base_ids
                else:
                    base_ids = np.asarray(self.detector.detect(
                        chunks, ids, is_new, stream_hashes), np.int64)
            score_seconds = sp.seconds
            search_seconds = sp.child_seconds("ingest.search")
            intra_seconds = sp.child_seconds("ingest.intra")
            # a chosen base that is one of this stream's new chunks came
            # from the stream; any other is already stored, so the index's
            given = base_ids >= 0
            stream_bases = int((given & np.isin(base_ids, ids[is_new])).sum())
            index_bases = int(given.sum()) - stream_bases

        # pass 3a: delta-vs-raw decisions over a worklist — every
        # delta.encode runs here, back to back, with no backend I/O
        # interleaved. A same-stream base that is not persisted yet is
        # resolved from the staged records (identical semantics to the
        # old put-then-lookup interleaving).
        backend = self.backend
        bytes_in = sum(ck.length for ck in chunks)
        bytes_stored = 0
        # per-record container overhead (headers etc.), backend-reported
        # so per-stream DCR matches the real on-disk footprint —
        # FileBackend's record header is 25 bytes, not a nominal 8
        overhead = int(getattr(backend, "record_overhead", 0))
        dup_chunks = int(n - is_new.sum())
        delta_chunks = raw_chunks = 0
        delta_seconds = base_read_seconds = 0.0
        base_reads = base_read_hits = 0
        staged_data: dict[int, bytes] = {}
        records: list[tuple[int, int, bytes, bytes | None]] = []
        check_deadline("commit")
        with om.Span("ingest.delta"):
            for i in np.flatnonzero(is_new):
                # last shed point: nothing written yet
                check_deadline("commit")
                ck = chunks[i]
                cid = int(ids[i])
                entry = None
                base = int(base_ids[i])
                if base >= 0:
                    # the base: this commit's own chunk, else a read of
                    # the store (a delta chain walked through the cache)
                    t0 = time.perf_counter()
                    base_data = staged_data.get(base)
                    if base_data is not None:
                        base_read_hits += 1
                    elif backend.contains(base):
                        base_data = backend.get(base)
                    base_read_seconds += time.perf_counter() - t0
                    base_reads += 1
                    if base_data is not None:
                        t0 = time.perf_counter()
                        d = delta.encode(ck.data, base_data)
                        delta_seconds += time.perf_counter() - t0
                        if len(d) < ck.length:
                            entry = (cid, base, d, ck.data)
                            bytes_stored += len(d) + overhead
                            delta_chunks += 1
                if entry is None:
                    entry = (cid, -1, ck.data, None)
                    bytes_stored += ck.length + overhead
                    raw_chunks += 1
                records.append(entry)
                staged_data[cid] = ck.data

        # pass 3b: one batched backend write + recipe + flush (group
        # commit: a stream is a single buffered append, DESIGN.md §8).
        # Refcount/digest bookkeeping happens only after the writes
        # succeed, so a failed commit cannot leave digests pointing at
        # payloads that were never stored.
        with om.Span("ingest.store") as sp:
            put_many = getattr(backend, "put_many", None)
            if put_many is not None:
                put_many(records)
            else:                   # third-party backends: per-chunk puts
                for cid, base, payload, data in records:
                    if base < 0:
                        backend.put_raw(cid, payload)
                    else:
                        backend.put_delta(cid, base, payload, data=data)
            for i, (cid, base, payload, _) in zip(np.flatnonzero(is_new),
                                                  records):
                self._refs.track(cid, base, len(payload))
                self._by_digest[digests[i]] = cid
            recipe = [int(c) for c in ids]
            if self._recipe_lengths_ok:     # persist materialized lengths
                handle = backend.add_recipe(    # for ranged restores
                    recipe, [int(ck.length) for ck in chunks])
            else:                           # pre-§9 backend signature
                handle = backend.add_recipe(recipe)
            for cid in recipe:      # only now do the chunks become live
                self._refs.incref_recipe(cid)
            backend.flush()
        store_seconds = sp.seconds

        if staged:
            with om.Span("ingest.observe") as sp:
                self.detector.observe(feats, batch)
            observe_seconds = sp.seconds

        if root.tracer is not None:
            root.labels.update(
                handle=handle, bytes_in=bytes_in, bytes_stored=bytes_stored,
                chunks=n, dup_chunks=dup_chunks, delta_chunks=delta_chunks,
                index_bases=index_bases, stream_bases=stream_bases,
                base_reads=base_reads, base_read_hits=base_read_hits,
                dcr=round(bytes_in / max(1, bytes_stored), 4))
        root.end()
        traced, compile_seconds = om.compile_tally()
        report = IngestReport(
            handle=handle, bytes_in=bytes_in, bytes_stored=bytes_stored,
            chunks=n, dup_chunks=dup_chunks, delta_chunks=delta_chunks,
            raw_chunks=raw_chunks,
            detect_seconds=extract_seconds + score_seconds + observe_seconds,
            chunk_seconds=chunk_seconds, delta_seconds=delta_seconds,
            extract_seconds=extract_seconds, score_seconds=score_seconds,
            observe_seconds=observe_seconds, store_seconds=store_seconds,
            dedup_seconds=dedup_seconds, search_seconds=search_seconds,
            intra_seconds=intra_seconds, index_bases=index_bases,
            stream_bases=stream_bases,
            base_read_seconds=base_read_seconds, base_reads=base_reads,
            base_read_hits=base_read_hits, commit_seconds=root.seconds,
            compiles=traced - compiles[0],
            compile_seconds=compile_seconds - compiles[1],
            spans=tuple((s.op, s.t0_ns, s.seconds) for s in root.walk()))
        with self._stats_lock:
            self.reports.append(report)
            self.stats.absorb(report)
            self._refresh_lifecycle_stats()
        self._observe_ingest(report)
        return report

    def _observe_ingest(self, r: IngestReport) -> None:
        """Record one commit into the registry: counters and the stage
        timings its spans measured (DESIGN.md §12.3)."""
        self._c_ingest_commits.inc()
        self._c_ingest_bytes["in"].inc(r.bytes_in)
        self._c_ingest_bytes["stored"].inc(r.bytes_stored)
        self._c_ingest_chunks["dup"].inc(r.dup_chunks)
        self._c_ingest_chunks["delta"].inc(r.delta_chunks)
        self._c_ingest_chunks["raw"].inc(r.raw_chunks)
        self._c_ingest_bases["index"].inc(r.index_bases)
        self._c_ingest_bases["stream"].inc(r.stream_bases)
        self._c_jax_compiles.inc(r.compiles)
        self._c_jax_compile_seconds.inc(r.compile_seconds)
        h = self._h_ingest_stage
        for stage, seconds in (
                ("chunk", r.chunk_seconds), ("dedup", r.dedup_seconds),
                ("extract", r.extract_seconds), ("score", r.score_seconds),
                ("search", r.search_seconds), ("intra", r.intra_seconds),
                ("observe", r.observe_seconds),
                ("base_read", r.base_read_seconds),
                ("delta", r.delta_seconds), ("store", r.store_seconds)):
            h[stage].observe(seconds)

    # --- serving path (repro.api.restore, DESIGN.md §9) ----------------------

    def restore(self, handle: int) -> bytes:
        """Reconstruct a committed stream byte-for-byte by its handle.
        Raises KeyError once the stream has been deleted (IndexError for
        a handle the store never issued). Safe to call from any number
        of threads at once (DESIGN.md §10.4)."""
        from repro.api import observe as om
        with om.Span("restore", self.observe.tracer, surface="full",
                     handle=handle) as root:
            with om.Span("restore.plan"):
                recipe = self.backend.recipe(handle)
            data, d = self._fetch_counted(recipe)
            with om.Span("restore.join") as sp:
                out = b"".join(data[cid] for cid in recipe)
            self._note_restore(root, handle, len(out), len(recipe),
                               sp.seconds, d, surface="full")
        return out

    def restore_iter(self, handle: int, batch_chunks: int = 256):
        """Stream a committed object as chunk-aligned ``bytes`` views.

        Chunks are materialized ``batch_chunks`` recipe slots at a time
        (one planned ``get_many`` per batch), so serving a stream far
        larger than the decode-cache budget never holds more than a
        couple of batches of output in memory. While the caller consumes
        batch *k*, batch *k+1* is already being fetched on the prefetch
        pool (DESIGN.md §10.3), so I/O, decode and consumer work
        overlap. Same errors as ``restore``, raised at call time; the
        ``RestoreReport`` is recorded when the iterator is exhausted.
        Its root span runs from the call to exhaustion and is booked
        in the tracer only: a span held across ``yield`` would enclose
        the consumer's work in the profiler's trace."""
        from repro.api import observe as om
        root = om.Span("restore", self.observe.tracer, surface="iter",
                       handle=handle).start()
        try:
            with om.Span("restore.plan", parent=root):
                recipe = self.backend.recipe(handle)    # raise at call time
        except Exception as e:
            root.end(e)
            raise

        def gen():
            acc = zero_deltas()
            total = 0
            fut = None
            try:
                for i in range(0, len(recipe), batch_chunks):
                    part = recipe[i:i + batch_chunks]
                    if fut is not None:
                        data, d = fut.result()
                        fut = None
                    else:
                        data, d = self._fetch_counted(part, root)
                    accumulate(acc, d)
                    nxt = recipe[i + batch_chunks:i + 2 * batch_chunks]
                    if nxt:     # overlap the next fetch with consumption
                        fut = self._prefetch_pool().submit(
                            self._prefetch_fetch, nxt, root)
                    for cid in part:
                        piece = data[cid]
                        total += len(piece)
                        yield piece
            finally:
                if fut is not None:     # abandoned mid-stream
                    fut.cancel()
            self._note_restore(root, handle, total, len(recipe), 0.0, acc,
                               surface="iter")

        return gen()

    def restore_range(self, handle: int, offset: int, length: int) -> bytes:
        """Serve ``stream[offset:offset + length]`` — the partial-read
        serving primitive. Recipe prefix sums (persisted at commit) map
        the byte range onto the minimal chunk window, so only the chunks
        overlapping the range are read and chain-decoded. Ranges are
        clamped to the stream tail; negative offset/length raise
        ValueError; same handle errors as ``restore``."""
        from repro.api import observe as om
        with om.Span("restore", self.observe.tracer, surface="range",
                     handle=handle) as root:
            acc = zero_deltas()
            with om.Span("restore.plan"):
                recipe = self.backend.recipe(handle)
                first, last, skip = self._layout(
                    handle, recipe, acc).chunk_window(offset, length)
            if last < first:
                self._note_restore(root, handle, 0, 0, 0.0, acc,
                                   surface="range")
                return b""
            part = recipe[first:last + 1]
            data, d = self._fetch_counted(part)
            accumulate(acc, d)
            with om.Span("restore.join") as sp:
                blob = b"".join(data[cid] for cid in part)
                out = blob[skip:skip + min(length, len(blob) - skip)]
            self._note_restore(root, handle, len(out), len(part),
                               sp.seconds, acc, surface="range")
        return out

    def stream_length(self, handle: int) -> int:
        """Total materialized bytes of a committed stream (no decoding
        when the backend persisted recipe lengths)."""
        return self._layout(handle, self.backend.recipe(handle)).total_bytes

    # --- digest-table persistence seam (DESIGN.md §11.5) ---------------------

    def digest_seeds(self) -> dict[bytes, int]:
        """Snapshot of the exact-dedup digest table (content digest ->
        stored chunk id). The table is in-memory only: a store reopened
        on an existing backend starts with it empty, so re-ingesting
        bytes it already holds stores them again physically. Callers
        that reopen stores across processes (the object-store CLI)
        persist this snapshot and hand it back via ``seed_digests``."""
        with self._stats_lock:
            return dict(self._by_digest)

    def seed_digests(self, mapping: dict[bytes, int]) -> int:
        """Preload the exact-dedup digest table from a ``digest_seeds``
        snapshot taken before the store was closed. Entries whose chunk
        id is no longer stored (deleted + compacted away meanwhile) are
        skipped, so a stale snapshot can never alias fresh content onto
        missing records. Returns how many entries were admitted."""
        admitted = 0
        with self._commit_lock, self._lifecycle_lock.read():
            self._check_open()
            for dig, cid in mapping.items():
                cid = int(cid)
                if self.backend.contains(cid):
                    self._by_digest[bytes(dig)] = cid
                    admitted += 1
        return admitted

    def _fetch_unique(self, cids: Sequence[int]) -> dict[int, bytes]:
        """Materialize each distinct chunk id once: planned ``get_many``
        when the backend implements it, per-chunk ``get`` otherwise."""
        uniq = list(dict.fromkeys(int(c) for c in cids))
        get_many = getattr(self.backend, "get_many", None)
        if get_many is not None:
            return dict(zip(uniq, get_many(uniq)))
        return {cid: self.backend.get(cid) for cid in uniq}

    def _acquire_read_deadline(self, op: str) -> None:
        """Shared lifecycle lock, bounded by the caller's deadline scope
        (§15.3): unbounded callers block exactly as before; a request
        with a budget waits at most what is left of it and fails with
        the deadline error its server maps to the shed taxonomy —
        a wedged compaction then costs one request, not a hung thread."""
        t = remaining_time()
        if t is None:
            self._lifecycle_lock.acquire_read()
            return
        try:
            self._lifecycle_lock.acquire_read(timeout=max(0.0, t))
        except LockTimeout as e:
            raise DeadlineExceededError(f"{op} (lifecycle-lock wait)") from e

    def _fetch_counted(self, cids: Sequence[int],
                       request: Any = None) -> tuple[dict, list]:
        """``_fetch_unique`` under the shared lifecycle lock, returning
        ``(data, io_counter_deltas)``. The snapshot pair runs on the
        same thread as the fetch (see ``FileBackend.io_counters``), so
        the deltas are exact per call even with other restores in
        flight — including when this runs on the prefetch pool. One
        ``restore.fetch`` span, under ``request`` where given (a pool
        thread has no open span of its request), else under the
        thread's open span."""
        from repro.api import observe as om
        lock = self._lifecycle_lock
        with om.Span("restore.fetch", parent=request) as sp:
            check_deadline("restore")
            snap = self._backend_counters()
            self._acquire_read_deadline("restore")
            try:
                # a resumed restore_iter generator can arrive here after
                # close(): the backend's reader fds are gone, so fail
                # with a clean error instead of whatever the closed
                # backend raises. The flag flips under the write lock,
                # so a reader seeing it False is ordered before the
                # close and fetches safely.
                self._check_open()
                data = self._fetch_unique(cids)
            finally:
                lock.release_read()
            now = self._backend_counters()
            d = [now[i] - snap[i] for i in range(len(snap))]
            if sp.tracer is not None:
                sp.labels.update(chunks=len(cids), read_s=d[0],
                                 decode_s=d[1], cache_hits=d[3],
                                 cache_misses=d[4])
        return data, d

    def _prefetch_fetch(self, cids: Sequence[int],
                        request: Any = None) -> tuple[dict, list]:
        """``_fetch_counted`` as a prefetch-pool task: folds this pool
        thread's telemetry record and metric shard when the task is
        done. Pool threads live as long as the store, so without the
        explicit fold (concurrency.IoTelemetry.fold_current) their
        counters would sit outside the dead aggregate until close —
        lifetime totals must be exact under thread reuse, not GC-timed.
        Folding happens after the counter snapshot pair, so the per-call
        deltas the caller consumes are unaffected."""
        try:
            return self._fetch_counted(cids, request)
        finally:
            fold = self._fold_io
            if fold is not None:
                fold()
            self.observe.metrics.fold_current()

    def _prefetch_pool(self) -> ThreadPoolExecutor:
        pool = self._prefetch
        if pool is None:
            with self._stats_lock:
                # never recreate the pool after close() drained it —
                # the executor would leak (nothing shuts it down again)
                if self._closed:
                    raise RuntimeError("store is closed")
                if self._prefetch is None:
                    self._prefetch = ThreadPoolExecutor(
                        max_workers=4, thread_name_prefix="repro-prefetch")
                pool = self._prefetch
        return pool

    def _layout(self, handle: int, recipe: Sequence[int],
                acc: list | None = None) -> RecipeLayout:
        layout = self._layouts.get(handle)
        if layout is None:
            lengths = None
            recipe_lengths = getattr(self.backend, "recipe_lengths", None)
            if recipe_lengths is not None:
                lengths = recipe_lengths(handle)
            if lengths is None:     # pre-§9 recipe: materialize once
                data, d = self._fetch_counted(recipe)
                if acc is not None:
                    accumulate(acc, d)
                lengths = [len(data[cid]) for cid in recipe]
            layout = RecipeLayout(lengths)
            # cache only while the handle is still live, checked under
            # the shared lifecycle lock: a write-locked delete retires
            # the recipe and pops the layout as one atomic step, so an
            # unguarded insert could land *after* the pop and pin the
            # layout forever (handles are never reused). Two threads may
            # still build the same layout concurrently; both compute
            # identical sums, so last-writer-wins is benign.
            lock = self._lifecycle_lock
            self._acquire_read_deadline("restore")
            try:
                try:
                    self.backend.recipe(handle)
                except (KeyError, IndexError):
                    pass        # deleted meanwhile: serve, don't cache
                else:
                    self._layouts[handle] = layout
            finally:
                lock.release_read()
        return layout

    def _backend_counters(self) -> tuple:
        """This thread's backend I/O counters (concurrency.COUNTER_FIELDS
        order); falls back to the backend-lifetime totals for third-party
        backends without per-thread telemetry (exact under serial use,
        which is all such backends support)."""
        io_counters = self._io_counters
        if io_counters is not None:
            return io_counters()
        b = self.backend
        return (getattr(b, "read_seconds", 0.0),
                getattr(b, "decode_seconds", 0.0),
                getattr(b, "bytes_read", 0),
                getattr(b, "cache_hits", 0),
                getattr(b, "cache_misses", 0),
                getattr(b, "prefetch_bytes", 0),
                getattr(b, "read_requests", 0))

    def _note_restore(self, root: Any, handle: int, bytes_out: int,
                      chunks: int, join_seconds: float, d: Sequence,
                      surface: str = "full") -> None:
        """End the request's root span and record its report: wall time
        from the span, read/decode/cache counters from the fetches'
        telemetry deltas, the answer's assembly from its join span."""
        hits, misses = int(d[3]), int(d[4])
        if root.tracer is not None:
            root.labels.update(
                bytes_out=bytes_out, bytes_read=int(d[2]),
                requests=int(d[6]), cache_hits=hits, cache_misses=misses,
                hit_ratio=round(hits / max(1, hits + misses), 4),
                prefetch_bytes=int(d[5]))
        root.end()
        report = RestoreReport(
            handle=handle, bytes_out=bytes_out, chunks=chunks,
            seconds=root.seconds,
            read_seconds=d[0], decode_seconds=d[1], bytes_read=int(d[2]),
            cache_hits=hits, cache_misses=misses,
            prefetch_bytes=int(d[5]), requests=int(d[6]),
            join_seconds=join_seconds)
        with self._stats_lock:
            self.last_restore = report
            self.stats.absorb_restore(report)
        self._c_restore_ops[surface].inc()
        self._c_restore_bytes["out"].inc(report.bytes_out)
        self._c_restore_bytes["read"].inc(report.bytes_read)
        h = self._h_restore_stage
        h["total"].observe(report.seconds)
        h["read"].observe(report.read_seconds)
        h["decode"].observe(report.decode_seconds)
        h["join"].observe(join_seconds)
        self._h_restore_requests.observe(report.requests)

    # --- space reclamation (repro.api.lifecycle, DESIGN.md §7) ---------------

    def _check_open(self) -> None:
        # uniform post-close contract: every surface fails with the same
        # clean error before mutating anything (a delete reaching the
        # closed backend would retire the recipe in memory, then die on
        # the closed journal handle mid-mutation)
        if self._backend_closed:
            raise RuntimeError("store is closed")

    def _acquire_write_deadline(self, op: str) -> None:
        """Exclusive lifecycle lock, bounded by the caller's deadline
        scope — the write-side twin of ``_acquire_read_deadline``. A
        deadline-carrying delete waiting out a storm of restores sheds
        instead of blocking its server slot forever."""
        t = remaining_time()
        if t is None:
            self._lifecycle_lock.acquire_write()
            return
        try:
            self._lifecycle_lock.acquire_write(timeout=max(0.0, t))
        except LockTimeout as e:
            raise DeadlineExceededError(f"{op} (lifecycle-lock wait)") from e

    def delete(self, handle: int) -> int:
        """Retire a committed stream; returns the logical bytes the delete
        made reclaimable. May trigger compaction per the store policy.
        Takes the exclusive lifecycle lock: in-flight restores finish
        first, restores arriving later run against the post-delete state
        (a restore of the deleted handle then raises KeyError)."""
        check_deadline("delete")
        self._acquire_write_deadline("delete")
        try:
            self._check_open()
            return lifecycle.delete_stream(self, handle)
        finally:
            self._lifecycle_lock.release_write()

    def collect(self) -> lifecycle.CollectReport:
        """Mark-sweep accounting pass (mutates no data)."""
        self._acquire_write_deadline("collect")
        try:
            self._check_open()
            return lifecycle.collect(self)
        finally:
            self._lifecycle_lock.release_write()

    def compact(self) -> lifecycle.CompactionRun:
        """Rewrite the container without dead records, rebasing survivors.
        Exclusive: the backend swaps its chunk index and reopens its
        reader-pool fds, so no restore may be mid-plan while it runs."""
        self._acquire_write_deadline("compact")
        try:
            self._check_open()
            return lifecycle.compact(self)
        finally:
            self._lifecycle_lock.release_write()

    def scrub(self, repair: bool = False):
        """Fsck walk (DESIGN.md §13.3): verify every stored record
        against its persisted checksum, check recipe reachability (every
        live recipe's chunks exist, every delta base resolves) and
        refcount consistency, and return a ``ScrubReport`` with the
        per-chunk blast radius. With ``repair=True`` corrupt chunks and
        their transitive dependents are durably quarantined and every
        affected stream retired through the recovery-retire tombstone
        machinery — a follow-up scrub reports clean. Exclusive, like
        delete/compact: nothing reads or commits while the walk runs."""
        from repro.api import integrity
        self._acquire_write_deadline("scrub")
        try:
            self._check_open()
            return integrity.scrub(self, repair=repair)
        finally:
            self._lifecycle_lock.release_write()

    def _refresh_lifecycle_stats(self) -> None:
        # dead_bytes = everything compaction can drop: unreferenced records
        # plus records pinned only as delta bases (rebasing frees them)
        self.stats.live_bytes = self._refs.live_bytes
        self.stats.dead_bytes = self._refs.dead_bytes + self._refs.pinned_bytes

    def close(self) -> None:
        """Idempotent. Restores arriving after close — including a
        partially-consumed ``restore_iter`` generator being resumed —
        raise RuntimeError instead of touching the closed backend."""
        # the flag flips under the same lock that guards prefetch-pool
        # creation, so no pool can be created after it is set; then
        # drain the pool BEFORE taking the exclusive lock — its tasks
        # acquire the shared side, so the reverse order deadlocks.
        # Finally close the backend under exclusion: in-flight restores
        # finish before the reader-pool fds go away (the contract
        # FileBackend documents).
        with self._stats_lock:
            if self._closed:
                return
            self._closed = True
        if self._prefetch is not None:
            self._prefetch.shutdown(wait=True)
            self._prefetch = None
        with self._lifecycle_lock.write():
            self._backend_closed = True
            self.backend.close()
        self.observe.close()    # flush + close the JSONL trace sink
