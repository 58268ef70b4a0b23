"""Value types of the layered detection & store API (DESIGN.md §2).

These are the *only* objects that cross layer boundaries:

  DetectBatch    one stream's worth of chunks handed to a detector —
                 replaces the positional ``(chunks, ids, is_new,
                 stream_hashes)`` array soup of the v0 ``Detector.detect``
                 protocol;
  DetectResult   per-chunk resemblance verdict (base chunk id, score);
  IngestReport   immutable per-stream accounting returned by
                 ``StreamSession.commit()`` — the stream handle plus the
                 stream's own byte/chunk/time counters;
  RestoreReport  immutable per-restore accounting (DESIGN.md §9.4):
                 bytes served vs container bytes read, read/decode time
                 split, decode-cache hits/misses. The store keeps the
                 latest on ``DedupStore.last_restore``;
  StoreStats     the store-lifetime aggregate (sum of every IngestReport
                 and RestoreReport plus offline fit time). Kept for the
                 v0 surface; new code should prefer the per-call reports.

Nothing in this module mutates anything and nothing here imports the
pipeline, so every layer (core detectors, container backends, registry,
benchmarks) can depend on it without cycles.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

import numpy as np

if TYPE_CHECKING:  # avoid an import cycle at runtime; chunking is a leaf
    from repro.core.chunking import Chunk


@dataclasses.dataclass
class DetectBatch:
    """One stream of chunks, exact-dedup already resolved.

    chunks         the stream's chunks, in stream order
    ids            [n] int64 chunk id per chunk (duplicates share ids)
    is_new         [n] bool — True where the chunk's content was never
                   stored before (first occurrence wins inside a stream)
    stream_hashes  [len(stream)] uint32 windowed gear hashes of the whole
                   stream, as produced by the chunker scan — detectors
                   reuse them for free sub-chunk features. May be a
                   device-resident ``kernels.ingest.StreamScan`` (indexes
                   like the numpy array; fused detectors read its
                   ``.device`` handle and skip the host round-trip)
    """

    chunks: "Sequence[Chunk]"
    ids: np.ndarray
    is_new: np.ndarray
    stream_hashes: np.ndarray

    def __post_init__(self) -> None:
        self.ids = np.asarray(self.ids, np.int64)
        self.is_new = np.asarray(self.is_new, bool)
        if len(self.chunks) != self.ids.shape[0] or self.ids.shape != self.is_new.shape:
            raise ValueError(
                f"DetectBatch shape mismatch: {len(self.chunks)} chunks, "
                f"ids {self.ids.shape}, is_new {self.is_new.shape}")

    def __len__(self) -> int:
        return len(self.chunks)

    @property
    def offsets(self) -> np.ndarray:
        return np.asarray([c.offset for c in self.chunks], np.int64)


@dataclasses.dataclass
class DetectResult:
    """Per-chunk verdict: base chunk id to delta-encode against (-1 = store
    raw) and, when the detector produces one, the resemblance score."""

    base_ids: np.ndarray
    scores: np.ndarray | None = None

    def __post_init__(self) -> None:
        self.base_ids = np.asarray(self.base_ids, np.int64)

    def __len__(self) -> int:
        return int(self.base_ids.shape[0])


@dataclasses.dataclass(frozen=True)
class IngestReport:
    """What one committed stream did to the store (returned by
    ``StreamSession.commit()``; never mutated afterwards)."""

    handle: int                 # pass to DedupStore.restore()
    bytes_in: int = 0
    bytes_stored: int = 0
    chunks: int = 0
    dup_chunks: int = 0
    delta_chunks: int = 0
    raw_chunks: int = 0
    detect_seconds: float = 0.0
    chunk_seconds: float = 0.0
    delta_seconds: float = 0.0
    # detect/store stage breakdown (benchmarks/bench_ingest.py): for a
    # staged detector, detect_seconds == extract + score + observe;
    # legacy single-call detectors book everything under score_seconds.
    # store_seconds is backend I/O (put_many/recipe/flush), excluding the
    # delta encodes already counted by delta_seconds.
    extract_seconds: float = 0.0
    score_seconds: float = 0.0
    observe_seconds: float = 0.0
    store_seconds: float = 0.0
    # measured by the commit's spans (DESIGN.md §12.3): pass 1's digest
    # lookups and id assignment; the index query inside score_seconds;
    # pass 3a's base lookups outside delta_seconds, with their count and
    # how many of them were this commit's own chunks (no store read);
    # the whole commit, lock waits included
    dedup_seconds: float = 0.0
    search_seconds: float = 0.0
    base_read_seconds: float = 0.0
    base_reads: int = 0
    base_read_hits: int = 0
    commit_seconds: float = 0.0
    # programs JAX traced on the committing thread during the commit, and
    # their trace + lower + compile seconds
    compiles: int = 0
    compile_seconds: float = 0.0
    # (op, start ns, seconds) of the commit's spans, root first, then
    # depth first; starts are ``time.time_ns()``, the profiler's clock,
    # so a caller with no tracer can place each stage on a device trace
    spans: tuple = dataclasses.field(default=(), repr=False)

    @property
    def dcr(self) -> float:
        """This stream's own deduplication-compression ratio."""
        return self.bytes_in / max(1, self.bytes_stored)


@dataclasses.dataclass(frozen=True)
class RestoreReport:
    """What one restore (full, ranged, or fully-consumed iterator) cost
    (DESIGN.md §9.4). ``read_seconds``/``decode_seconds``/``bytes_read``
    and the cache counters come from backend telemetry deltas; backends
    without counters (e.g. the in-memory one) report zeros there while
    ``seconds``/``bytes_out`` stay exact."""

    handle: int
    bytes_out: int = 0          # bytes served to the caller
    chunks: int = 0             # recipe slots touched
    seconds: float = 0.0        # end-to-end wall time of the call
    read_seconds: float = 0.0   # container payload I/O (summed across
    #                             pooled readers, so it can exceed the
    #                             wall-clock share once readahead overlaps
    #                             reads with decode — DESIGN.md §10.5)
    decode_seconds: float = 0.0  # delta-chain decoding
    bytes_read: int = 0         # container bytes fetched (vs bytes_out)
    cache_hits: int = 0
    cache_misses: int = 0
    # container bytes whose read was fully hidden behind decode work by
    # the double-buffered fetcher (§10.3) — the readahead payoff gauge
    prefetch_bytes: int = 0
    # physical payload reads issued (preads / ranged GETs): the cost
    # metric for latency-bound remote backends (DESIGN.md §11.3)
    requests: int = 0
    join_seconds: float = 0.0   # assembling and slicing the answer

    @property
    def read_amplification(self) -> float:
        """Container bytes read per byte served (< 1 once cache-warm)."""
        return self.bytes_read / max(1, self.bytes_out)


@dataclasses.dataclass
class StoreStats:
    """Store-lifetime aggregate: the sum of every committed IngestReport
    plus offline model-fit time (invariant tested in tests/test_api.py).

    The lifecycle fields (DESIGN.md §7) are maintained by the reclamation
    subsystem, not by ``absorb``: ``live_bytes``/``dead_bytes`` mirror the
    refcount table after every commit/delete/collect (``dead_bytes``
    counts everything a compaction pass can drop — unreferenced records
    plus records pinned only as delta bases, which rebasing frees);
    ``reclaimed_bytes`` accumulates the measured container shrink across
    compactions; ``chain_depth_hist`` is the live delta-chain depth
    histogram from the last ``collect()``."""

    bytes_in: int = 0
    bytes_stored: int = 0
    chunks: int = 0
    dup_chunks: int = 0
    delta_chunks: int = 0
    raw_chunks: int = 0
    detect_seconds: float = 0.0
    chunk_seconds: float = 0.0
    delta_seconds: float = 0.0
    extract_seconds: float = 0.0
    score_seconds: float = 0.0
    observe_seconds: float = 0.0
    store_seconds: float = 0.0
    fit_seconds: float = 0.0
    live_bytes: int = 0
    dead_bytes: int = 0
    reclaimed_bytes: int = 0
    chain_depth_hist: dict[int, int] = dataclasses.field(default_factory=dict)
    # restore telemetry (DESIGN.md §9.4): the running sum of every
    # absorbed RestoreReport, maintained by absorb_restore
    restores: int = 0
    restore_bytes_out: int = 0
    restore_bytes_read: int = 0
    restore_seconds: float = 0.0
    restore_read_seconds: float = 0.0
    restore_decode_seconds: float = 0.0
    restore_cache_hits: int = 0
    restore_cache_misses: int = 0
    restore_prefetch_bytes: int = 0
    restore_requests: int = 0
    restore_join_seconds: float = 0.0

    @property
    def dcr(self) -> float:
        return self.bytes_in / max(1, self.bytes_stored)

    def absorb(self, report: IngestReport) -> None:
        self.bytes_in += report.bytes_in
        self.bytes_stored += report.bytes_stored
        self.chunks += report.chunks
        self.dup_chunks += report.dup_chunks
        self.delta_chunks += report.delta_chunks
        self.raw_chunks += report.raw_chunks
        self.detect_seconds += report.detect_seconds
        self.chunk_seconds += report.chunk_seconds
        self.delta_seconds += report.delta_seconds
        self.extract_seconds += report.extract_seconds
        self.score_seconds += report.score_seconds
        self.observe_seconds += report.observe_seconds
        self.store_seconds += report.store_seconds

    def absorb_restore(self, report: "RestoreReport") -> None:
        self.restores += 1
        self.restore_bytes_out += report.bytes_out
        self.restore_bytes_read += report.bytes_read
        self.restore_seconds += report.seconds
        self.restore_read_seconds += report.read_seconds
        self.restore_decode_seconds += report.decode_seconds
        self.restore_cache_hits += report.cache_hits
        self.restore_cache_misses += report.cache_misses
        self.restore_prefetch_bytes += report.prefetch_bytes
        self.restore_requests += report.requests
        self.restore_join_seconds += report.join_seconds
