"""repro.api — the layered public surface of the dedup/delta system.

Layers (DESIGN.md §2 and §7), each depending only on the ones above it:

  types        DetectBatch / DetectResult / IngestReport / StoreStats
  detect       staged detector protocol (extract -> score -> observe),
               legacy-``detect`` compatibility shim
  concurrency  RWLock + per-thread I/O telemetry for concurrent serving
  containers   ContainerBackend protocol; memory + file backends and the
               shared PlannedChainReader read engine
  objectstore  ranged-GET ObjectStoreBackend over an object API, the
               fault-injecting LocalObjectStore fake, the boto3 seam,
               and the cp/ls/stat/verify CLI (DESIGN.md §11)
  observe      metrics registry (counters/gauges/log2 histograms with
               per-thread shards), structured trace spans (ring buffer +
               JSONL sink), Prometheus/JSON exporters and the dump/tail
               CLI (DESIGN.md §12)
  faults       deterministic fault injection: FaultSchedule, named
               crashpoints + FaultInjector, bit-flip/truncate helpers,
               and the crash-script harness (DESIGN.md §13.4)
  integrity    crc32c, typed corruption errors, and the scrub/repair
               fsck walk behind DedupStore.scrub (DESIGN.md §13)
  refcount     chunk recipe/base refcounting for space reclamation
  restore      serving-path policy: restore planner (chain-grouped,
               topologically ordered, offset-sorted reads), byte-budgeted
               DecodeCache, recipe prefix sums for ranged reads
  store        DedupStore with transactional StreamSession ingestion and
               the restore/restore_iter/restore_range serving surface
  serve        multi-tenant DedupServer front end: per-tenant
               namespaces/quotas, admission control, request deadlines,
               circuit-breaker degradation (DESIGN.md §15)
  lifecycle    delete / mark-sweep collect / compaction with rebase,
               pluggable reclamation policies
  registry     name -> factory tables for detectors/indexes/chunkers/
               backends/policies
  config       DedupConfig.from_dict(...) -> build_store(...)

Quick start:

    from repro import api
    store = api.build_store(api.DedupConfig.from_dict({"detector": "card"}))
    store.fit([first_version])
    with store.open_stream() as s:
        s.write(first_version)
    report = s.report                     # IngestReport from the commit
    assert store.restore(report.handle) == first_version
    store.delete(report.handle)           # retire the stream ...
    store.collect()
    store.compact()                       # ... and reclaim its bytes

(The snippet above is executed verbatim by tests/test_api.py, so it
stays honest.)
"""
from repro.api.types import (  # noqa: F401
    DetectBatch,
    DetectResult,
    IngestReport,
    RestoreReport,
    StoreStats,
)
from repro.api.restore import (  # noqa: F401
    DEFAULT_CACHE_BYTES,
    DEFAULT_CACHE_SHARDS,
    DecodeCache,
    RecipeLayout,
    RestorePlan,
    ShardedDecodeCache,
    coalesce_reads,
    plan_chains,
)
from repro.api.concurrency import (  # noqa: F401
    DeadlineExceededError,
    IoTelemetry,
    LockTimeout,
    RWLock,
    check_deadline,
    current_deadline,
    deadline_scope,
    remaining_time,
)
from repro.api.detect import (  # noqa: F401
    LegacyDetectMixin,
    StagedDetector,
    is_staged,
    run_detect,
)
from repro.api.containers import (  # noqa: F401
    ContainerBackend,
    FileBackend,
    InMemoryBackend,
    PlannedChainReader,
)
# objectstore exports resolve lazily (PEP 562, __getattr__ below): an
# eager import here would land repro.api.objectstore in sys.modules
# while ``python -m repro.api.objectstore`` is still locating it, and
# runpy warns about exactly that. The registry reaches the module by
# name anyway, so nothing else needs it at package-import time.
from repro.api.refcount import RefcountTable  # noqa: F401
from repro.api.store import DedupStore, StreamSession, chunk_with  # noqa: F401
from repro.api.lifecycle import (  # noqa: F401
    CollectReport,
    CompactionRun,
    EagerPolicy,
    NeverPolicy,
    ReclamationPolicy,
    ThresholdPolicy,
)
from repro.api.registry import (  # noqa: F401
    available_backends,
    available_chunkers,
    available_detectors,
    available_indexes,
    available_policies,
    get_backend,
    get_chunker,
    get_detector,
    get_index,
    get_policy,
    register_backend,
    register_chunker,
    register_detector,
    register_index,
    register_policy,
)
from repro.api.config import (  # noqa: F401
    DedupConfig,
    build_backend,
    build_chunker,
    build_detector,
    build_policy,
    build_server,
    build_store,
    enable_compile_cache,
)

_OBJECTSTORE_EXPORTS = frozenset({
    "FaultSchedule", "LocalObjectStore", "ObjectStoreBackend",
    "S3ObjectClient", "TransientError",
})

# integrity + fault-injection layers (DESIGN.md §13) resolve lazily too:
# both are leaf modules, but keeping them off the package-import path
# keeps ``import repro.api`` lean and mirrors the objectstore treatment.
# FaultSchedule/TransientError stay addressed through objectstore above
# for compatibility (objectstore re-exports them from faults).
_INTEGRITY_EXPORTS = frozenset({
    "CorruptChunkError", "CorruptJournalError", "ScrubReport", "crc32c",
})
_FAULTS_EXPORTS = frozenset({
    "FaultInjector", "RetryBudgetExceeded", "SimulatedCrash",
    "register_crashpoint", "registered_crashpoints",
})

# same lazy treatment for the observability layer: repro.api.observe has
# a ``python -m`` CLI of its own (dump/tail), so it must not be imported
# at package-import time (DedupStore imports it on construction, which
# is after runpy has located the module)
_OBSERVE_EXPORTS = frozenset({
    "MetricsRegistry", "Observability", "Tracer", "parse_prometheus_text",
})

# the §15 multi-tenant serving layer rides on the store, so it stays off
# the package-import path like the other heavy layers
_SERVE_EXPORTS = frozenset({
    "CircuitBreaker", "CircuitOpenError", "DedupServer", "OverloadError",
    "QuotaExceededError", "RequestRejected", "TenantConfig",
})


def __getattr__(name: str):
    if name in _OBJECTSTORE_EXPORTS:
        from repro.api import objectstore
        return getattr(objectstore, name)
    if name in _OBSERVE_EXPORTS:
        from repro.api import observe
        return getattr(observe, name)
    if name in _INTEGRITY_EXPORTS:
        from repro.api import integrity
        return getattr(integrity, name)
    if name in _FAULTS_EXPORTS:
        from repro.api import faults
        return getattr(faults, name)
    if name in _SERVE_EXPORTS:
        from repro.api import serve
        return getattr(serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
