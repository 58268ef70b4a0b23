"""Declarative pipeline construction: ``DedupConfig.from_dict`` -> ``build_store``.

One construction path for everything (benchmarks, examples, the
checkpoint store, services): a plain-dict config names each component by
its registry key plus keyword arguments for its factory:

    cfg = DedupConfig.from_dict({
        "detector": "card",
        "detector_args": {"feat": {"k": 32, "m": 64, "n": 2},
                          "model": {"d": 50, "steps": 150},
                          "index": "banded-lsh",       # vs "exact"
                          "use_kernel": False},
        "chunker": "fastcdc",
        "chunker_args": {"avg_size": 8192},
        "backend": "file",
        "backend_args": {"path": "/data/containers"},
        "policy": "threshold",               # reclamation (DESIGN.md §7.4)
        "policy_args": {"ratio": 0.25},
        "restore_cache_bytes": 64 << 20,     # decode-cache budget (§9.2)
    })
    store = build_store(cfg)

Configs are JSON-serializable (``to_dict`` round-trips) so a service can
ship them over the wire or pin them in a manifest next to the containers.
"""
from __future__ import annotations

import dataclasses
import inspect
import os
import pathlib
from typing import Any

from repro.api import registry
from repro.api.store import DedupStore

_KNOWN_KEYS = {"detector", "detector_args", "chunker", "chunker_args",
               "backend", "backend_args", "policy", "policy_args",
               "restore_cache_bytes", "restore_cache_shards",
               "restore_cache_policy", "restore_reader_fds",
               "restore_readahead", "restore_coalesce_gap",
               "restore_tier_path", "restore_tier_bytes",
               "verify_reads", "retry_deadline",
               "trace_path", "trace_ring_events",
               "server_workers", "server_args", "tenant_args"}

# the checkout root (src/repro/api/config.py -> three levels up from src)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Call before the first compile, never at import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other path is set here. Otherwise the cache lives at a fixed path
    inside the checkout (``.jax_cache``, git-ignored): the path is part
    of the cache key, so a temporary or per-run name would never hit.
    Either way every program is cached, not only those that took over a
    second to compile (JAX's default): the store's path is many small
    programs, each shape bucket its own.
    """
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# serving/integrity knobs (DESIGN.md §10, §11.3, §13) -> backend factory
# kwargs; each is forwarded only when set and only to factories that
# declare the kwarg
_BACKEND_KNOBS = {"restore_cache_bytes": "cache_bytes",
                  "restore_cache_shards": "cache_shards",
                  "restore_cache_policy": "cache_policy",
                  "restore_reader_fds": "reader_fds",
                  "restore_readahead": "readahead",
                  "restore_coalesce_gap": "coalesce_gap",
                  "restore_tier_path": "tier_path",
                  "restore_tier_bytes": "tier_bytes",
                  "verify_reads": "verify_reads",
                  "retry_deadline": "retry_deadline"}

# integer knobs validated in from_dict: knob name -> smallest legal value
_INT_KNOB_FLOORS = {"restore_cache_bytes": 1, "restore_cache_shards": 1,
                    "restore_reader_fds": 1, "restore_readahead": 0,
                    "restore_coalesce_gap": 0, "restore_tier_bytes": 1}


@dataclasses.dataclass
class DedupConfig:
    detector: str = "card"
    detector_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    chunker: str = "fastcdc"
    chunker_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    backend: str = "memory"
    backend_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    policy: str = "never"
    policy_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    # serving-engine knobs (DESIGN.md §9.2, §10); None keeps each
    # backend's default. Forwarded as the ``cache_bytes`` /
    # ``cache_shards`` / ``reader_fds`` / ``readahead`` factory
    # arguments to backends that declare them (the file backend);
    # backends without a decode cache / reader pool (memory) ignore all.
    restore_cache_bytes: int | None = None      # decode-cache budget
    restore_cache_shards: int | None = None     # cache lock stripes
    # decode-cache eviction policy by registry name (DESIGN.md §14.1):
    # "lru" (default) or the scan-resistant "arc"; resolved through
    # registry.get_cache_policy at backend construction
    restore_cache_policy: str | None = None
    restore_reader_fds: int | None = None       # pread pool size
    restore_readahead: int | None = None        # read runs in flight (0 off)
    # largest gap (bytes) two payload reads may straddle and still be
    # fetched as one pread / ranged GET (§11.3). Backends default it to
    # their medium — 4 KiB for the file log, 1 MiB for object stores —
    # so set it only to override; 0 coalesces exactly-adjacent reads only.
    restore_coalesce_gap: int | None = None
    # local-disk chunk cache tier in front of remote backends
    # (DESIGN.md §14.3): tier_path roots the per-chunk payload files,
    # tier_bytes budgets them (None = backend default). Backends without
    # a remote hop (file, memory) ignore both.
    restore_tier_path: str | None = None
    restore_tier_bytes: int | None = None
    # integrity knobs (DESIGN.md §13): verify_reads=True makes backends
    # that persist checksums validate every payload on the read path,
    # raising CorruptChunkError instead of serving garbage;
    # retry_deadline bounds the object-store retry policy's total sleep
    # per logical request (seconds) — exceeding it raises
    # RetryBudgetExceeded (§13.5). None keeps each backend's default.
    verify_reads: bool | None = None
    retry_deadline: float | None = None
    # multi-tenant serving (DESIGN.md §15): build_server wraps the store
    # in a DedupServer with server_workers executor threads; server_args
    # are extra DedupServer kwargs and tenant_args the default
    # TenantConfig fields (quota_bytes / max_inflight / max_queue /
    # cache_bytes / cache_policy / default_timeout) applied to tenants
    # created on first use. All ignored by plain build_store.
    server_workers: int | None = None
    server_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    tenant_args: dict[str, Any] = dataclasses.field(default_factory=dict)
    # observability (DESIGN.md §12): every store gets a metrics registry
    # unconditionally; structured op tracing turns on only when one of
    # these is set. trace_path appends spans as JSONL (followable with
    # ``python -m repro.api.observe tail``); trace_ring_events keeps the
    # last N spans in memory (``store.observe.tracer.events()``).
    # Setting trace_path alone also enables a default-sized ring.
    trace_path: str | None = None
    trace_ring_events: int | None = None

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "DedupConfig":
        unknown = set(d) - _KNOWN_KEYS
        if unknown:
            raise ValueError(f"unknown DedupConfig keys {sorted(unknown)}; "
                             f"known: {sorted(_KNOWN_KEYS)}")
        cfg = cls(**{k: dict(v) if isinstance(v, dict) else v
                     for k, v in d.items()})
        for name in ("detector", "chunker", "backend", "policy"):
            if not isinstance(getattr(cfg, name), str):
                raise TypeError(f"{name} must be a registry name (str)")
        for name, floor in _INT_KNOB_FLOORS.items():
            value = getattr(cfg, name)
            if value is None:
                continue
            # 0 is meaningful for readahead (serial reads) and for the
            # coalesce gap (merge exactly-adjacent reads only)
            if (not isinstance(value, int) or isinstance(value, bool)
                    or value < floor):
                raise ValueError(f"{name} must be an int >= {floor}, "
                                 f"got {value!r}")
        for name in ("restore_cache_policy", "restore_tier_path"):
            value = getattr(cfg, name)
            if value is not None and not isinstance(value, str):
                raise TypeError(f"{name} must be a str, got {value!r}")
        if cfg.verify_reads is not None and not isinstance(cfg.verify_reads,
                                                           bool):
            raise TypeError(f"verify_reads must be a bool, "
                            f"got {cfg.verify_reads!r}")
        deadline = cfg.retry_deadline
        if deadline is not None and (isinstance(deadline, bool)
                                     or not isinstance(deadline, (int, float))
                                     or deadline < 0):
            raise ValueError(f"retry_deadline must be a number >= 0 "
                             f"(seconds), got {deadline!r}")
        if cfg.trace_path is not None and not isinstance(cfg.trace_path,
                                                         str):
            raise TypeError("trace_path must be a str (JSONL sink path)")
        ring = cfg.trace_ring_events
        if ring is not None and (not isinstance(ring, int) or ring < 0):
            raise ValueError(f"trace_ring_events must be an int >= 0, "
                             f"got {ring!r}")
        workers = cfg.server_workers
        if workers is not None and (not isinstance(workers, int)
                                    or isinstance(workers, bool)
                                    or workers < 1):
            raise ValueError(f"server_workers must be an int >= 1, "
                             f"got {workers!r}")
        return cfg

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def build_detector(cfg: DedupConfig) -> Any:
    return registry.get_detector(cfg.detector)(**cfg.detector_args)


def build_chunker(cfg: DedupConfig) -> Any:
    return registry.get_chunker(cfg.chunker)(**cfg.chunker_args)


def build_backend(cfg: DedupConfig) -> Any:
    factory = registry.get_backend(cfg.backend)
    args = dict(cfg.backend_args)
    wanted = {kwarg: getattr(cfg, name)
              for name, kwarg in _BACKEND_KNOBS.items()
              if getattr(cfg, name) is not None and kwarg not in args}
    if wanted:
        # forward only to factories that declare the knob; backends with
        # no decode cache / reader pool (memory) legitimately skip them.
        # A factory whose signature cannot be inspected gets an explicit
        # error instead of a silently ignored knob — pass backend_args
        # directly there.
        try:
            params = inspect.signature(factory).parameters
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"serving knobs {sorted(wanted)} are set but backend "
                f"{cfg.backend!r} has an uninspectable factory signature; "
                "pass them via backend_args instead") from e
        args.update({k: v for k, v in wanted.items() if k in params})
    return factory(**args)


def build_policy(cfg: DedupConfig) -> Any:
    return registry.get_policy(cfg.policy)(**cfg.policy_args)


def build_store(cfg: DedupConfig) -> DedupStore:
    """Resolve every component through the registry and assemble the store."""
    return DedupStore(build_detector(cfg), build_chunker(cfg),
                      backend=build_backend(cfg), policy=build_policy(cfg),
                      trace_path=cfg.trace_path,
                      trace_ring_events=cfg.trace_ring_events)


def build_server(cfg: DedupConfig, store: DedupStore | None = None):
    """One-call multi-tenant deployment (DESIGN.md §15): ``build_store``
    plus a ``DedupServer`` over it, sized by ``server_workers`` with
    ``tenant_args`` as the default per-tenant limits. Pass an existing
    ``store`` to front one that is already serving."""
    from repro.api.serve import DedupServer, TenantConfig
    if store is None:
        store = build_store(cfg)
    kwargs = dict(cfg.server_args)
    if cfg.server_workers is not None and "workers" not in kwargs:
        kwargs["workers"] = cfg.server_workers
    if cfg.tenant_args and "default_tenant" not in kwargs:
        kwargs["default_tenant"] = TenantConfig(**cfg.tenant_args)
    return DedupServer(store, **kwargs)
