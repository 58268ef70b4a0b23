"""What the benchmark fastens onto the store from outside.

The program is not edited: these wrap its functions and methods for the
length of a ``with`` block and put the originals back after it.

* ``CompileClock``: JAX's compile events (trace, lower, compile or cache
  load) and the persistent cache's lookups, hits and writes.
* ``uncached``: the persistent compilation cache switched off for a
  block, so that what the block compiles is compiled whatever the cache
  holds.
* ``capture``: what the timed path produced, for the comparison with the
  references after the window: each commit's chunk boundaries, initial
  features and chosen bases (with the projected features and the index
  size they were chosen from), and each ``sim_topk`` call's queries,
  index rows and answer.
* ``spans``: ``jax.profiler.TraceAnnotation`` spans around the store's
  layers, so that the trace names what the host did in each idle gap of
  the device. Used in traced runs only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import numpy as np


class CompileClock:
    """Seconds JAX spent tracing, lowering, and compiling or loading from
    the persistent cache; the cache's lookups, hits and writes; and the
    number of programs traced (one per jit cache miss in this process)."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
    CACHE = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self) -> None:
        self.split = dict.fromkeys(self.STAGES.values(), 0.0)
        self.cache = dict.fromkeys(self.CACHE.values(), 0)
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def seconds(self) -> float:
        return sum(self.split.values())

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event in self.STAGES:
            self.split[self.STAGES[event]] += duration_secs
            if self.STAGES[event] == "trace":
                self.traces += 1

    def _event(self, event: str, **_) -> None:
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def summary(self) -> str:
        sp, c = self.split, self.cache
        return (f"compile {self.seconds:.3f} s (trace {sp['trace']:.3f}, "
                f"lower {sp['lower']:.3f}, compile or cache load "
                f"{sp['compile']:.3f}); {self.traces} programs traced; "
                f"persistent cache {c['lookups']} lookups, {c['hits']} "
                f"hits, {c['writes']} writes")


@contextlib.contextmanager
def uncached():
    """Compile without the persistent cache inside the block."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@contextlib.contextmanager
def patched(targets):
    """Replace ``(owner, name, wrapper_factory)`` attributes for the block."""
    saved = [(owner, name, getattr(owner, name)) for owner, name, _ in targets]
    for owner, name, make in targets:
        setattr(owner, name, make(getattr(owner, name)))
    try:
        yield
    finally:
        for owner, name, fn in reversed(saved):
            setattr(owner, name, fn)


@dataclasses.dataclass
class TopkCall:
    queries: np.ndarray      # [B, D] float32, as the index was asked
    rows: int                # index rows the call searched
    arg: np.ndarray | None = None     # the kernel's best row [B]


@dataclasses.dataclass
class Choice:
    feats: np.ndarray        # [n, D] projected features the score read
    ids: np.ndarray          # [n] chunk ids
    is_new: np.ndarray       # [n] bool
    rows: int                # index rows when the stream was scored
    base_ids: np.ndarray     # [n] the chosen base, -1 for none


@dataclasses.dataclass
class Captured:
    bounds: list = dataclasses.field(default_factory=list)    # [n+1] each
    features: list = dataclasses.field(default_factory=list)  # [n, M] each
    topk: list = dataclasses.field(default_factory=list)      # TopkCall
    choices: list = dataclasses.field(default_factory=list)   # Choice


@contextlib.contextmanager
def capture(store, out: Captured):
    """Record what each commit of ``store`` produced on the timed path."""
    from repro.api import store as store_mod
    from repro.core import similarity
    from repro.kernels import ops

    def chunker(fn):
        @functools.wraps(fn)
        def run(cfg, stream):
            chunks, scan = fn(cfg, stream)
            b = np.empty(len(chunks) + 1, np.int64)
            b[0] = 0
            b[1:] = [c.offset + c.length for c in chunks]
            out.bounds.append(b)
            return chunks, scan
        return run

    def extractor(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            feats = fn(*a, **kw)
            out.features.append(feats)
            return feats
        return run

    asking: list[TopkCall] = []     # the query whose kernel call is due

    def query(fn):
        @functools.wraps(fn)
        def run(index, feats):
            asking.append(TopkCall(np.asarray(feats, np.float32),
                                   len(index)))
            try:
                return fn(index, feats)
            finally:
                out.topk.append(asking.pop())
        return run

    def kernel(fn):
        @functools.wraps(fn)
        def run(q, index):
            score, arg = fn(q, index)
            if asking:          # not a call made outside a query
                asking[-1].arg = np.asarray(arg)
            return score, arg
        return run

    det = store.detector

    def scorer(fn):
        @functools.wraps(fn)
        def run(feats, batch):
            rows = len(det.index)
            result = fn(feats, batch)
            out.choices.append(Choice(
                np.array(feats, np.float32), batch.ids.copy(),
                batch.is_new.copy(), rows, result.base_ids.copy()))
            return result
        return run

    with patched([(store_mod, "chunk_with", chunker),
                  (det, "extractor", extractor),
                  (det, "score", scorer),
                  (similarity.CosineIndex, "query", query),
                  (ops, "sim_topk", kernel)]):
        yield out


def _span(name: str):
    def make(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return run
    return make


@contextlib.contextmanager
def spans(store):
    """``bench.<layer>`` spans around the store's layers: chunking, the
    detector's three stages, each delta encode, the container write, and
    each restore call."""
    from repro.api import store as store_mod
    from repro.core import delta
    from repro.kernels import ops
    det = store.detector
    with patched([(store_mod, "chunk_with", _span("bench.chunk")),
                  (det, "extract", _span("bench.extract")),
                  (det, "score", _span("bench.score")),
                  (det, "observe", _span("bench.observe")),
                  (delta, "encode", _span("bench.delta")),
                  (ops, "sim_topk", _span("bench.sim_topk")),
                  (store.backend, "put_many", _span("bench.store")),
                  (store, "restore", _span("bench.restore")),
                  (store, "restore_range", _span("bench.restore_range"))]):
        yield
