"""What every traffic op shares: the run's record, the loader that finds
ops, generators and metric readers by file name, and the store's
set-up.

A mix file (``bench/traffic/<mix>.json``) names its ``op`` and gives its
parameters. The op is ``bench/ops/<op>.py``, whose ``run(run, tmp)`` does
set-up, measured window and the comparison with the references. A
configuration names its ``generator``, ``bench/generators/<name>.py``,
whose ``versions(seed, **generator_args)`` yields the stream's versions.
Each metric is ``bench/metrics/<metric>.py``, whose ``read(run)`` takes
its number from the ``Run``. A later cell, mix, configuration or metric
is a new file beside these, and no file here changes for it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import os

import jax
import numpy as np

from bench import instrument
from bench import trace as tr

BENCH = os.path.dirname(os.path.abspath(__file__))


@functools.cache
def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded by its file name."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    started: float                      # perf_counter at process start
    setup_s: float = 0.0
    window_s: float = 0.0
    commits: list = dataclasses.field(default_factory=list)   # (report, s)
    requests: list = dataclasses.field(default_factory=list)  # (s, bytes)
    restore: dict = dataclasses.field(default_factory=dict)   # stats deltas
    compiles_in_window: int = 0
    compile_s_in_window: float = 0.0
    attempted: int = 0
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)    # name: (v, lim)
    notes: list = dataclasses.field(default_factory=list)     # stderr lines
    memory_peak_bytes: int | None = None
    events: list | None = None          # reduced profiler trace
    topk_shapes: list = dataclasses.field(default_factory=list)  # (B, N, D)
    device_kind: str = ""
    limits: dict = dataclasses.field(default_factory=dict)
    clock: instrument.CompileClock | None = None
    # what the window produced, kept for the comparison (and the control)
    produced: dict = dataclasses.field(default_factory=dict)

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared, with its limit: the run is correct only if
        every such value is at most its limit."""
        self.checks[name] = (value, limit)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for v, lim in
                                         self.checks.values())


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed) & (2**64 - 1),
                                                *salt]))


def versions(config: dict, seed: int):
    return plugin("generators", config["generator"]).versions(
        seed, **config["generator_args"])


def build_store(config: dict, path: str):
    from repro import api
    cfg = dict(config["store"])
    cfg["backend_args"] = dict(cfg.get("backend_args", {}), path=path)
    return api.build_store(api.DedupConfig.from_dict(cfg))


def commit(store, data: bytes):
    with store.open_stream() as s:
        s.write(data)
    return s.report


@contextlib.contextmanager
def profiled(run: Run, log_dir: str):
    """Profile the block when the run is traced; keep the reduced trace."""
    if not run.trace:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        run.events = tr.load_profile(log_dir)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
