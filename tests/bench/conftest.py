"""Shared set-up of the benchmark's tests: the repo root on the path and
the cells cut to a size the CPU runs in seconds."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(cell: str, size_mib: float = 1.0, kernel: bool = False,
              check_share: float = 1.0):
    """(spec, config, traffic) of ``cell``, cut to ``size_mib`` versions,
    2 KiB chunks, 10 model steps, and the kernel path only on request."""
    from bench import run as bench_run
    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    c = bench_run.cell_spec(spec, cell)
    config = bench_run.load_json(bench_run.BENCH, "configs",
                                 c["config"] + ".json")
    traffic = bench_run.load_json(bench_run.BENCH, "traffic",
                                  c["traffic"] + ".json")
    config["generator_args"]["size"] = int(size_mib * 2**20)
    det = config["store"]["detector_args"]
    det["use_kernel"] = kernel
    det["model"]["steps"] = 10
    config["store"]["chunker_args"]["avg_size"] = 2048
    if "check_share" in traffic:
        traffic["check_share"] = check_share
    return spec, config, traffic


def run_tiny(cell: str, seed: int = 2**33 + 17, seconds: float = 1.0,
             traced: bool = False, **kw):
    """One run of ``cell`` at the tiny size, below the look for a chip."""
    from bench import run as bench_run
    spec, config, traffic = tiny_cell(cell, **kw)
    return bench_run.execute(cell, seed, seconds, traced, spec=spec,
                             config=config, traffic=traffic,
                             started=time.perf_counter())


@pytest.fixture
def tiny():
    return run_tiny
