"""The trace reduction, the peaks table and the roofline arithmetic, on
synthetic intervals and on a small trace recorded on a TPU v5e."""
import os

import pytest

from bench import run as bench_run
from bench import trace

DEV = "/device:TPU:0"
FIXTURE = os.path.join(bench_run.BENCH, "fixtures", "ingest_trace.json.gz")


def ev(line, name, start, dur, plane=DEV):
    return (plane, line, name, start, dur)


def synthetic():
    """A 100 ns window: device ops at [10, 30) and [20, 40) (union 30 ns)
    and [70, 80); host spans chunk [0, 50), delta [50, 90) with an inner
    store [60, 75)."""
    return [ev("python", trace.WINDOW_SPAN, 0, 100, "/host:CPU"),
            ev("python", "bench.chunk", 0, 50, "/host:CPU"),
            ev("python", "bench.delta", 50, 40, "/host:CPU"),
            ev("python", "bench.store", 60, 15, "/host:CPU"),
            ev(trace.OPS_LINE, "fusion.1", 10, 20),
            ev(trace.OPS_LINE, "fusion.2", 20, 20),
            ev(trace.OPS_LINE, "custom-call", 70, 10),
            ev(trace.MODULES_LINE, "jit_scan(1)", 10, 30),
            ev(trace.MODULES_LINE, "jit_sim_topk(2)", 70, 10)]


def test_union_and_clip():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)]) == [
        (0, 4), (5, 12)]
    assert trace.clip([(0, 4), (5, 12)], 3, 6) == [(3, 4), (5, 6)]


def test_busy_window_and_programs():
    events = synthetic()
    assert trace.windows(events) == [(0, 100)]
    assert trace.busy(events, DEV, [(0, 100)]) == [(10, 40), (70, 80)]
    assert trace.busy(events, DEV, [(0, 20), (35, 75)]) == [
        (10, 20), (35, 40), (70, 75)]
    busy, window = trace.busy_seconds(events)
    assert busy == pytest.approx(40e-9) and window == pytest.approx(1e-7)
    assert trace.program_seconds(events) == pytest.approx(
        {"jit_scan(1)": 30e-9, "jit_sim_topk(2)": 10e-9})
    assert trace.program_calls(events, "jit_sim_topk") == [1e-8]
    assert trace.program_calls(events, "jit_sim_topk", [(0, 50)]) == []


def test_idle_gaps_go_to_the_innermost_span():
    gaps = trace.idle_gaps(synthetic())
    # idle: [0,10) chunk, [40,50) chunk, [50,60) delta, [60,70) store,
    # [80,90) delta, [90,100) no span but the window
    assert gaps == pytest.approx({"bench.chunk": 20e-9, "bench.delta": 20e-9,
                                  "bench.store": 10e-9, "other": 10e-9})
    assert sum(gaps.values()) + 40e-9 == pytest.approx(1e-7)


def test_window_is_required():
    with pytest.raises(ValueError):
        trace.windows(synthetic()[1:])


def test_windows_leave_out_what_lies_between():
    """Two timed commits with untimed work between: [0, 40) and
    [60, 100). Busy 30 + 10 ns of 80."""
    events = [e for e in synthetic() if e[2] != trace.WINDOW_SPAN] + [
        ev("python", trace.WINDOW_SPAN, 0, 40, "/host:CPU"),
        ev("python", trace.WINDOW_SPAN, 60, 40, "/host:CPU")]
    busy, window = trace.busy_seconds(events)
    assert busy == pytest.approx(40e-9) and window == pytest.approx(80e-9)
    gaps = trace.idle_gaps(events)
    assert gaps == pytest.approx({"bench.chunk": 10e-9, "bench.store": 10e-9,
                                  "bench.delta": 10e-9, "other": 10e-9})


def test_peaks_table():
    p = trace.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks("TPU v9 imaginary")


def test_sim_topk_roofline_arithmetic():
    read = bench_run.reader("sim_topk_roofline")
    cost = read.__globals__["cost"]
    flops, nbytes = cost(8, 1024, 50)
    assert flops == 2 * 8 * 1024 * 50
    assert nbytes == 4 * (8 * 50 + 1024 * 50) + 8 * 8
    peak = trace.peaks("TPU v5 lite")
    s, bound = trace.least_seconds(*cost(8192, 65536, 50), peak)
    assert bound == "compute" and s == pytest.approx(
        2 * 8192 * 65536 * 50 / 197e12)
    s, bound = trace.least_seconds(*cost(1, 65536, 50), peak)
    assert bound == "memory"


def test_recorded_chip_trace():
    events = trace.load(FIXTURE)
    planes = trace.device_planes(events)
    assert planes, "the recorded trace holds a TPU plane"
    busy, window = trace.busy_seconds(events)
    assert 0 < busy <= window
    programs = trace.program_seconds(events)
    assert any(name.startswith("jit_sim_topk") for name in programs)
    gaps = trace.idle_gaps(events)
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    assert {"bench.chunk", "bench.delta"} <= set(gaps)
