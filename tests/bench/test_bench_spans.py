"""The readers of the program's own spans: their arithmetic on a
synthetic run, ``idle_unnamed_share.ingest`` on a trace recorded on a
TPU v5e (``python -m bench.record_spans``), silence for a program that
reports no such fields, and the program's compile count against the
benchmark's own ``CompileClock``."""
import os
import random
import types

import pytest

from bench import drive, instrument
from bench import run as bench_run
from bench import trace

FIXTURE = os.path.join(bench_run.BENCH, "fixtures",
                       "ingest_trace_spans.json.gz")
BIG = 1_700_000_000_000_000_000       # program clock minus trace clock


def run_of(commits=(), events=None):
    run = drive.Run(cell="sql_backup.ingest", config={}, traffic={},
                    seed=1, seconds=1.0, trace=True, started=0.0)
    run.commits = list(commits)
    run.events = events
    return run


def report(**kw):
    base = dict(bytes_in=2 << 20, dedup_seconds=0.004, search_seconds=0.01,
                base_read_seconds=0.02, base_reads=40, base_read_hits=10,
                spans=())
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name, field", [
    ("dedup_ms_per_MiB", "dedup_seconds"),
    ("search_ms_per_MiB", "search_seconds"),
    ("base_read_ms_per_MiB", "base_read_seconds")])
def test_stage_readers_are_ms_per_MiB(name, field):
    read = bench_run.reader(name)
    run = run_of([(report(), 1.0), (report(**{field: 0.03}), 1.0)])
    want = 1000.0 * (getattr(report(), field) + 0.03) / 4.0
    assert read(run) == pytest.approx(want)
    # a program that does not time the stage: silent, never 0
    bare = types.SimpleNamespace(bytes_in=1 << 20)
    assert read(run_of([(bare, 1.0)])) is None
    assert read(run_of()) is None


def test_base_read_note_gives_lookups_and_own_share():
    run = run_of([(report(), 1.0), (report(base_reads=60,
                                           base_read_hits=40), 1.0)])
    bench_run.reader("base_read_ms_per_MiB")(run)
    assert "100 base lookups, 50.00% from the commit's own chunks" \
        in run.notes[-1]


def ev(name, start, dur, plane="/host:CPU", line="python"):
    return (plane, line, name, start, dur)


def synthetic():
    """A 1000 ns window, device busy [100, 200) and [600, 700); one
    commit's spans, and the benchmark's anchors opening where four of
    them do, on the trace clock."""
    spans = [("ingest", 10, 940), ("ingest.chunk", 15, 285),
             ("ingest.extract", 310, 90), ("ingest.score", 410, 90),
             ("ingest.search", 415, 65), ("ingest.delta", 520, 360),
             ("ingest.observe", 900, 40)]
    anchors = {"ingest.chunk": "bench.chunk",
               "ingest.extract": "bench.extract",
               "ingest.score": "bench.score",
               "ingest.observe": "bench.observe"}
    events = [ev(trace.WINDOW_SPAN, 0, 1000),
              ev("fusion", 100, 100, "/device:TPU:0", trace.OPS_LINE),
              ev("fusion", 600, 100, "/device:TPU:0", trace.OPS_LINE)]
    events += [ev(anchors[op], t, 5) for op, t, _ in spans if op in anchors]
    rep = report(spans=tuple((op, t + BIG, d / 1e9) for op, t, d in spans))
    return rep, events


def test_idle_unnamed_share_arithmetic():
    read = bench_run.reader("idle_unnamed_share.ingest")
    rep, events = synthetic()
    run = run_of([(rep, 1.0)], events)
    # idle 800 ns: 10 + 50 ns under no span (before the commit's root
    # and after it); the rest named down to the innermost span
    assert read(run) == pytest.approx(100.0 * 60 / 800)
    assert read.__globals__["split"](run) == pytest.approx({
        "other": 60e-9, "repro.ingest": 75e-9, "repro.ingest.chunk": 185e-9,
        "repro.ingest.extract": 90e-9, "repro.ingest.score": 25e-9,
        "repro.ingest.search": 65e-9, "repro.ingest.delta": 260e-9,
        "repro.ingest.observe": 40e-9})


def test_idle_unnamed_share_is_silent_when_it_cannot_place_spans():
    read = bench_run.reader("idle_unnamed_share.ingest")
    rep, events = synthetic()
    # an anchor missing, anchors that disagree, no spans, no trace
    assert read(run_of([(rep, 1.0)], [e for e in events
                                      if e[2] != "bench.score"])) is None
    skewed = [e[:3] + (e[3] + 2_000_000,) + e[4:]
              if e[2] == "bench.observe" else e for e in events]
    assert read(run_of([(rep, 1.0)], skewed)) is None
    assert read(run_of([(report(), 1.0)], events)) is None
    bare = types.SimpleNamespace(bytes_in=1 << 20)
    assert read(run_of([(bare, 1.0)], events)) is None
    assert read(run_of([(rep, 1.0)], None)) is None


def test_recorded_chip_trace_with_program_spans():
    """On the chip trace the program's spans name more of the idle time
    than the benchmark's spans do, and the split adds up."""
    from bench import record_spans
    events = trace.load(FIXTURE)
    assert trace.device_planes(events), "recorded on a TPU"
    theirs = [e for e in events if e[0] != record_spans.PLANE]
    ours = [e for e in events if e[0] == record_spans.PLANE]
    roots = sorted(e[3] for e in ours if e[2] == "repro.ingest")
    assert roots
    commits = []
    for i, t in enumerate(roots):
        end = roots[i + 1] if i + 1 < len(roots) else float("inf")
        commits.append((report(spans=tuple(
            (e[2][len("repro."):], e[3], e[4] / 1e9) for e in ours
            if t <= e[3] < end)), 1.0))
    run = run_of(commits, theirs)
    read = bench_run.reader("idle_unnamed_share.ingest")
    share = read(run)
    busy, window = trace.busy_seconds(theirs)
    gaps = trace.idle_gaps(theirs)
    other = 100.0 * gaps.get("other", 0.0) / sum(gaps.values())
    assert share is not None and 0 <= share < other
    by_span = read.__globals__["split"](run)
    assert sum(by_span.values()) == pytest.approx(window - busy, rel=1e-6)
    assert {"repro.ingest.chunk", "repro.ingest.delta"} <= set(by_span)


def test_commit_counts_its_compiles_as_the_benchmark_does(tmp_path):
    from repro import api
    cfg = api.DedupConfig.from_dict({
        "detector": "card",
        "detector_args": {"use_kernel": False, "model": {"steps": 5}},
        "chunker_args": {"avg_size": 2048},
        "backend": "file", "backend_args": {"path": str(tmp_path / "s")}})
    store = api.build_store(cfg)
    rnd = random.Random(3)
    store.fit([rnd.randbytes(64 << 10)])
    clock = instrument.CompileClock()
    with instrument.uncached():
        # a stream in a larger bucket than the fit's: new shapes compile
        report = drive.commit(store, rnd.randbytes(600 << 10))
    store.close()
    assert report.compiles > 0
    assert report.compiles == clock.traces
    assert report.compile_seconds == pytest.approx(clock.seconds)
    snap = store.metrics().snapshot()
    assert snap["repro_jax_compiles_total"]["samples"][0]["value"] \
        == report.compiles
