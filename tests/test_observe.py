"""Observability layer (DESIGN.md §12): metrics registry correctness
(per-thread shards, torn-free snapshots, derived views), the Prometheus
and JSON exporters plus the strict round-trip parser, lock wait-time
histograms under forced writer contention, the IoTelemetry explicit-fold
contract for pooled executors, trace spans over real ingest/restore/GC
paths, the fault-retry metrics of the object-store backend, and the
zero-division guards in benchmarks/common."""
import gc
import json
import os
import threading
import time

import pytest

from repro import api
from repro.api import observe
from repro.api.concurrency import IoTelemetry, RWLock
from repro.api.observe import (MetricsRegistry, Tracer,
                               parse_prometheus_text)


# ---------------------------------------------------------------------------
# registry basics


def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.counter("repro_t_ops_total", "ops", labels={"op": "get"}).inc()
    reg.counter("repro_t_ops_total", "ops", labels={"op": "get"}).inc(4)
    reg.counter("repro_t_ops_total", "ops", labels={"op": "put"}).inc(2)
    reg.gauge("repro_t_depth", "queue depth").set(7)
    h = reg.histogram("repro_t_lat_seconds", "latency",
                      bounds=observe.SECONDS_BUCKETS)
    for v in (1e-6, 0.001, 0.5, 100.0):
        h.observe(v)
    snap = reg.snapshot()
    c = snap["repro_t_ops_total"]
    assert c["type"] == "counter"
    by_label = {tuple(sorted(s["labels"].items())): s["value"]
                for s in c["samples"]}
    assert by_label[(("op", "get"),)] == 5
    assert by_label[(("op", "put"),)] == 2
    assert snap["repro_t_depth"]["samples"][0]["value"] == 7
    hist = snap["repro_t_lat_seconds"]["samples"][0]
    assert hist["count"] == 4
    assert hist["sum"] == pytest.approx(100.501001)
    assert sum(n for _, n in hist["buckets"]) == hist["count"]


def test_histogram_bucket_placement_and_overflow():
    reg = MetricsRegistry()
    bounds = observe.log2_bounds(0, 3)          # 1, 2, 4, 8
    h = reg.histogram("repro_t_w", "", bounds=bounds)
    h.observe(1.0)      # le=1 bucket (bisect_left: boundary inclusive)
    h.observe(3.0)      # le=4
    h.observe(999.0)    # +Inf overflow
    sample = reg.snapshot()["repro_t_w"]["samples"][0]
    got = dict(sample["buckets"])
    assert got[1.0] == 1 and got[4.0] == 1
    assert sample["count"] == 3                 # +Inf implied by count


def test_kind_and_bounds_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("repro_t_x_total", "")
    with pytest.raises(ValueError):
        reg.gauge("repro_t_x_total", "")
    reg.histogram("repro_t_h", "", bounds=observe.COUNT_BUCKETS)
    with pytest.raises(ValueError):
        reg.histogram("repro_t_h", "", bounds=observe.BYTES_BUCKETS)


def test_derived_view_and_callback():
    reg = MetricsRegistry()
    state = {"n": 0}

    def export():
        reg.counter("repro_t_view_total", "view").set_total(state["n"])
    reg.register_callback(export)
    state["n"] = 41
    # a native inc on the same series adds on top of the exported view
    reg.counter("repro_t_view_total", "view").inc()
    [s] = reg.snapshot()["repro_t_view_total"]["samples"]
    assert s["value"] == 42
    state["n"] = 100
    [s] = reg.snapshot()["repro_t_view_total"]["samples"]
    assert s["value"] == 101


# ---------------------------------------------------------------------------
# concurrency: exact totals, no torn reads


def test_concurrent_counters_exact():
    reg = MetricsRegistry()
    threads_n, per_thread = 8, 10_000

    def worker():
        c = reg.counter("repro_t_hammer_total", "")
        for _ in range(per_thread):
            c.inc()
        reg.fold_current()

    ts = [threading.Thread(target=worker) for _ in range(threads_n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    [s] = reg.snapshot()["repro_t_hammer_total"]["samples"]
    assert s["value"] == threads_n * per_thread


def test_snapshot_while_hammering_is_consistent():
    """A reader snapshotting mid-hammer must never see a torn histogram
    (count != bucket sum) and counter/histogram totals must be
    monotonic across snapshots."""
    reg = MetricsRegistry()
    stop = threading.Event()

    def hammer():
        c = reg.counter("repro_t_mono_total", "")
        h = reg.histogram("repro_t_mono_seconds", "",
                          bounds=observe.SECONDS_BUCKETS)
        while not stop.is_set():
            for _ in range(100):
                c.inc()
                h.observe(0.001)

    ts = [threading.Thread(target=hammer, daemon=True) for _ in range(4)]
    for t in ts:
        t.start()
    last_c = last_n = -1.0
    for _ in range(50):
        snap = reg.snapshot()
        fam = snap.get("repro_t_mono_seconds")
        if fam:
            [s] = fam["samples"]
            assert sum(n for _, n in s["buckets"]) == s["count"]
            assert s["count"] >= last_n
            last_n = s["count"]
        cfam = snap.get("repro_t_mono_total")
        if cfam:
            [s] = cfam["samples"]
            assert s["value"] >= last_c
            last_c = s["value"]
    stop.set()
    for t in ts:
        t.join(60)
    assert last_c > 0 and last_n > 0


def test_lock_wait_histogram_under_writer_contention():
    """A reader blocked behind a held write lock lands in a visible
    wait-time bucket; uncontended acquires land near zero."""
    reg = MetricsRegistry()

    def obs(side, seconds):
        reg.histogram("repro_lock_wait_seconds", "",
                      labels={"side": side},
                      bounds=observe.SECONDS_BUCKETS).observe(seconds)

    lock = RWLock(observer=obs)
    with lock.read():       # uncontended
        pass
    lock.acquire_write()
    waited = []

    def reader():
        t0 = time.perf_counter()
        with lock.read():
            waited.append(time.perf_counter() - t0)

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.05)
    lock.release_write()
    t.join(60)
    reg.fold_current()
    samples = {s["labels"]["side"]: s for s in
               reg.snapshot()["repro_lock_wait_seconds"]["samples"]}
    assert samples["read"]["count"] == 2
    assert samples["write"]["count"] == 1
    # the blocked read's wait dominates the histogram sum
    assert samples["read"]["sum"] >= 0.9 * waited[0] >= 0.02


# ---------------------------------------------------------------------------
# IoTelemetry explicit-fold contract (satellite: pooled executors)


def test_iotelemetry_fold_current_exact_and_idempotent():
    tel = IoTelemetry()

    def task():
        c = tel.local()
        c.bytes_read += 100
        c.requests += 1
        tel.fold_current()
        tel.fold_current()              # idempotent
        c2 = tel.local()                # fresh record after the fold
        assert c2 is not c
        c2.bytes_read += 11
        tel.fold_current()

    t = threading.Thread(target=task)
    t.start()
    t.join(60)
    gc.collect()                        # the GC fold must not double-count
    assert tel.total("bytes_read") == 111
    assert tel.total("requests") == 1


def test_iotelemetry_scoped_folds_on_exit():
    tel = IoTelemetry()

    def task():
        with tel.scoped() as c:
            c.bytes_read += 7
        # folded immediately: a pool thread that never exits still
        # published its counters
        assert tel.total("bytes_read") == 7

    t = threading.Thread(target=task)
    t.start()
    t.join(60)
    assert tel.total("bytes_read") == 7


def test_registry_fold_current_from_pool_thread():
    reg = MetricsRegistry()
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=1) as ex:
        def task():
            reg.counter("repro_t_pool_total", "").inc(5)
            reg.fold_current()
        ex.submit(task).result(60)
        # pool thread still alive, but the fold already published
        [s] = reg.snapshot()["repro_t_pool_total"]["samples"]
        assert s["value"] == 5


# ---------------------------------------------------------------------------
# exporters + strict parser


def test_prometheus_label_escaping_roundtrip():
    reg = MetricsRegistry()
    nasty = 'a\\b"c\nd'
    reg.counter("repro_t_esc_total", 'help with "quotes"\nand newline',
                labels={"path": nasty}).inc(3)
    text = reg.to_prometheus()
    assert '\\\\b\\"c\\nd' in text
    parsed = parse_prometheus_text(text)
    [(name, labels, value)] = [s for s in parsed["samples"]
                               if s[0] == "repro_t_esc_total"]
    assert labels == {"path": nasty} and value == 3.0


def test_prometheus_histogram_exposition_shape():
    reg = MetricsRegistry()
    h = reg.histogram("repro_t_sh_seconds", "x",
                      bounds=observe.log2_bounds(0, 2))
    h.observe(1.5)
    h.observe(10.0)
    text = reg.to_prometheus()
    assert "# TYPE repro_t_sh_seconds histogram" in text
    parsed = parse_prometheus_text(text)
    buckets = {l["le"]: v for n, l, v in parsed["samples"]
               if n == "repro_t_sh_seconds_bucket"}
    assert buckets["2"] == 1.0          # cumulative
    assert buckets["4"] == 1.0
    assert buckets["+Inf"] == 2.0
    [count] = [v for n, _, v in parsed["samples"]
               if n == "repro_t_sh_seconds_count"]
    assert count == 2.0


@pytest.mark.parametrize("bad", [
    "repro_x_total{le=} 1",             # malformed label
    "repro_x_total 1",                  # sample without a TYPE line
    "# TYPE repro_x_total counter\n9bad_name 1",
    '# TYPE repro_x_total counter\nrepro_x_total{a="b} 1',
])
def test_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad)


def test_json_snapshot_loads_clean():
    reg = MetricsRegistry()
    reg.counter("repro_t_j_total", "").inc()
    reg.histogram("repro_t_j_seconds", "",
                  bounds=observe.SECONDS_BUCKETS).observe(0.5)
    snap = json.loads(reg.to_json())
    assert snap["repro_t_j_total"]["type"] == "counter"
    [s] = snap["repro_t_j_seconds"]["samples"]
    assert s["count"] == 1 == sum(n for _, n in s["buckets"])


# ---------------------------------------------------------------------------
# tracer


def test_tracer_ring_bound_and_sink_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = Tracer(ring_events=4, path=path)
    for i in range(10):
        tr.record("op", 0.001, i=i)
    ring = tr.events()
    assert len(ring) == 4 and [e["i"] for e in ring] == [6, 7, 8, 9]
    tr.close()
    with open(path) as f:
        sink = [json.loads(line) for line in f if line.strip()]
    assert len(sink) == 10              # sink keeps everything
    assert all(e["op"] == "op" and "tid" in e and "s" in e for e in sink)


def test_tracer_span_parent_links():
    tr = Tracer(ring_events=16)
    with tr.span("parent", phase="x") as labels:
        labels["extra"] = 1
    parent_id = tr.events()[-1]["id"]
    child = tr.record("parent.child", 0.5, parent=parent_id)
    events = {e["op"]: e for e in tr.events()}
    assert events["parent"]["extra"] == 1
    assert events["parent.child"]["parent"] == parent_id
    assert child != parent_id
    assert tr.ops() == {"parent": 1, "parent.child": 1}


# ---------------------------------------------------------------------------
# config knobs


def test_config_trace_knobs_roundtrip(tmp_path):
    cfg = api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "trace_path": str(tmp_path / "t.jsonl"),
        "trace_ring_events": 64})
    assert cfg.trace_ring_events == 64
    with pytest.raises(TypeError):
        api.DedupConfig.from_dict({"detector": "dedup-only",
                                   "trace_path": 7})
    with pytest.raises(ValueError):
        api.DedupConfig.from_dict({"detector": "dedup-only",
                                   "trace_ring_events": -1})


# ---------------------------------------------------------------------------
# acceptance: instrumented store paths (the ISSUE's criterion)


def _traced_store(tmp_path, **extra):
    cfg = api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "chunker_args": {"avg_size": 4096},
        "backend": "file",
        "backend_args": {"path": str(tmp_path / "containers")},
        "trace_ring_events": 1024,
        **extra})
    return api.build_store(cfg)


def test_ingest_metrics_and_spans(tmp_path):
    store = _traced_store(tmp_path)
    data = os.urandom(64 << 10)
    with store.open_stream() as s:
        s.write(data)
    parsed = parse_prometheus_text(store.metrics().to_prometheus())
    assert parsed["types"]["repro_ingest_stage_seconds"] == "histogram"
    assert parsed["types"]["repro_ingest_commits_total"] == "counter"
    assert parsed["types"]["repro_store_dcr"] == "gauge"
    stages = {l["stage"] for n, l, v in parsed["samples"]
              if n == "repro_ingest_stage_seconds_count" and v >= 1}
    assert stages == {"chunk", "dedup", "extract", "score", "search",
                      "observe", "base_read", "delta", "store"}
    assert parsed["types"]["repro_jax_compiles_total"] == "counter"
    ops = store.observe.tracer.ops()
    assert ops["ingest"] == 1
    for stage in ("chunk", "dedup", "extract", "score", "observe",
                  "delta", "store"):
        assert ops[f"ingest.{stage}"] == 1, stage
    store.close()


def test_restore_metrics_cache_hits_and_spans(tmp_path):
    store = _traced_store(tmp_path)
    data = os.urandom(64 << 10)
    with store.open_stream() as s:
        s.write(data)
    h = s.report.handle
    assert store.restore(h) == data     # cold
    assert store.restore(h) == data     # warm: decode-cache hits
    parsed = parse_prometheus_text(store.metrics().to_prometheus())
    assert parsed["types"]["repro_restore_stage_seconds"] == "histogram"
    assert parsed["types"]["repro_restore_requests"] == "histogram"
    by = {(n, tuple(sorted(l.items()))): v
          for n, l, v in parsed["samples"]}
    assert by[("repro_restore_ops_total", (("surface", "full"),))] == 2
    assert by[("repro_reader_cache_lookups_total",
               (("outcome", "hit"),))] > 0
    stages = {l["stage"] for n, l, v in parsed["samples"]
              if n == "repro_restore_stage_seconds_count" and v >= 1}
    assert stages == {"total", "read", "decode", "join"}
    ops = store.observe.tracer.ops()
    for op in ("restore", "restore.plan", "restore.fetch",
               "restore.join"):
        assert ops[op] == 2, op
    assert "restore.prefetch" not in ops
    restores = [e for e in store.observe.tracer.events()
                if e["op"] == "restore"]
    assert restores[-1]["hit_ratio"] > 0        # warm pass hit the cache
    assert restores[-1]["surface"] == "full"
    store.close()


def test_restore_surfaces_labelled(tmp_path):
    store = _traced_store(tmp_path)
    data = os.urandom(48 << 10)
    with store.open_stream() as s:
        s.write(data)
    h = s.report.handle
    assert b"".join(store.restore_iter(h)) == data
    assert store.restore_range(h, 1000, 2000) == data[1000:3000]
    by = {tuple(sorted(l.items())): v for n, l, v in
          parse_prometheus_text(store.metrics().to_prometheus())["samples"]
          if n == "repro_restore_ops_total"}
    assert by[(("surface", "iter"),)] == 1
    assert by[(("surface", "range"),)] == 1
    store.close()


def test_gc_metrics_and_spans(tmp_path):
    store = _traced_store(tmp_path)
    for _ in range(2):
        with store.open_stream() as s:
            s.write(os.urandom(48 << 10))
    store.delete(s.report.handle)
    store.collect()
    store.compact()
    parsed = parse_prometheus_text(store.metrics().to_prometheus())
    phases = {l["phase"] for n, l, v in parsed["samples"]
              if n == "repro_gc_phase_seconds_count" and v >= 1}
    assert {"delete", "collect", "compact", "compact.sizing",
            "compact.rewrite"} <= phases
    by = {n: v for n, l, v in parsed["samples"] if not l}
    assert by["repro_gc_freed_bytes_total"] > 0
    ops = store.observe.tracer.ops()
    for op in ("gc.delete", "gc.collect", "gc.compact"):
        assert ops.get(op, 0) >= 1, op
    store.close()


def test_store_views_match_stats(tmp_path):
    store = _traced_store(tmp_path)
    with store.open_stream() as s:
        s.write(os.urandom(64 << 10))
    stats = store.stats
    by = {(n, tuple(sorted(l.items()))): v for n, l, v in
          parse_prometheus_text(store.metrics().to_prometheus())["samples"]}
    assert by[("repro_ingest_bytes_total", (("dir", "in"),))] \
        == stats.bytes_in
    assert by[("repro_ingest_bytes_total", (("dir", "stored"),))] \
        == stats.bytes_stored
    assert by[("repro_store_dcr", ())] == pytest.approx(stats.dcr)
    store.close()


def test_tracing_disabled_by_default(tmp_path):
    cfg = api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "chunker_args": {"avg_size": 4096}})
    store = api.build_store(cfg)
    assert store.observe.tracer is None
    with store.open_stream() as s:
        s.write(os.urandom(16 << 10))
    assert store.restore(s.report.handle)
    # metrics still collected even with tracing off
    assert "repro_ingest_commits_total" in store.metrics().snapshot()
    store.close()


def test_objectstore_retry_metrics(tmp_path):
    cfg = api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "chunker_args": {"avg_size": 4096},
        "backend": "objectstore",
        "backend_args": {"path": str(tmp_path / "obj")},
        "trace_ring_events": 512})
    store = api.build_store(cfg)
    data = os.urandom(64 << 10)
    with store.open_stream() as s:
        s.write(data)
    h = s.report.handle
    store.close()

    store = api.build_store(api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "chunker_args": {"avg_size": 4096},
        "backend": "objectstore",
        "backend_args": {"path": str(tmp_path / "obj"),
                         # fault every other GET ordinal: each call's
                         # first attempt fails, its retry succeeds.
                         # Ordinal 1 fires during the reopen _scan
                         # (before observability is bound), later ones
                         # during the restore — which is the point: the
                         # bound metrics must catch those
                         "fault_hook":
                             api.FaultSchedule({"get":
                                                list(range(1, 64, 2))}),
                         "retry_backoff": 0.001},
        "trace_ring_events": 512}))
    assert store.restore(h) == data
    assert store.backend.retries >= 1
    parsed = parse_prometheus_text(store.metrics().to_prometheus())
    by = {(n, tuple(sorted(l.items()))): v
          for n, l, v in parsed["samples"]}
    assert by[("repro_objstore_retries_total", ())] == \
        store.backend.retries
    assert by[("repro_objstore_backoff_seconds_total", ())] > 0
    assert by[("repro_objstore_request_seconds_count",
               (("op", "get"),))] >= 1
    assert by[("repro_objstore_get_bytes_count", ())] >= 1
    retry_spans = [e for e in store.observe.tracer.events()
                   if e["op"] == "objstore.retry"]
    assert retry_spans and retry_spans[0]["client_op"] == "get"
    store.close()


def test_reader_run_shape_histograms(tmp_path):
    store = _traced_store(tmp_path)
    with store.open_stream() as s:
        s.write(os.urandom(96 << 10))
    h = s.report.handle
    store.close()
    store = _traced_store(tmp_path)     # cold decode cache: real reads
    assert store.restore(h)
    parsed = parse_prometheus_text(store.metrics().to_prometheus())
    assert parsed["types"]["repro_reader_run_bytes"] == "histogram"
    assert parsed["types"]["repro_reader_run_extents"] == "histogram"
    by = {n: v for n, l, v in parsed["samples"] if n.endswith("_count")}
    assert by["repro_reader_run_bytes_count"] >= 1
    assert by["repro_reader_run_extents_count"] >= 1
    store.close()


def test_trace_sink_written_through_store(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    store = _traced_store(tmp_path, trace_path=trace)
    with store.open_stream() as s:
        s.write(os.urandom(32 << 10))
    assert store.restore(s.report.handle)
    n_ring = len(store.observe.tracer.events())
    store.close()
    with open(trace) as f:
        sink = [json.loads(line) for line in f if line.strip()]
    assert len(sink) == n_ring >= 2
    ops = {e["op"] for e in sink}
    assert "ingest" in ops and "restore" in ops


def test_observe_cli_dump(tmp_path, capsys):
    trace = str(tmp_path / "trace.jsonl")
    tr = Tracer(ring_events=8, path=trace)
    tr.record("alpha", 0.25, k=1)
    tr.record("alpha", 0.75)
    tr.record("beta", 0.1)
    tr.close()
    assert observe.main(["dump", trace]) == 0
    out = capsys.readouterr().out
    assert "# 3 spans" in out and "alpha" in out and "beta" in out


# ---------------------------------------------------------------------------
# satellite: zero-division guards in the bench helpers


def test_bench_helpers_zero_division_guards():
    from benchmarks import common
    assert common.mbps(0, 0.0) == 0.0
    assert common.mbps(1 << 20, 0.0) == 0.0
    assert common.mbps(1 << 20, 1.0) == 1.0
    assert common.ratio(5, 0) == 0.0
    assert common.ratio(6, 3) == 2.0
    assert common.fmt_ratio(5, 0) == "n/a"
    assert common.fmt_ratio(1, 3, places=3) == "0.333"


# ---------------------------------------------------------------------------
# spans on the profiler's clock (DESIGN.md §12.3)

INGEST_PASSES = ("ingest.chunk", "ingest.dedup", "ingest.extract",
                 "ingest.score", "ingest.delta", "ingest.store",
                 "ingest.observe")


def _card_store(tmp_path, **extra):
    cfg = api.DedupConfig.from_dict({
        "detector": "card",
        "detector_args": {"use_kernel": False, "model": {"steps": 5}},
        "chunker_args": {"avg_size": 2048},
        "backend": "file",
        "backend_args": {"path": str(tmp_path / "card")},
        **extra})
    return api.build_store(cfg)


def _versions(n=2, size=256 << 10):
    import random
    rnd = random.Random(7)
    v = bytearray(rnd.randbytes(size))
    out = [bytes(v)]
    for _ in range(n - 1):
        at = rnd.randrange(size - 64)
        v[at:at + 64] = rnd.randbytes(64)
        out.append(bytes(v))
    return out


def _inside(child, parent, slack=1e-6):
    return (child["t0"] >= parent["t0"] - slack
            and child["t0"] + child["s"] <= parent["t0"] + parent["s"]
            + slack)


def test_commit_spans_nest_in_order(tmp_path):
    store = _card_store(tmp_path, trace_ring_events=1024)
    vs = _versions()
    store.fit(vs[:1])
    for v in vs:
        with store.open_stream() as s:
            s.write(v)
    r = s.report
    events = store.observe.tracer.events()
    root = [e for e in events if e["op"] == "ingest"][-1]
    mine = [e for e in events if e["id"] > root["id"]
            and e["op"].startswith("ingest.")]
    by = {e["op"]: e for e in mine}
    assert sorted(by) == sorted(INGEST_PASSES + ("ingest.search",))
    assert all(sum(e["op"] == op for e in mine) == 1 for op in by)
    passes = [by[op] for op in INGEST_PASSES]
    for e in passes:
        assert e["parent"] == root["id"] and _inside(e, root), e["op"]
    for a, b in zip(passes, passes[1:]):       # in order, no overlap
        assert a["t0"] + a["s"] <= b["t0"] + 1e-6, (a["op"], b["op"])
    search = by["ingest.search"]
    assert search["parent"] == by["ingest.score"]["id"]
    assert _inside(search, by["ingest.score"])
    # the report's stage fields are the spans' durations
    assert r.chunk_seconds == pytest.approx(by["ingest.chunk"]["s"])
    assert r.dedup_seconds == pytest.approx(by["ingest.dedup"]["s"])
    assert r.search_seconds == pytest.approx(search["s"])
    assert r.commit_seconds == pytest.approx(root["s"])
    assert 0 < r.delta_seconds + r.base_read_seconds \
        <= by["ingest.delta"]["s"] + 1e-6
    assert r.base_reads >= r.base_read_hits >= 0 and r.delta_chunks
    assert [op for op, _, _ in r.spans] == ["ingest", "ingest.chunk",
        "ingest.dedup", "ingest.extract", "ingest.score", "ingest.search",
        "ingest.delta", "ingest.store", "ingest.observe"]
    assert abs(r.spans[0][1] / 1e9 - root["t0"]) < 1e-6
    assert root["handle"] == r.handle and root["bytes_in"] == r.bytes_in
    store.close()


def test_restore_spans_have_real_starts(tmp_path):
    store = _traced_store(tmp_path)
    data = os.urandom(96 << 10)
    with store.open_stream() as s:
        s.write(data)
    h = s.report.handle
    tr = store.observe.tracer
    for call, want in ((lambda: store.restore(h), data),
                       (lambda: store.restore_range(h, 500, 9000),
                        data[500:9500]),
                       (lambda: b"".join(store.restore_iter(h, 4)), data)):
        n0 = len(tr.events())
        assert call() == want
        new = tr.events()[n0:]
        root = new[-1]
        assert root["op"] == "restore" and root["parent"] is None
        kids = new[:-1]
        assert {e["op"] for e in kids} <= {"restore.plan", "restore.fetch",
                                           "restore.join"}
        assert {"restore.plan", "restore.fetch"} <= {e["op"] for e in kids}
        for e in kids:
            assert e["parent"] == root["id"] and _inside(e, root), e["op"]
        starts = [e["t0"] for e in kids if e["op"] != "restore.fetch"]
        assert starts == sorted(starts) and len(set(starts)) == len(starts)
        fetch = [e for e in kids if e["op"] == "restore.fetch"]
        assert sum(e["cache_hits"] + e["cache_misses"] for e in fetch) \
            == root["cache_hits"] + root["cache_misses"]
        if root["surface"] != "iter":
            join = [e for e in kids if e["op"] == "restore.join"]
            assert len(join) == 1
            assert store.last_restore.join_seconds == pytest.approx(
                join[0]["s"])
        assert store.last_restore.seconds == pytest.approx(root["s"])
    assert "restore.prefetch" not in tr.ops()
    assert store.stats.restore_join_seconds > 0
    store.close()


def test_spans_share_the_profiler_clock(tmp_path):
    """Each ring event starts where the profiler's ``repro.<op>`` event
    does: the trace's host times are relative to its session start."""
    import glob

    import jax
    from jax.profiler import ProfileData
    store = _traced_store(tmp_path)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with store.open_stream() as s:
            s.write(os.urandom(64 << 10))
        assert store.restore(s.report.handle)
    finally:
        jax.profiler.stop_trace()
    ring = store.observe.tracer.events()
    store.close()
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    start, spans = None, {}
    for plane in ProfileData.from_file(path).planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                start = int(v)
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(observe.PROFILER_PREFIX):
                    spans.setdefault(ev.name, []).append(int(ev.start_ns))
    assert start is not None
    assert len(ring) >= 9
    for op in {e["op"] for e in ring}:
        ours = sorted(e["t0"] for e in ring if e["op"] == op)
        theirs = sorted((start + t) / 1e9 for t in spans["repro." + op])
        assert len(ours) == len(theirs), op
        for a, b in zip(ours, theirs):
            assert abs(a - b) < 5e-4, (op, a - b)


def test_store_without_tracer_books_nothing(tmp_path):
    traced = _traced_store(tmp_path)
    before = len(traced.observe.tracer.events())
    cfg = api.DedupConfig.from_dict({"detector": "dedup-only",
                                     "chunker_args": {"avg_size": 4096}})
    plain = api.build_store(cfg)
    assert plain.observe.tracer is None
    data = os.urandom(64 << 10)
    with traced.observe.tracer.span("outer"):
        with plain.open_stream() as s:      # a root with no tracer
            s.write(data)
        assert plain.restore_range(s.report.handle, 10, 100) == \
            data[10:110]
    r = s.report
    assert r.commit_seconds > 0 and r.chunk_seconds > 0
    assert r.dedup_seconds > 0 and r.store_seconds > 0
    assert r.commit_seconds >= r.chunk_seconds + r.dedup_seconds \
        + r.store_seconds
    assert [op for op, _, _ in r.spans][:3] == ["ingest", "ingest.chunk",
                                                "ingest.dedup"]
    assert plain.last_restore.seconds > 0
    assert plain.last_restore.join_seconds >= 0
    # the untraced store's spans book into no ring, not even the
    # traced store's span they ran under
    assert [e["op"] for e in traced.observe.tracer.events()[before:]] \
        == ["outer"]
    plain.close()
    traced.close()


def test_span_stack_survives_errors():
    tr = Tracer(ring_events=16)
    with pytest.raises(KeyError):
        with observe.Span("top", tr):
            with observe.Span("top.inner"):
                raise KeyError("x")
    with observe.Span("after", tr):
        pass
    events = {e["op"]: e for e in tr.events()}
    assert events["top.inner"]["error"] == "KeyError"
    assert events["top.inner"]["parent"] == events["top"]["id"]
    assert events["after"]["parent"] is None
