"""Multi-tenant serving front end (DESIGN.md §15): RWLock timeouts, the
shared retry helper, request deadlines, admission control / typed
shedding, quota accounting (the hypothesis property lives in
test_serve_property.py), namespace
isolation, per-tenant caches, and the circuit breaker's
open → half-open → closed cycle with its metric families."""
import random
import threading
import time

import pytest

from repro import api
from repro.api.concurrency import (DeadlineExceededError, LockTimeout, RWLock,
                                   check_deadline, deadline_scope,
                                   remaining_time)
from repro.api.faults import RetryBudgetExceeded, TransientError, with_retries
from repro.api.serve import (CircuitBreaker, CircuitOpenError, DedupServer,
                             OverloadError, QuotaExceededError, TenantConfig)

JOIN_S = 10.0


# --- RWLock timeouts ----------------------------------------------------------

def test_rwlock_read_timeout_under_writer():
    lock = RWLock()
    lock.acquire_write()
    t0 = time.perf_counter()
    with pytest.raises(LockTimeout) as ei:
        lock.acquire_read(timeout=0.05)
    assert 0.04 <= time.perf_counter() - t0 < JOIN_S
    assert ei.value.side == "read"
    lock.release_write()
    with lock.read(timeout=1.0):        # lock usable afterwards
        pass


def test_rwlock_write_timeout_under_reader():
    lock = RWLock()
    lock.acquire_read()
    with pytest.raises(LockTimeout) as ei:
        lock.acquire_write(timeout=0.05)
    assert ei.value.side == "write"
    lock.release_read()
    with lock.write(timeout=1.0):
        pass


def test_rwlock_writer_timeout_unblocks_waiting_readers():
    # writer preference holds readers off while a writer waits; when the
    # writer *times out* it must wake them, or they hang forever on a
    # wait() nobody will ever notify
    lock = RWLock()
    lock.acquire_read()
    timed_out = threading.Event()

    def writer():
        try:
            lock.acquire_write(timeout=1.0)
        except LockTimeout:
            timed_out.set()

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    deadline = time.perf_counter() + JOIN_S
    while not lock._writers_waiting:    # writer is now waiting
        assert time.perf_counter() < deadline
        time.sleep(0.001)
    got = threading.Event()

    def reader():
        lock.acquire_read()             # parked behind the waiting writer
        got.set()
        lock.release_read()

    r = threading.Thread(target=reader, daemon=True)
    r.start()
    time.sleep(0.03)
    assert not got.is_set()             # preference: reader held off
    assert timed_out.wait(JOIN_S)
    assert got.wait(JOIN_S)             # woken by the timed-out writer
    w.join(JOIN_S)
    r.join(JOIN_S)
    lock.release_read()


def test_rwlock_timeout_reports_wait_to_observer():
    waits = []
    lock = RWLock(observer=lambda side, s: waits.append((side, s)))
    lock.acquire_write()
    with pytest.raises(LockTimeout):
        lock.acquire_read(timeout=0.02)
    assert [side for side, _ in waits] == ["write", "read"]
    assert waits[-1][1] >= 0.02         # the failed wait is the signal


# --- faults.with_retries ------------------------------------------------------

def test_with_retries_absorbs_faults_then_succeeds():
    calls, sleeps, attempts, backoffs = [], [], [], []

    def fn(tag):
        calls.append(tag)
        if len(calls) < 3:
            raise TransientError(503, "flaky")
        return f"ok:{tag}"

    out = with_retries(fn, ("x",), max_retries=5, backoff=0.01,
                       rng=random.Random(7), sleep=sleeps.append,
                       on_attempt=lambda s, ok: attempts.append(ok),
                       on_backoff=lambda d, a: backoffs.append(a))
    assert out == "ok:x" and calls == ["x", "x", "x"]
    assert attempts == [False, False, True]
    assert backoffs == [1, 2]
    # decorrelated jitter bounds: uniform(backoff, min(cap, 3*prev))
    assert len(sleeps) == 2
    assert all(0.01 <= d <= 0.01 * (1 << 5) for d in sleeps)


def test_with_retries_attempt_budget_reraises_last():
    def fn():
        raise TransientError(429, "always")

    with pytest.raises(TransientError) as ei:
        with_retries(fn, max_retries=2, backoff=0.001,
                     sleep=lambda d: None)
    assert not isinstance(ei.value, RetryBudgetExceeded)
    assert ei.value.status == 429


def test_with_retries_deadline_budget():
    def fn():
        raise TransientError()

    with pytest.raises(RetryBudgetExceeded) as ei:
        with_retries(fn, max_retries=100, backoff=0.01, deadline=0.05,
                     sleep=lambda d: None)
    assert ei.value.attempts >= 1
    assert ei.value.slept <= 0.05


def test_with_retries_non_transient_propagates_immediately():
    calls = []

    def fn():
        calls.append(1)
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        with_retries(fn, sleep=lambda d: None)
    assert len(calls) == 1


# --- deadline scopes ----------------------------------------------------------

def test_deadline_scope_nested_keeps_tighter_budget():
    assert remaining_time() is None
    with deadline_scope(30.0):
        with deadline_scope(0.01):
            assert remaining_time() <= 0.01
        assert remaining_time() > 1.0   # outer budget restored
        with deadline_scope(60.0):      # looser inner scope: ignored
            assert remaining_time() <= 30.0
    assert remaining_time() is None


def test_check_deadline_raises_once_expired():
    with deadline_scope(0.0):
        with pytest.raises(DeadlineExceededError) as ei:
            check_deadline("restore")
    assert ei.value.op == "restore"
    check_deadline("unbounded")         # no scope: never raises


def test_deadline_scope_is_thread_local():
    seen = []

    def other():
        seen.append(remaining_time())

    with deadline_scope(0.001):
        th = threading.Thread(target=other)
        th.start()
        th.join(JOIN_S)
    assert seen == [None]


# --- server fixtures ----------------------------------------------------------

def _obj_server(tmp_path, *, latency=0.0, fault_hook=None, max_retries=2,
                retry_deadline=None, tenant=None, workers=4,
                max_object_bytes=None, breaker=None, avg_chunk=None):
    backend_args = {"path": str(tmp_path / "obj"), "latency": latency,
                    "fault_hook": fault_hook, "max_retries": max_retries,
                    "cache_bytes": 1}     # ~no decode cache: reads hit I/O
    if retry_deadline is not None:
        backend_args["retry_deadline"] = retry_deadline
    if max_object_bytes is not None:
        backend_args["max_object_bytes"] = max_object_bytes
    cfg = {"detector": "dedup-only", "backend": "objectstore",
           "backend_args": backend_args}
    if avg_chunk is not None:
        cfg["chunker_args"] = {"avg_size": avg_chunk}
    store = api.build_store(api.DedupConfig.from_dict(cfg))
    return DedupServer(store, workers=workers, breaker=breaker,
                       default_tenant=tenant or TenantConfig())


def _payload(n, seed=0):
    return random.Random(seed).randbytes(n)


# --- directed server behavior -------------------------------------------------

def test_namespace_isolation_and_roundtrip(tmp_path):
    srv = _obj_server(tmp_path)
    try:
        data_a, data_b = b"alpha" * 4000, b"bravo" * 4000
        ra = srv.ingest("a", data_a)
        rb = srv.ingest("b", data_b)
        assert srv.restore("a", ra.handle) == data_a
        assert srv.restore_range("b", rb.handle, 10, 25) == data_b[10:35]
        with pytest.raises(KeyError):
            srv.restore("a", rb.handle)     # foreign handle == missing
        with pytest.raises(KeyError):
            srv.delete("b", ra.handle)
        assert srv.delete("a", ra.handle) >= 0
        with pytest.raises(KeyError):
            srv.restore("a", ra.handle)     # gone after delete
    finally:
        srv.close(close_store=True)


def test_quota_admission_and_settlement(tmp_path):
    srv = _obj_server(tmp_path,
                      tenant=TenantConfig(quota_bytes=64 << 10))
    try:
        rep = srv.ingest("t", b"q" * 4000)
        stats = srv.tenant_stats("t")
        # the charge settles to the store's actual, not the raw upper bound
        assert stats["bytes_stored"] == rep.bytes_stored <= 4000
        assert stats["reserved"] == 0
        # a duplicate stream dedupes: its settled charge is far below raw
        rep2 = srv.ingest("t", b"q" * 4000)
        assert rep2.bytes_stored < 4000
        assert (srv.tenant_stats("t")["bytes_stored"]
                == rep.bytes_stored + rep2.bytes_stored)
        with pytest.raises(QuotaExceededError):
            srv.ingest("t", _payload(80 << 10))
        assert srv.tenant_stats("t")["reserved"] == 0   # rejected: uncharged
        assert srv.tenant_stats("t")["shed"] == {"quota": 1}
        # freeing the streams returns their quota headroom
        srv.delete("t", rep.handle)
        srv.delete("t", rep2.handle)
        assert srv.tenant_stats("t")["bytes_stored"] == 0
    finally:
        srv.close(close_store=True)


def test_admission_sheds_overload_when_queue_full(tmp_path):
    gate = threading.Event()
    armed = threading.Event()

    def hook(op, key, n):
        if armed.is_set() and op == "get":
            gate.wait(JOIN_S)
        return None

    srv = _obj_server(tmp_path, fault_hook=hook,
                      tenant=TenantConfig(max_inflight=1, max_queue=1))
    try:
        data = _payload(30000, seed=3)
        rep = srv.ingest("t", data)
        armed.set()                     # every GET now parks on the gate
        f1 = srv.submit("t", "restore", rep.handle)
        f2 = srv.submit("t", "restore", rep.handle)
        with pytest.raises(OverloadError) as ei:    # queue (1+1) is full
            srv.submit("t", "restore", rep.handle)
        assert ei.value.pending == 2 and ei.value.limit == 2
        assert srv.tenant_stats("t")["shed"] == {"overload": 1}
        armed.clear()
        gate.set()                      # drain: admitted work completes
        assert f1.result(JOIN_S) == data
        assert f2.result(JOIN_S) == data
        snap = srv.store.metrics().to_prometheus()
        assert 'repro_tenant_shed_total{reason="overload",tenant="t"} 1' \
            in snap
    finally:
        gate.set()
        srv.close(close_store=True)


def test_deadline_expiry_mid_restore_is_typed_and_prompt(tmp_path):
    # per-GET latency makes the restore span many slow reads; the §15.3
    # probes must shed it mid-plan with the typed error, long before the
    # full restore would have finished — and never corrupt later serving
    srv = _obj_server(tmp_path, latency=0.03, max_object_bytes=8192,
                      avg_chunk=2048)
    try:
        data = _payload(256 << 10, seed=5)      # ~30 objects => many GETs
        rep = srv.ingest("t", data)
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededError):
            srv.restore("t", rep.handle, timeout=0.06)
        assert time.perf_counter() - t0 < 2.0   # shed, not served late
        assert srv.tenant_stats("t")["shed"] == {"deadline": 1}
        assert srv.restore("t", rep.handle) == data     # store unharmed
    finally:
        srv.close(close_store=True)


def test_deadline_expiry_sheds_commit_before_writes(tmp_path):
    srv = _obj_server(tmp_path, latency=0.02)
    try:
        before = srv.store.stats.bytes_stored
        with pytest.raises(DeadlineExceededError):
            srv.ingest("t", _payload(256 << 10, seed=7), timeout=1e-4)
        assert srv.store.stats.bytes_stored == before   # nothing committed
        assert srv.tenant_stats("t")["bytes_stored"] == 0
        assert srv.tenant_stats("t")["reserved"] == 0
    finally:
        srv.close(close_store=True)


def test_store_restore_respects_ambient_deadline_scope(tmp_path):
    # the deadline machinery works below the server too: a bare store
    # call inside an expired scope sheds instead of running
    srv = _obj_server(tmp_path, latency=0.02, max_object_bytes=8192)
    try:
        rep = srv.ingest("t", _payload(96 << 10, seed=9))
        with deadline_scope(0.01):
            with pytest.raises(DeadlineExceededError):
                srv.store.restore(rep.handle)
    finally:
        srv.close(close_store=True)


def test_tenant_cache_serves_repeat_restores_without_backend_io(tmp_path):
    srv = _obj_server(tmp_path,
                      tenant=TenantConfig(cache_bytes=4 << 20))
    try:
        data = _payload(40000, seed=11)
        rep = srv.ingest("t", data)
        assert srv.restore("t", rep.handle) == data     # cold: hits backend
        gets = srv.store.backend.client.op_counts.get("get", 0)
        assert srv.restore("t", rep.handle) == data     # warm: tenant cache
        assert srv.store.backend.client.op_counts.get("get", 0) == gets
        stats = srv.tenant_stats("t")
        assert stats["cache_hits"] == 1 and stats["cache_misses"] == 1
        srv.delete("t", rep.handle)     # delete invalidates the cache entry
        assert srv.tenant_stats("t")["cache_hits"] == 1
        with pytest.raises(KeyError):
            srv.restore("t", rep.handle)
    finally:
        srv.close(close_store=True)


def test_breaker_opens_gates_writes_and_recovers(tmp_path):
    storm = threading.Event()

    def hook(op, key, n):
        if storm.is_set() and op == "get":
            return TransientError(503, f"storm {op} #{n}")
        return None

    breaker = CircuitBreaker(fail_threshold=2, window_seconds=5.0,
                             cooldown_seconds=0.05, probe_successes=1)
    srv = _obj_server(tmp_path, fault_hook=hook, max_retries=0,
                      breaker=breaker)
    try:
        data = b"stormy" * 3000
        rep = srv.ingest("t", data)
        storm.set()
        for _ in range(2):
            with pytest.raises(TransientError):
                srv.restore("t", rep.handle)
        assert breaker.state() == "open"
        with pytest.raises(CircuitOpenError):       # read-only degradation
            srv.ingest("t", b"rejected")
        with pytest.raises(CircuitOpenError):
            srv.delete("t", rep.handle)
        time.sleep(0.06)                # cooldown elapses lazily
        storm.clear()
        assert srv.restore("t", rep.handle) == data     # half-open probe
        assert breaker.state() == "closed"
        assert breaker.transitions == {"closed": 1, "half_open": 1,
                                       "open": 1}
        srv.ingest("t", b"writable again")          # write gate reopened
        snap = srv.store.metrics().to_prometheus()
        assert 'repro_server_breaker_transitions_total{to="open"} 1' in snap
        assert ('repro_server_breaker_transitions_total{to="half_open"} 1'
                in snap)
        assert 'repro_server_breaker_transitions_total{to="closed"} 1' in snap
        assert "repro_server_breaker_state 0" in snap
        assert srv.tenant_stats("t")["shed"]["circuit"] == 2
    finally:
        srv.close(close_store=True)


def test_breaker_halfopen_failure_reopens():
    t = [0.0]
    br = CircuitBreaker(fail_threshold=1, cooldown_seconds=10.0,
                        probe_successes=2, clock=lambda: t[0])
    br.record_failure()
    assert br.state() == "open"
    t[0] = 11.0
    assert br.state() == "half_open"
    br.record_failure()                 # failed probe: back to open
    assert br.state() == "open"
    t[0] = 22.0
    assert br.state() == "half_open"
    br.record_success()
    assert br.state() == "half_open"    # needs probe_successes=2
    br.record_success()
    assert br.state() == "closed"
    assert br.transitions["open"] == 2


def test_submit_rejects_unknown_op_and_closed_server(tmp_path):
    srv = _obj_server(tmp_path)
    with pytest.raises(ValueError):
        srv.submit("t", "scrub")
    srv.close(close_store=True)
    with pytest.raises(RuntimeError):
        srv.submit("t", "restore", 0)
    srv.close()                         # idempotent


def test_build_server_from_config(tmp_path):
    cfg = api.DedupConfig.from_dict({
        "detector": "dedup-only",
        "backend": "objectstore",
        "backend_args": {"path": str(tmp_path / "o")},
        "server_workers": 2,
        "tenant_args": {"quota_bytes": 1 << 20, "max_inflight": 3},
    })
    srv = api.build_server(cfg)
    try:
        assert isinstance(srv, DedupServer)
        rep = srv.ingest("t", b"configured" * 100)
        assert srv.restore("t", rep.handle) == b"configured" * 100
        assert srv.tenant_stats("t")["quota_bytes"] == 1 << 20
    finally:
        srv.close(close_store=True)

