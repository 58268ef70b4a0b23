"""Op ``restore``: closed-loop clients reading back stored versions.

Set-up commits ``history_versions`` versions and reopens the store from
its directory, so the window starts with cold caches. ``clients``
closed-loop clients then call ``restore_range`` (``surface: range``: a
version drawn uniformly, a length log-uniform in [``length_min``,
``length_max``], a uniform offset) or ``restore`` (``surface: full``:
whole versions in turn) until ``--seconds`` have passed. A share
``check_share`` of the answers, drawn from the seed, is kept and
compared with the source bytes after the window.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
import time

import jax

from bench import drive, instrument
from bench import trace as tr


def _stats(store) -> dict:
    s = store.stats
    return {"bytes_out": s.restore_bytes_out, "read_s": s.restore_read_seconds,
            "decode_s": s.restore_decode_seconds,
            "hits": s.restore_cache_hits, "misses": s.restore_cache_misses}


def run(run: drive.Run, tmp: str) -> None:
    cfg, mix = run.config, run.traffic
    stream = drive.versions(cfg, run.seed)
    path = os.path.join(tmp, "store")
    store = drive.build_store(cfg, path)
    history = [next(stream) for _ in range(mix["history_versions"])]
    store.fit(history[:1])
    # a traced run holds the last set-up commit, so that the device path
    # the cell's data went through is in the trace
    handles = [drive.commit(store, v).handle for v in history[:-1]]
    with drive.profiled(run, os.path.join(tmp, "trace")), \
            jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.commit"):
            handles.append(drive.commit(store, history[-1]).handle)
        store.close()
        store = drive.build_store(cfg, path)
        run.setup_s = time.perf_counter() - run.started
        before = _stats(store)
        spans = (instrument.spans(store) if run.trace
                 else contextlib.nullcontext())
        with spans:
            kept = _serve(run, store, handles, history)
    after = _stats(store)
    run.restore = {k: after[k] - before[k] for k in after}
    run.memory_peak_bytes = drive.peak_bytes()
    store.close()
    run.produced = {"versions": history, "kept": kept}
    bad = sum(got != history[i][off:off + n] for i, off, n, got in kept)
    run.failed += bad
    run.check("answers_wrong", bad, 0)
    run.check("requests_failed", run.failed - bad, 0)
    run.notes.append(f"compared {len(kept)} of {run.attempted} answers "
                     f"with the source bytes")


def _serve(run: drive.Run, store, handles: list, history: list) -> list:
    mix = run.traffic
    ranged = mix["surface"] == "range"
    lo, hi = math.log(mix.get("length_min", 1)), math.log(
        mix.get("length_max", 1))
    kept, lock = [], threading.Lock()
    t_start = time.perf_counter()
    t_end = t_start + run.seconds
    last = [t_start]

    def client(c: int) -> None:
        rng = drive.rng(run.seed, 100 + c)
        k = c
        while time.perf_counter() < t_end:
            if ranged:
                i = int(rng.integers(len(history)))
                n = int(math.exp(rng.uniform(lo, hi)))
                off = int(rng.integers(0, max(1, len(history[i]) - n)))
            else:
                i, off, n = k % len(history), 0, len(history[k % len(history)])
                k += mix["clients"]
            keep = rng.random() < mix["check_share"]
            t0 = time.perf_counter()
            try:
                got = (store.restore_range(handles[i], off, n) if ranged
                       else store.restore(handles[i]))
            except Exception as e:      # noqa: BLE001  -- counted failed
                got, err = None, e
            t1 = time.perf_counter()
            with lock:
                run.attempted += 1
                last[0] = max(last[0], t1)
                if got is None:
                    run.failed += 1
                    run.notes.append(f"request failed: {err!r}")
                    continue
                run.requests.append((t1 - t0, len(got)))
                if keep:
                    kept.append((i, off, n, got))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(mix["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run.window_s = last[0] - t_start
    return kept
