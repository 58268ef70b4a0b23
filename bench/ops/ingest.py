"""Op ``ingest``: one closed-loop backup client.

Set-up fits the detector on version 0 and commits ``history_versions``
versions. That runs every program of a commit once: the stream scan,
extract and projection buckets, whose shapes recur from commit to
commit, and from the second version on the index search. The window
commits version 1, 2, ... through ``open_stream()`` until the commits
have taken ``--seconds``; each commit is timed whole. Making the next
version and its reference boundaries and features, between commits, is
not timed.

``sim_topk`` is traced on the unpadded index, which grows with every
commit, so each commit compiles it at a shape no commit before had. A
deployment pays that compile at every commit, and the timed commit pays
it here: the window compiles without the persistent cache, as a store
whose index never repeats a size would, so that every run, whatever the
cache holds, does the same work.

After the window every committed version is restored from the store
reopened from its directory, and what the timed path produced is
compared with the plain references: chunk boundaries, initial features,
each ``sim_topk`` answer and each chunk's chosen base.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax
import numpy as np

from bench import drive, instrument, reference
from bench import trace as tr


def run(run: drive.Run, tmp: str) -> None:
    cfg, mix = run.config, run.traffic
    stream = drive.versions(cfg, run.seed)
    chunker = cfg["store"]["chunker_args"]
    feat = cfg["store"]["detector_args"]["feat"]
    store = drive.build_store(cfg, os.path.join(tmp, "store"))
    history = [next(stream) for _ in range(mix["history_versions"])]
    store.fit(history[:1])
    for v in history:
        drive.commit(store, v)
    clock = run.clock

    def prepare():
        """The next version with its reference chunk boundaries and
        initial features: untimed work between commits."""
        v = next(stream)
        h = reference.gear_hashes(v)
        b = reference.fastcdc_bounds(
            h, avg=chunker["avg_size"], min_factor=chunker["min_factor"],
            max_factor=chunker["max_factor"],
            norm_level=chunker["norm_level"])
        f = reference.initial_features(h, b[:-1], np.diff(b), **feat)
        return v, (b, f)

    nxt = prepare()
    run.setup_s = time.perf_counter() - run.started
    captured = instrument.Captured()
    done, refs = [], []
    with instrument.uncached(), \
            drive.profiled(run, os.path.join(tmp, "trace")):
        while True:
            v, b = nxt
            traces0, compile0 = clock.traces, clock.seconds
            spans = (instrument.spans(store) if run.trace
                     else contextlib.nullcontext())
            with spans, instrument.capture(store, captured), \
                    jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                t0 = time.perf_counter()
                report = drive.commit(store, v)
                wall = time.perf_counter() - t0
            run.compiles_in_window += clock.traces - traces0
            run.compile_s_in_window += clock.seconds - compile0
            run.window_s += wall
            run.commits.append((report, wall))
            done.append(v)
            refs.append(b)
            if run.window_s >= run.seconds:
                break
            nxt = prepare()
    run.attempted = len(done)
    run.notes.append(f"set-up: {len(history)} version(s) committed; "
                     f"{run.compiles_in_window} programs traced in the "
                     f"window, {run.compile_s_in_window:.3f} s")
    for r, wall in run.commits:
        run.notes.append(
            f"commit {r.handle}: {r.bytes_in} B in {wall:.3f} s | chunk "
            f"{r.chunk_seconds:.3f} extract {r.extract_seconds:.3f} score "
            f"{r.score_seconds:.3f} observe {r.observe_seconds:.3f} delta "
            f"{r.delta_seconds:.3f} store {r.store_seconds:.3f} | chunks "
            f"{r.chunks} dup {r.dup_chunks} delta {r.delta_chunks} raw "
            f"{r.raw_chunks} | dcr {r.dcr:.4f}")
    run.memory_peak_bytes = drive.peak_bytes()
    dim = store.detector.model_cfg.d
    run.topk_shapes = [(len(c.queries), c.rows, dim) for c in captured.topk
                       if c.arg is not None]
    index = store.detector.index
    handles = [r.handle for r, _ in run.commits]
    run.produced = {"versions": done, "reference": refs,
                    "captured": captured,
                    "index_rows": np.array(index._buf[:len(index)]),
                    "index_ids": np.array(index._ids[:len(index)]),
                    "threshold": cfg["store"]["detector_args"]["threshold"]}
    store.close()
    check(run, tmp, handles)


def check(run: drive.Run, tmp: str, handles: list) -> None:
    """Every version the window committed, restored from the reopened
    store; its chunk boundaries and initial features; every ``sim_topk``
    answer and every chunk's chosen base of the window."""
    p = run.produced
    reopened = drive.build_store(run.config, os.path.join(tmp, "store"))
    bad = 0
    for h, v in zip(handles, p["versions"]):
        try:
            ok = reopened.restore(h) == v
        except Exception as e:      # noqa: BLE001  -- a lost commit
            run.notes.append(f"restore of handle {h} raised {e!r}")
            ok = False
        bad += not ok
    reopened.close()
    run.failed = bad
    run.check("versions_restored_wrong", bad, 0)

    cap = p["captured"]
    bounds_bad, feat_gap = 0, 0.0
    for (b, want), got, f in zip(p["reference"], cap.bounds, cap.features):
        if not np.array_equal(got, b):
            bounds_bad += 1
            continue
        feat_gap = max(feat_gap, float(np.abs(f.astype(np.float64)
                                              - want).max()))
    bounds_bad += len(p["versions"]) - len(cap.bounds)
    run.check("versions_chunked_wrong", bounds_bad, 0)
    run.check("feature_gap", feat_gap, run.limits["feature_gap"])

    gap = 0.0
    for c in cap.topk:
        if c.arg is not None:
            gap = max(gap, reference.top1_gap(c.queries,
                                              p["index_rows"][:c.rows],
                                              c.arg))
    run.check("top1_gap", gap, run.limits["top1_gap"])

    # a commit whose choice went uncaptured reads 2, past any cosine gap
    base = 0.0 if len(cap.choices) == len(p["versions"]) else 2.0
    for c in cap.choices:
        base = max(base, reference.base_gap(
            c.feats, c.ids, c.is_new, p["index_rows"][:c.rows],
            p["index_ids"][:c.rows], c.base_ids, p["threshold"]))
    run.check("base_gap", base, run.limits["base_gap"])
    run.notes.append(f"compared {len(p['versions'])} versions, "
                     f"{sum(len(b) - 1 for b, _ in p['reference'])} "
                     f"chunks, {len(cap.choices)} base choices, "
                     f"{len(run.topk_shapes)} sim_topk calls")
