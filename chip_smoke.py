"""One-chip smoke run of the CARD store's ingest and restore path.

Builds a store through ``api.build_store`` at the CARD detector's
defaults (Pallas kernels on, exact index, fused ingest; features k=32
m=64 n=2; context model d=50 trained 300 steps; FastCDC 16 KiB average
chunks) over a file backend. It fits the model on the first of
``--versions`` nightly SQL-dump backups of ``--size-mb`` MiB each (made
from ``--seed``), ingests every version, restores every handle and a few
byte ranges, and checks each result by SHA-256. Then it checks the chip
against a plain reference: versions 0-1 through the same config on the
host's CPU device with the jnp path (``use_kernel=False``), and the
per-chunk numpy oracle over version 1's chunks.

Everything runs in this one process, which starts no other: a process
that has touched JAX holds the chip. Where JAX finds no TPU the script
exits nonzero and prints no result. Otherwise the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and any failed check exits nonzero without it.

    python chip_smoke.py [--size-mb 128] [--versions 4] [--seed 1234]
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import hashlib
import json
import os
import sys
import tempfile
import time

# libtpu writes its logs under /tmp unless told otherwise; this run keeps
# to its checkout, HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro import api  # noqa: E402
from repro.core import context_model, features, hashing, similarity  # noqa: E402
from repro.data import workloads  # noqa: E402
from repro.kernels import ingest, ops  # noqa: E402

# Agreement with the host reference, set from what a v5e chip showed at
# the defaults (features 1.8e-7 off, 98.3% of bases the same, DCR equal
# to 5 digits) with room for other sizes and seeds:
FEATURE_ATOL = 1e-6      # initial features vs the numpy oracle, max |diff|
BASE_AGREEMENT = 0.97    # share of new chunks given the same base
DCR_MARGIN = 0.005       # relative DCR difference over versions 0-1
RANGE_READS = 8


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(*parts) -> None:
    print(*parts, flush=True)


def sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


class CompileClock:
    """Seconds JAX spent tracing, lowering, and compiling or loading from
    the persistent cache, and the cache's lookups, hits and writes."""

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
    CACHE = {"/jax/compilation_cache/compile_requests_use_cache": "lookups",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self) -> None:
        self.split = dict.fromkeys(self.STAGES.values(), 0.0)
        self.cache = dict.fromkeys(self.CACHE.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def seconds(self) -> float:
        return sum(self.split.values())

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event in self.STAGES:
            self.split[self.STAGES[event]] += duration_secs

    def _event(self, event: str, **_) -> None:
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def summary(self) -> str:
        sp, c = self.split, self.cache
        return (f"compile {self.seconds:.3f} s (trace {sp['trace']:.3f}, "
                f"lower {sp['lower']:.3f}, compile or cache load "
                f"{sp['compile']:.3f}); persistent cache {c['lookups']} "
                f"lookups, {c['hits']} hits, {c['writes']} writes")


@contextlib.contextmanager
def watch_device_stages(seen: dict):
    """Record the platforms of what the scan, extract, projection and
    ``sim_topk`` programs return, and count resemblance queries."""
    def spy(fn, stage):
        @functools.wraps(fn)
        def run(*a, **kw):
            out = fn(*a, **kw)
            seen.setdefault(stage, set()).update(
                d.platform for leaf in jax.tree.leaves(out)
                for d in leaf.devices())
            seen[stage + "_calls"] = seen.get(stage + "_calls", 0) + 1
            return out
        return run

    targets = [(ingest, "_scan_fused", "scan"),
               (ingest, "_extract_fused", "extract"),
               (context_model, "_project", "projection"),
               (ops, "sim_topk", "sim_topk")]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in targets]
    for mod, name, stage in targets:
        setattr(mod, name, spy(getattr(mod, name), stage))
    query = similarity.CosineIndex.query

    def counted(self, features):
        seen["queries"] = seen.get("queries", 0) + 1
        return query(self, features)

    similarity.CosineIndex.query = counted
    try:
        yield
    finally:
        similarity.CosineIndex.query = query
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def record_bases(store) -> list:
    """Keep (base ids, is_new) of every scored stream of ``store``."""
    det = store.detector
    score = det.score
    out = []

    def spy(feats, batch):
        res = score(feats, batch)
        out.append((res.base_ids.copy(), batch.is_new.copy()))
        return res

    det.score = spy
    return out


def shingle_ids_np(sub: np.ndarray, n: int) -> np.ndarray:
    """numpy twin of ``features.shingle_ids`` (uint32 arithmetic wraps)."""
    k = sub.shape[-1]
    pows = [np.uint32(pow(int(features.SHINGLE_Q), t, 1 << 32))
            for t in range(n + 1)]
    out = []
    for r in range(1, n + 1):
        acc = sub[:, :k - r].astype(np.uint32)
        for t in range(1, r + 1):
            acc += sub[:, t:k - r + t] * pows[t]
        out.append(acc)
    return np.concatenate(out, axis=-1)


def unique_mask_np(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """numpy twin of ``features.unique_mask``."""
    s = np.sort(ids, axis=-1)
    first = np.ones(s.shape, bool)
    first[:, 1:] = s[:, 1:] != s[:, :-1]
    return s, first


def card_config(path: str, **detector_args):
    return api.DedupConfig.from_dict({
        "detector": "card", "detector_args": detector_args,
        "backend": "file", "backend_args": {"path": path}})


def ingest_all(store, versions, clock, label):
    """Ingest each version in its own session; log its report."""
    handles = []
    for i, v in enumerate(versions):
        c0, t0 = clock.seconds, time.perf_counter()
        with store.open_stream() as s:
            s.write(v)
        r = s.report
        wall = time.perf_counter() - t0
        handles.append(r.handle)
        log(f"{label} ingest v{i}: {r.bytes_in} B in {wall:.3f} s "
            f"(compile {clock.seconds - c0:.3f} s) | chunk "
            f"{r.chunk_seconds:.3f} extract {r.extract_seconds:.3f} score "
            f"{r.score_seconds:.3f} observe {r.observe_seconds:.3f} delta "
            f"{r.delta_seconds:.3f} store {r.store_seconds:.3f} s | "
            f"chunks {r.chunks} dup {r.dup_chunks} delta {r.delta_chunks} "
            f"raw {r.raw_chunks} | dcr {r.dcr:.4f}")
    return handles


def check_restores(store, versions, handles, seed):
    t0 = time.perf_counter()
    for i, (h, v) in enumerate(zip(handles, versions)):
        check(sha(store.restore(h)) == sha(v), f"restore of v{i} differs")
    full = time.perf_counter() - t0
    rng = np.random.Generator(np.random.PCG64(seed))
    t0 = time.perf_counter()
    for _ in range(RANGE_READS):
        i = int(rng.integers(len(versions)))
        v = versions[i]
        length = int(rng.integers(1, 1 << 20))
        off = int(rng.integers(0, max(1, len(v) - length)))
        got = store.restore_range(handles[i], off, length)
        check(sha(got) == sha(v[off:off + length]),
              f"restore_range(v{i}, {off}, {length}) differs")
    log(f"restore: {len(versions)} versions byte-identical by SHA-256 in "
        f"{full:.3f} s; {RANGE_READS} restore_range reads identical in "
        f"{time.perf_counter() - t0:.3f} s")


def compare_stage_outputs(store, stream):
    """Version 1's chunks. The gear scan and the integer stages (sub-chunk
    LSH, shingle ids, unique mask; run on the chip as their own jitted
    program) must equal numpy bit for bit. The initial features of the
    store's own fused extract program, which holds those stages, must
    agree with the unfused per-chunk path on the host CPU within
    FEATURE_ATOL. Also reports how far the chip's projection is from a
    float64 host projection with the same weights."""
    det = store.detector
    fcfg, lfloor = det.feat_cfg, store.cfg.max_size
    chunks, scan = api.chunk_with(store.cfg, stream)
    datas = [c.data for c in chunks]
    offs = np.asarray([c.offset for c in chunks], np.int32)
    lens = np.asarray([c.length for c in chunks], np.int32)
    host = hashing.gear_hashes_np(np.frombuffer(stream, np.uint8))
    check(np.array_equal(scan.asnumpy(), host),
          "chip gear scan differs from the numpy scan")

    @functools.partial(jax.jit, static_argnames=("lmax",))
    def integer_stages(h, o, n, *, lmax):
        sub = ingest.stream_subchunk_maxgear(h, o, n, k=fcfg.k, lmax=lmax)
        return (sub,) + features.unique_mask(features.shingle_ids(sub,
                                                                  fcfg.n))

    lmax = features.bucket_pow2(int(lens.max()), lfloor)
    chip_int = [np.asarray(x) for x in integer_stages(
        scan.device, jnp.asarray(offs), jnp.asarray(lens), lmax=lmax)]
    chip_feats = det.extractor(datas, scan, offs, lmax_floor=lfloor)
    chip_proj = det.model.transform(chip_feats)
    sub = features.batch_subchunk_lsh_np(datas, fcfg, host, offs)
    ids, mask = unique_mask_np(shingle_ids_np(sub, fcfg.n))
    for name, got, want in zip(("sub-chunk LSH", "shingle ids",
                                "unique mask"), chip_int, (sub, ids, mask)):
        check(np.array_equal(got, want),
              f"chip {name} differs from the numpy oracle")
    with jax.default_device(jax.devices("cpu")[0]):
        oracle = features.FeatureExtractor(fcfg, use_kernel=False,
                                           fused=False)(datas, host, offs)
    feat_err = float(np.abs(chip_feats - oracle).max())
    u = np.asarray(det.model._u_pinv, np.float64)
    ref = (2 * det.model_cfg.k) * (chip_feats.astype(np.float64) @ u)
    ref /= np.linalg.norm(ref, axis=-1, keepdims=True) + 1e-12
    proj_err = float(np.abs(chip_proj - ref).max())
    log(f"stages on v1 ({len(chunks)} chunks): gear scan, and sub-chunk "
        f"LSH, shingle ids, unique mask as their own chip program, "
        f"bit-identical to numpy; initial features of the store's fused "
        f"extract max |chip - host per-chunk path| {feat_err:.3e} (limit "
        f"{FEATURE_ATOL:g}); projection max |chip - float64 host| "
        f"{proj_err:.3e}")
    check(feat_err <= FEATURE_ATOL,
          f"initial features differ by {feat_err:.3e} > {FEATURE_ATOL:g}")


def run(args, clock: CompileClock, platform: str) -> None:
    t0 = time.perf_counter()
    versions = workloads.make_workload("sql_dump", workloads.WorkloadConfig(
        base_size=args.size_mb << 20, versions=args.versions,
        seed=args.seed))
    log(f"data: sql_dump, {args.versions} versions of "
        f"{[len(v) for v in versions]} B (seed {args.seed}) made in "
        f"{time.perf_counter() - t0:.3f} s")
    seen: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        store = api.build_store(card_config(os.path.join(tmp, "chip")))
        chip_bases = record_bases(store)
        with watch_device_stages(seen):
            c0, t0 = clock.seconds, time.perf_counter()
            store.fit(versions[:1])
            log(f"fit on v0: {time.perf_counter() - t0:.3f} s "
                f"(compile {clock.seconds - c0:.3f} s)")
            handles = ingest_all(store, versions, clock, "chip")
        check_restores(store, versions, handles, args.seed)

        for stage in ("scan", "extract", "projection", "sim_topk"):
            log(f"placement: {stage} output on {sorted(seen.get(stage, []))}"
                f" ({seen.get(stage + '_calls', 0)} calls)")
            check(seen.get(stage) == {platform},
                  f"{stage} output not on the {platform}: {seen.get(stage)}")
        kernel = seen.get("sim_topk_calls", 0)
        log(f"resemblance queries: {kernel} on the sim_topk kernel path, "
            f"{seen.get('queries', 0) - kernel} on the host path")
        check(kernel >= 1, "no query took the sim_topk kernel path")

        compare_stage_outputs(store, versions[1])
        stats = jax.devices()[0].memory_stats() or {}
        log(f"device memory: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")

        t0 = time.perf_counter()
        with jax.default_device(jax.devices("cpu")[0]):
            ref = api.build_store(card_config(os.path.join(tmp, "ref"),
                                              use_kernel=False))
            ref_bases = record_bases(ref)
            ref.fit(versions[:1])
            ingest_all(ref, versions[:2], clock, "reference (cpu)")
        log(f"reference run: {time.perf_counter() - t0:.3f} s")

        same = total = 0
        for (cb, new), (rb, rnew) in zip(chip_bases, ref_bases):
            check(np.array_equal(new, rnew), "chunking differs from reference")
            same += int((cb[new] == rb[new]).sum())
            total += int(new.sum())
        agree = same / max(total, 1)

        def dcr(reports):
            return (sum(r.bytes_in for r in reports)
                    / max(1, sum(r.bytes_stored for r in reports)))
        chip_dcr = dcr(store.reports[:2])
        ref_dcr = dcr(ref.reports)
        rel = abs(chip_dcr - ref_dcr) / ref_dcr
        log(f"reference: same base for {same}/{total} new chunks "
            f"({agree:.4f}, limit {BASE_AGREEMENT}); DCR over v0-v1 chip "
            f"{chip_dcr:.4f} vs reference {ref_dcr:.4f} (relative "
            f"{rel:.2e}, limit {DCR_MARGIN})")
        check(agree >= BASE_AGREEMENT, f"base agreement {agree:.4f}")
        check(rel <= DCR_MARGIN, f"DCR differs by {rel:.2e}")
        ref.close()
        store.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size-mb", type=int, default=128,
                    help="MiB per SQL-dump version")
    ap.add_argument("--versions", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args()
    if args.versions < 2 or args.size_mb < 1:
        ap.error("needs --versions >= 2 and --size-mb >= 1")

    cache = api.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x {len(devices)} "
        f"(jax {jax.__version__}); compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        run(args, clock, dev.platform)
    except CheckFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.3f} s, of which "
        f"{clock.summary()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
