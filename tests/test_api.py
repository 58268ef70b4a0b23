"""repro.api layered surface: registry/config round-trip, staged-detector
compat shim (bit-identical to the v0 interleaved protocol), stream-session
IngestReport accounting, and container-backend restore fidelity."""
import numpy as np
import pytest

from repro import api
from repro.core import baselines, chunking, context_model, features, pipeline, similarity
from repro.data import workloads

CCFG = chunking.ChunkerConfig(avg_size=8192)
AVG = 8192


@pytest.fixture(scope="module")
def versions():
    return workloads.make_workload(
        "sql_dump", workloads.WorkloadConfig(base_size=1 << 20, versions=3))


def _card_direct():
    return pipeline.CARDDetector(
        feat_cfg=features.FeatureConfig(k=32, m=64, n=2),
        model_cfg=context_model.ContextModelConfig(m=64, d=50, steps=60),
        use_kernel=False)


def _card_cfg(extra=None):
    d = {"detector": "card",
         "detector_args": {"feat": {"k": 32, "m": 64, "n": 2},
                           "model": {"m": 64, "d": 50, "steps": 60},
                           "use_kernel": False},
         "chunker_args": {"avg_size": AVG}}
    d.update(extra or {})
    return api.DedupConfig.from_dict(d)


def _stat_tuple(s):
    return (s.bytes_in, s.bytes_stored, s.chunks, s.dup_chunks,
            s.delta_chunks, s.raw_chunks)


def _run_store(store, versions):
    store.fit(versions[:1])
    for v in versions:
        store.ingest(v)
    return store.stats


# --- module docstring quick start --------------------------------------------

def test_api_quickstart_docstring_runs():
    """The repro.api docstring's quick-start snippet must execute verbatim
    (it drifted from the real session API once; never again)."""
    import re
    import textwrap

    match = re.search(r"Quick start:\n\n((?:    .*\n|\n)+)", api.__doc__)
    assert match, "quick-start block missing from repro.api docstring"
    snippet = textwrap.dedent(match.group(1))
    for call in ("build_store", "open_stream", "restore", "delete",
                 "collect", "compact"):
        assert call in snippet
    rng = np.random.default_rng(0)
    namespace = {"first_version":
                 rng.integers(0, 256, 96 * 1024, dtype=np.uint8).tobytes()}
    exec(compile(snippet, "<repro.api quick start>", "exec"), namespace)
    store = namespace["store"]
    assert store.stats.reclaimed_bytes > 0      # the reclaim really happened
    assert store.stats.live_bytes == 0


# --- registry + config construction -----------------------------------------

def test_registry_lists_builtins():
    assert {"card", "finesse", "n-transform", "dedup-only"} <= set(
        api.available_detectors())
    assert {"exact", "banded-lsh"} <= set(api.available_indexes())
    assert "fastcdc" in api.available_chunkers()
    assert {"memory", "file"} <= set(api.available_backends())
    with pytest.raises(KeyError, match="available"):
        api.get_detector("no-such-detector")


def test_config_round_trips_and_rejects_unknown_keys():
    cfg = _card_cfg()
    assert api.DedupConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="unknown"):
        api.DedupConfig.from_dict({"detecter": "card"})


@pytest.mark.parametrize("kind", ["card", "finesse", "n-transform", "dedup-only"])
def test_all_detectors_constructible_via_config(kind, versions):
    cfg = api.DedupConfig.from_dict(
        {"detector": kind,
         "detector_args": {"use_kernel": False} if kind == "card" else {},
         "chunker_args": {"avg_size": AVG}})
    store = api.build_store(cfg)
    stats = _run_store(store, versions[:2])
    assert stats.chunks > 0
    assert store.restore(store.reports[0].handle) == versions[0]


def test_config_path_matches_direct_construction(versions):
    """DedupConfig.from_dict -> build_store gives the same detection output
    as direct constructor calls (the context model is seeded)."""
    direct = pipeline.run_workload(_card_direct(), versions, CCFG)
    built = _run_store(api.build_store(_card_cfg()), versions)
    assert _stat_tuple(direct) == _stat_tuple(built)


def test_index_is_a_config_knob(versions):
    """exact vs banded-LSH selected declaratively; banding stays close."""
    exact = _run_store(api.build_store(_card_cfg()), versions)
    banded_cfg = _card_cfg()
    banded_cfg.detector_args["index"] = "banded-lsh"
    banded = _run_store(api.build_store(banded_cfg), versions)
    assert isinstance(api.build_detector(banded_cfg).index,
                      similarity.BandedLSHIndex)
    assert banded.dcr >= 0.9 * exact.dcr


# --- staged protocol + v0 compat shim ---------------------------------------

class _V0SuperFeatureDetector:
    """The pre-refactor monolithic FirstFit loop, verbatim: interleaved
    query/insert against the shared index. The staged overlay in
    SuperFeatureDetector.score must reproduce this bit-identically."""

    def __init__(self, scheme, name):
        self._scheme = scheme
        self.name = name
        self._index = baselines.SuperFeatureIndex()

    def fit(self, training_streams, cfg):
        pass

    def detect(self, chunks, ids, is_new, stream_hashes):
        out = np.full(len(chunks), -1, np.int64)
        for i, ck in enumerate(chunks):
            sfs = self._scheme.super_features(ck.data)
            if is_new[i]:
                hit = self._index.query(sfs)
                if hit is not None and hit != ids[i]:
                    out[i] = hit
            self._index.insert(sfs, int(ids[i]))
        return out


@pytest.mark.parametrize("scheme_cls,name", [(baselines.Finesse, "finesse"),
                                             (baselines.NTransform, "n-transform")])
def test_staged_firstfit_bit_identical_to_v0(scheme_cls, name, versions):
    staged = pipeline.SuperFeatureDetector(scheme_cls(), name)
    v0 = _V0SuperFeatureDetector(scheme_cls(), name)
    s_new = pipeline.run_workload(staged, versions, CCFG)
    s_old = pipeline.run_workload(v0, versions, CCFG)
    assert _stat_tuple(s_new) == _stat_tuple(s_old)
    assert staged._index._tables == v0._index._tables


def test_legacy_detect_shim_matches_staged(versions):
    """Calling the v0 .detect() surface equals running the staged stages —
    and a legacy-only wrapper goes through run_detect's fallback."""

    class LegacyOnly:
        def __init__(self, inner):
            self._inner = inner
            self.name = inner.name

        def fit(self, streams, cfg):
            self._inner.fit(streams, cfg)

        def detect(self, chunks, ids, is_new, stream_hashes):
            return self._inner.detect(chunks, ids, is_new, stream_hashes)

    staged = pipeline.run_workload(_card_direct(), versions, CCFG)
    legacy = pipeline.run_workload(LegacyOnly(_card_direct()), versions, CCFG)
    assert _stat_tuple(staged) == _stat_tuple(legacy)
    assert staged.dcr == legacy.dcr


def test_score_does_not_mutate_index(versions):
    det = _card_direct()
    det.fit(versions[:1], CCFG)
    stream = versions[0]
    buf = np.frombuffer(stream, dtype=np.uint8)
    from repro.core import hashing
    hashes = hashing.gear_hashes_np(buf)
    chunks = chunking.chunk_stream(stream, CCFG, hashes=hashes)
    ids = np.arange(len(chunks), dtype=np.int64)
    batch = api.DetectBatch(chunks=chunks, ids=ids,
                            is_new=np.ones(len(chunks), bool),
                            stream_hashes=hashes)
    feats = det.extract(batch)
    r1 = det.score(feats, batch)
    assert len(det.index) == 0          # pure: nothing admitted yet
    r2 = det.score(feats, batch)
    assert np.array_equal(r1.base_ids, r2.base_ids)
    det.observe(feats, batch)
    assert len(det.index) == len(chunks)


# --- stream sessions + IngestReport -----------------------------------------

def test_ingest_reports_sum_to_store_stats(versions):
    store = api.build_store(_card_cfg())
    store.fit(versions[:1])
    reports = []
    for v in versions:
        with store.open_stream() as session:
            session.write(v[: len(v) // 2])
            session.write(v[len(v) // 2:])
        reports.append(store.reports[-1])
    s = store.stats
    for field in ("bytes_in", "bytes_stored", "chunks", "dup_chunks",
                  "delta_chunks", "raw_chunks", "detect_seconds",
                  "chunk_seconds", "delta_seconds"):
        assert sum(getattr(r, field) for r in reports) == pytest.approx(
            getattr(s, field)), field
    assert [r.handle for r in reports] == [0, 1, 2]
    for r, v in zip(reports, versions):
        assert r.bytes_in == len(v)
        assert store.restore(r.handle) == v


def test_failed_commit_admits_nothing_to_index(versions):
    """Backend write failure mid-commit must leave the detector index
    untouched (observe is deferred past storage for staged detectors)."""

    class ExplodingBackend(api.InMemoryBackend):
        def put_raw(self, cid, data):
            raise OSError("disk full")

        def put_delta(self, cid, base, patch, data=None):
            raise OSError("disk full")

    store = api.DedupStore(pipeline.finesse_detector(), CCFG,
                           backend=ExplodingBackend())
    store.fit(versions[:1])
    session = store.open_stream()
    session.write(versions[0])
    with pytest.raises(OSError, match="disk full"):
        session.commit()
    assert store.detector._index._tables == []   # nothing admitted
    assert store.stats.chunks == 0
    assert store.backend.num_streams() == 0
    assert session.report is None


def test_session_report_available_after_context_exit(versions):
    store = api.build_store(_card_cfg())
    store.fit(versions[:1])
    with store.open_stream() as session:
        session.write(versions[0])
    assert session.report is not None
    assert session.report.handle == 0
    assert session.report.bytes_in == len(versions[0])


def test_aborted_session_leaves_no_trace(versions):
    store = api.build_store(_card_cfg())
    store.fit(versions[:1])
    session = store.open_stream()
    session.write(versions[0])
    session.abort()
    assert store.stats.chunks == 0
    assert store.backend.num_streams() == 0
    with pytest.raises(RuntimeError):
        session.commit()
    # a session abandoned by an exception also admits nothing
    with pytest.raises(RuntimeError, match="boom"):
        with store.open_stream() as s2:
            s2.write(versions[0])
            raise RuntimeError("boom")
    assert store.stats.chunks == 0 and len(store.detector.index) == 0


# --- container backends ------------------------------------------------------

def test_file_backend_restore_byte_identical(tmp_path, versions):
    cfg = api.DedupConfig.from_dict(
        {"detector": "finesse", "chunker_args": {"avg_size": AVG},
         "backend": "file", "backend_args": {"path": str(tmp_path)}})
    store = api.build_store(cfg)
    store.fit(versions[:1])
    handles = []
    for v in versions:
        session = store.open_stream()
        session.write(v)
        handles.append(session.commit().handle)
    assert store.stats.delta_chunks > 0     # delta records actually on disk
    for h, v in zip(handles, versions):
        assert store.restore(h) == v
    store.close()

    # reopen from disk only: a fresh backend must materialize delta chains
    reopened = api.FileBackend(tmp_path)
    assert reopened.num_streams() == len(versions)
    for h, v in zip(handles, versions):
        got = b"".join(reopened.get(c) for c in reopened.recipe(h))
        assert got == v
    reopened.close()


def test_reopened_store_never_shadows_old_chunk_ids(tmp_path, versions):
    """A store opened on an existing file backend must seed its id counter
    past the persisted chunks, or new ingests corrupt old streams."""
    cfg = api.DedupConfig.from_dict(
        {"detector": "dedup-only", "chunker_args": {"avg_size": AVG},
         "backend": "file", "backend_args": {"path": str(tmp_path)}})
    first = api.build_store(cfg)
    first.ingest(versions[0])
    h0 = first.reports[-1].handle
    first.close()

    second = api.build_store(cfg)           # same dir, fresh store
    second.ingest(versions[1])
    h1 = second.reports[-1].handle
    assert second.restore(h1) == versions[1]
    assert second.restore(h0) == versions[0]   # old stream intact
    second.close()


def test_memory_and_file_backends_agree(tmp_path, versions):
    mem = api.build_store(_card_cfg())
    fil = api.build_store(_card_cfg(
        {"backend": "file", "backend_args": {"path": str(tmp_path)}}))
    s_mem = _run_store(mem, versions[:2])
    s_fil = _run_store(fil, versions[:2])

    def normalized(stats, store):
        # bytes_stored includes the backend-reported per-record overhead
        # (25-byte log headers on file, none in dicts); strip it so the
        # payload accounting must still agree bit-for-bit
        records = stats.delta_chunks + stats.raw_chunks
        t = _stat_tuple(stats)
        return (t[0], t[1] - records * store.backend.record_overhead,
                *t[2:])

    assert normalized(s_mem, mem) == normalized(s_fil, fil)
    fil.close()


def test_file_backend_survives_torn_tail(tmp_path, versions):
    """kill -9 mid-commit tears the log/recipe tails; reopen must drop the
    torn (never-reported) record, keep every committed stream, and keep
    the directory appendable."""
    cfg = api.DedupConfig.from_dict(
        {"detector": "dedup-only", "chunker_args": {"avg_size": AVG},
         "backend": "file", "backend_args": {"path": str(tmp_path)}})
    store = api.build_store(cfg)
    store.ingest(versions[0])
    h0 = store.reports[-1].handle
    store.ingest(versions[1])
    store.close()

    log = tmp_path / "chunks.log"
    recipes = tmp_path / "recipes.jsonl"
    log.write_bytes(log.read_bytes()[:-11])             # torn payload
    recipes.write_bytes(recipes.read_bytes()[:-5])      # torn JSON line

    reopened = api.build_store(cfg)
    assert reopened.backend.num_streams() == 1          # stream 1 tail torn away
    assert reopened.restore(h0) == versions[0]
    reopened.ingest(versions[2])                        # appends still work...
    h2 = reopened.reports[-1].handle
    assert reopened.restore(h2) == versions[2]
    reopened.close()
    third = api.FileBackend(tmp_path)                   # ...and re-scan cleanly
    assert b"".join(third.get(c) for c in third.recipe(h2)) == versions[2]
    third.close()


def test_file_backend_torn_newline_only(tmp_path, versions):
    """A final recipe line that parses but lost only its newline is still
    torn — keeping it would merge the next append onto the same line and
    destroy every recipe on the reopen after that."""
    cfg = api.DedupConfig.from_dict(
        {"detector": "dedup-only", "chunker_args": {"avg_size": AVG},
         "backend": "file", "backend_args": {"path": str(tmp_path)}})
    store = api.build_store(cfg)
    store.ingest(versions[0])
    h0 = store.reports[-1].handle
    store.ingest(versions[1])
    store.close()

    recipes = tmp_path / "recipes.jsonl"
    recipes.write_bytes(recipes.read_bytes()[:-1])      # shear the newline

    second = api.build_store(cfg)
    assert second.backend.num_streams() == 1            # stream 1 dropped
    second.ingest(versions[2])
    h2 = second.reports[-1].handle
    second.close()

    third = api.build_store(cfg)                        # the critical reopen
    assert third.backend.num_streams() == 2
    assert third.restore(h0) == versions[0]
    assert third.restore(h2) == versions[2]
    third.close()


def test_custom_chunker_registers_and_runs(versions):
    """The chunker seam is real: a registered fixed-size chunker flows
    through build_store and the whole ingest/restore path."""
    from repro.core import hashing

    class FixedSizeChunker:
        def __init__(self, size=8192):
            self.size = size

        def chunk(self, stream):
            hashes = hashing.gear_hashes_np(np.frombuffer(stream, np.uint8))
            chunks = [chunking.Chunk(off, len(stream[off:off + self.size]),
                                     stream[off:off + self.size])
                      for off in range(0, len(stream), self.size)]
            return chunks, hashes

    if "fixed" not in api.available_chunkers():
        api.register_chunker("fixed")(FixedSizeChunker)
    cfg = api.DedupConfig.from_dict(
        {"detector": "finesse", "chunker": "fixed",
         "chunker_args": {"size": 8192}})
    store = api.build_store(cfg)
    store.fit(versions[:1])
    stats = _run_store(store, versions[:2])
    assert stats.chunks == sum(-(-len(v) // 8192) for v in versions[:2])
    assert stats.dup_chunks > 0
    assert store.restore(store.reports[1].handle) == versions[1]


def test_builtin_registration_survives_failed_import(monkeypatch):
    """A failing builtin import must not permanently empty the registries."""
    import builtins
    from repro.api import registry as reg

    monkeypatch.setattr(reg, "_builtins_loaded", False)
    real_import = builtins.__import__

    def boom(name, *args, **kwargs):
        if name == "repro.core":
            raise ImportError("transient")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", boom)
    with pytest.raises(ImportError, match="transient"):
        reg.get_detector("card")
    monkeypatch.setattr(builtins, "__import__", real_import)
    assert "card" in reg.available_detectors()          # recovers


def test_checkpoint_store_has_no_private_reach_through():
    import inspect
    from repro.checkpoint import dedup_store
    assert "_recipes" not in inspect.getsource(dedup_store)


# --- banded LSH batch insert -------------------------------------------------

def test_banded_insert_batch_matches_serial_insert():
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((64, 50)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    ids = np.arange(64, dtype=np.int64)

    a = similarity.BandedLSHIndex(50)
    b = similarity.BandedLSHIndex(50)
    a.insert_batch(feats, ids)
    for f, cid in zip(feats, ids):      # v0 path: one insert per row
        b._feats[int(cid)] = np.asarray(f, np.float32)
        signs = (np.einsum("bkd,d->bk", b._planes, f) > 0)
        weights = (1 << np.arange(b.band_bits, dtype=np.uint64))
        keys = (signs.astype(np.uint64) * weights).sum(axis=1)
        for band, key in enumerate(keys):
            b._tables[band].setdefault(int(key), []).append(int(cid))
    assert a._tables == b._tables
    qid_a, qs_a = a.query(feats[:8])
    qid_b, qs_b = b.query(feats[:8])
    assert np.array_equal(qid_a, qid_b)
    assert np.allclose(qs_a, qs_b)


def _fresh_python(script, **env):
    """Run ``script`` in a new interpreter; ``env`` entries override the
    inherited environment, and a None value removes the variable."""
    import os
    import pathlib
    import subprocess
    import sys
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    full = dict(os.environ, PYTHONPATH=str(src) + os.pathsep
                + os.environ.get("PYTHONPATH", ""))
    for k, v in env.items():
        full.pop(k, None)
        if v is not None:
            full[k] = v
    return subprocess.run([sys.executable, "-c", script], env=full,
                          capture_output=True, text=True, timeout=120)


def test_import_initialises_no_jax_backend():
    """Importing the package must not take a device: a child process
    that imports repro after its parent touched JAX would otherwise need
    the chip the parent holds."""
    p = _fresh_python(
        "import repro.api, repro.core.pipeline, repro.kernels.ingest\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized())\n")
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


def test_compile_cache_env_dir_wins_else_fixed_checkout_path(tmp_path):
    import pathlib
    script = ("import jax\nfrom repro import api\n"
              "print(api.enable_compile_cache())\n"
              "print(jax.config.jax_compilation_cache_dir)\n"
              "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    # every program is cached, however fast it compiled
    p = _fresh_python(script, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path)] * 2 + ["0"]

    p = _fresh_python(script, JAX_COMPILATION_CACHE_DIR=None)
    assert p.returncode == 0, p.stderr
    fixed = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    assert p.stdout.split() == [str(fixed)] * 2 + ["0"]
