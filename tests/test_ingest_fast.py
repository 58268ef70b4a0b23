"""Fused ingest fast path (DESIGN.md §8): bit-identity against the
per-chunk numpy oracle, zero steady-state recompilation, device-scan
chunking equivalence, group-commit storage equivalence."""
import numpy as np
import pytest

from repro.core import chunking, features, hashing
from repro.kernels import ingest


def _fused_features(chunks, stream_hashes, offsets, cfg=None):
    ext = features.FeatureExtractor(cfg, use_kernel=False, fused=True)
    return ext(chunks, stream_hashes, np.asarray(offsets))


def _oracle_features(chunks, stream_hashes, offsets, cfg=None):
    """The per-chunk numpy oracle: subchunk_maxgear_np per chunk ->
    shingle_ids -> unique -> reference embed."""
    ext = features.FeatureExtractor(cfg, use_kernel=False, fused=False)
    sub = np.stack([
        features.subchunk_maxgear_np(
            np.asarray(stream_hashes)[o:o + len(c)], ext.cfg.k)
        for c, o in zip(chunks, offsets)])
    return ext.features_from_subhashes(sub)


def _case(sizes, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    stream = rng.integers(0, 256, size=sum(sizes), dtype=np.uint8)
    offsets = np.cumsum([0] + list(sizes[:-1]))
    chunks = [stream[o:o + s].tobytes() for o, s in zip(offsets, sizes)]
    return chunks, hashing.gear_hashes_np(stream), offsets


def test_fused_matches_per_chunk_oracle_ragged():
    """Ragged chunk sizes including shorter than the 32B gear warm-up."""
    chunks, h, offs = _case([1, 2, 31, 32, 33, 5, 700, 8192, 40000, 17])
    got = _fused_features(chunks, h, offs)
    want = _oracle_features(chunks, h, offs)
    np.testing.assert_allclose(got, want, atol=3e-7)


def test_fused_subchunk_stage_is_bit_identical():
    """The integer stages (sub-chunk LSH, shingle ids) must be exact —
    compare through the whole pipeline with the embed replaced by the
    identity-revealing unique-id sort."""
    chunks, h, offs = _case([5, 100, 31, 8192, 999], seed=3)
    k = features.FeatureConfig().k
    sub_oracle = np.stack([
        features.subchunk_maxgear_np(h[o:o + len(c)], k)
        for c, o in zip(chunks, offs)])
    # the batched jnp reference shares the fused path's segment math
    lmax = max(len(c) for c in chunks)
    gear = np.zeros((len(chunks), lmax), np.uint32)
    for i, (c, o) in enumerate(zip(chunks, offs)):
        gear[i, :len(c)] = h[o:o + len(c)]
    lens = np.asarray([len(c) for c in chunks], np.int32)
    import jax.numpy as jnp
    sub_j = np.asarray(features.batch_subchunk_maxgear_j(
        jnp.asarray(gear), jnp.asarray(lens), k))
    assert np.array_equal(sub_oracle, sub_j)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_property_sweep(seed):
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.integers(min_value=1, max_value=3000),
                    min_size=1, max_size=12),
           st.integers(min_value=0, max_value=2**31 - 1))
    def prop(sizes, s):
        chunks, h, offs = _case(sizes, seed=s + seed)
        got = _fused_features(chunks, h, offs)
        want = _oracle_features(chunks, h, offs)
        np.testing.assert_allclose(got, want, atol=3e-7)

    prop()


def test_steady_state_zero_recompiles():
    """Same-bucket streams must hit a warm jit cache: no new traces of
    the scan or extract programs after the first stream of a bucket."""
    from repro import api
    cfg = api.DedupConfig.from_dict({
        "detector": "card",
        "detector_args": {"feat": {"k": 8, "m": 16, "n": 2},
                          "model": {"m": 16, "d": 8, "steps": 4},
                          "use_kernel": False},
        "chunker_args": {"avg_size": 1024}})
    store = api.build_store(cfg)
    rng = np.random.Generator(np.random.PCG64(0))
    streams = [rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()
               for _ in range(4)]
    store.fit(streams[:1])
    store.ingest(streams[0])
    store.ingest(streams[1])            # same bucket: warms every program
    before = ingest.trace_count()
    store.ingest(streams[2])
    store.ingest(streams[3])
    assert ingest.trace_count() == before, "steady-state ingest retraced"


def test_lmax_floor_prevents_longest_chunk_retrace():
    """The Lmax bucket is pinned at the chunker's max_size (wired through
    CARDDetector.fit), so a stream whose observed longest chunk straddles
    a pow2 boundary must not retrace the extract program."""
    from repro.core import features
    ext = features.FeatureExtractor(
        features.FeatureConfig(k=8, m=16, n=2), use_kernel=False)
    rng = np.random.Generator(np.random.PCG64(2))

    def feats(sizes, seed):
        chunks, h, offs = _case(sizes, seed=seed)
        return ext(chunks, h, offs, lmax_floor=4096)

    feats([1500, 900, 1200], seed=1)        # warm: longest 1500
    before = ingest.trace_count()
    feats([2500, 700], seed=2)              # longest 2500: same 4096 bucket
    assert ingest.trace_count() == before


def test_device_scan_matches_host_chunking():
    """chunk_with's device gear scan must reproduce the host chunker
    bit-for-bit: same hashes, same boundaries."""
    from repro.api.store import chunk_with
    rng = np.random.Generator(np.random.PCG64(7))
    stream = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
    cfg = chunking.ChunkerConfig(avg_size=4096)
    host = chunking.chunk_stream(stream, cfg)
    dev_chunks, scan = chunk_with(cfg, stream)
    assert [(c.offset, c.length) for c in host] == \
           [(c.offset, c.length) for c in dev_chunks]
    assert np.array_equal(
        np.asarray(scan),
        hashing.gear_hashes_np(np.frombuffer(stream, np.uint8)))


def test_fused_and_unfused_stores_bit_identical(tmp_path):
    """End-to-end pin: verdicts, per-stream accounting, container records
    and restored bytes agree between the fused fast path and the
    per-chunk baseline."""
    from repro import api
    rng = np.random.Generator(np.random.PCG64(5))
    base = rng.integers(0, 256, size=300_000, dtype=np.uint8)
    v2 = base.copy()
    v2[1000:1100] = rng.integers(0, 256, size=100, dtype=np.uint8)
    v3 = np.concatenate([base[:150_000],
                         rng.integers(0, 256, size=500, dtype=np.uint8),
                         base[150_000:]])
    versions = [v.tobytes() for v in (base, v2, v3)]

    def build(fused, path):
        cfg = api.DedupConfig.from_dict({
            "detector": "card",
            "detector_args": {"feat": {"k": 16, "m": 32, "n": 2},
                              "model": {"m": 32, "d": 16, "steps": 8},
                              "use_kernel": False, "fused": fused},
            "chunker_args": {"avg_size": 4096},
            "backend": "file", "backend_args": {"path": str(path)}})
        store = api.build_store(cfg)
        store.fit(versions[:1])
        for v in versions:
            store.ingest(v)
        return store

    s_f = build(True, tmp_path / "fused")
    s_u = build(False, tmp_path / "unfused")
    for rf, ru in zip(s_f.reports, s_u.reports):
        assert (rf.chunks, rf.dup_chunks, rf.delta_chunks, rf.raw_chunks,
                rf.bytes_stored) == (ru.chunks, ru.dup_chunks,
                                     ru.delta_chunks, ru.raw_chunks,
                                     ru.bytes_stored)
    assert sorted(s_f.backend.chunk_ids()) == sorted(s_u.backend.chunk_ids())
    for cid in s_f.backend.chunk_ids():
        assert s_f.backend.record(cid) == s_u.backend.record(cid)
    for h, v in enumerate(versions):
        assert s_f.restore(h) == v
        assert s_u.restore(h) == v
    s_f.close()
    s_u.close()


def test_put_many_file_backend_matches_per_chunk(tmp_path):
    """Group commit writes the same records the per-chunk puts would, and
    a reopened backend serves them identically."""
    from repro.api import containers
    rng = np.random.Generator(np.random.PCG64(9))
    payloads = [rng.integers(0, 256, size=int(s), dtype=np.uint8).tobytes()
                for s in rng.integers(10, 5000, size=8)]

    a = containers.FileBackend(tmp_path / "a")
    a.put_raw(0, payloads[0])
    a.put_delta(1, 0, payloads[1], data=payloads[2])
    a.put_raw(2, payloads[3])
    a.flush()

    b = containers.FileBackend(tmp_path / "b")
    b.put_many([(0, -1, payloads[0], None),
                (1, 0, payloads[1], payloads[2]),
                (2, -1, payloads[3], None)])
    b.flush()

    for cid in (0, 1, 2):
        assert a.record(cid) == b.record(cid)
        assert a.payload_size(cid) == b.payload_size(cid)
        assert a.base_of(cid) == b.base_of(cid)

    reopened = containers.FileBackend(tmp_path / "b")
    for cid in (0, 2):
        assert reopened.record(cid) == a.record(cid)
    a.close(); b.close(); reopened.close()


def test_put_many_failed_write_leaves_no_phantom_index(tmp_path):
    """A group-commit write that fails (ENOSPC) must not leave index
    entries pointing at never-written offsets — contains() lying would
    let later commits delta-encode against phantom bases."""
    from repro.api import containers

    backend = containers.FileBackend(tmp_path)

    class FailingLog:
        def __init__(self, f):
            self._f = f

        def write(self, data):
            raise OSError(28, "No space left on device")

        def __getattr__(self, attr):
            return getattr(self._f, attr)

    backend._log = FailingLog(backend._log)
    with pytest.raises(OSError, match="No space"):
        backend.put_many([(0, -1, b"x" * 100, None),
                          (1, 0, b"patch", b"y" * 100)])
    assert not backend.contains(0)
    assert not backend.contains(1)
    assert backend.max_chunk_id() == -1


def test_streamscan_indexes_like_numpy():
    rng = np.random.Generator(np.random.PCG64(4))
    data = rng.integers(0, 256, size=5000, dtype=np.uint8)
    scan, cand_s, cand_l = ingest.scan_stream(data, 0xFF, 0xF)
    ref = hashing.gear_hashes_np(data)
    assert len(scan) == 5000
    assert np.array_equal(scan[100:200], ref[100:200])
    assert np.array_equal(np.asarray(scan), ref)
    assert np.array_equal(cand_s, (ref & np.uint32(0xFF)) == 0)
    assert np.array_equal(cand_l, (ref & np.uint32(0xF)) == 0)


def _scan_and_extract(n=5000):
    import jax.numpy as jnp
    rng = np.random.Generator(np.random.PCG64(11))
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    scan, _, _ = ingest.scan_stream(data, 0xFF, 0xF)
    offs = np.arange(0, n, 1000, dtype=np.int32)
    lens = np.full(len(offs), 1000, np.int32)
    a, b = (jnp.asarray(x) for x in hashing.multiply_shift_params(16))
    feats = ingest._extract_fused(
        scan.device, jnp.asarray(offs), jnp.asarray(lens), a, b,
        k=8, n=2, lmax=1024, normalize=True, use_kernel=False)
    return scan, feats


def test_scan_and_extract_land_on_the_default_device():
    """The staged stream goes to the default device, uncommitted, so the
    scan and the extract run there: on a chip host, the chip. (Handing
    the host buffer over by dlpack pinned both to the CPU.)"""
    import jax
    staged = ingest._stage(np.arange(100, dtype=np.uint8), 1 << 16)
    assert not staged.committed
    assert staged.devices() == {jax.devices()[0]}
    scan, feats = _scan_and_extract()
    dev = jax.devices()[0]
    assert scan.device.devices() == {dev} and feats.devices() == {dev}
    if dev.platform != "cpu":
        assert not scan.device.committed and not feats.committed


def test_scan_follows_default_device_choice():
    """With two host devices and the second made the default, the scan
    and extract must run on the second: a placement that ignored the
    default device (dlpack returns arrays committed to CPU 0) fails."""
    import os
    import pathlib
    import subprocess
    import sys
    script = (
        "import jax\n"
        "import tests.test_ingest_fast as t\n"
        "with jax.default_device(jax.devices()[1]):\n"
        "    scan, feats = t._scan_and_extract()\n"
        "print(sorted(d.id for d in scan.device.devices()),"
        " sorted(d.id for d in feats.devices()))\n")
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[1] [1]"
