"""Fused per-stream ingest pipeline (DESIGN.md §8).

The pre-fusion ingest path ran the scan and Algorithm 1 as a host-bound
pipeline: a 31-pass numpy gear scan, a per-chunk Python loop over
``subchunk_maxgear_np`` (with its own warm-up re-derivation loop), then
shingle/unique/embed dispatches with numpy round-trips in between — and
a fresh XLA compilation whenever the stream's chunk count or longest
chunk changed. This module replaces all of it with TWO jitted device
programs per stream:

    _scan_fused      bytes [Spad] u8
                       -> windowed gear hashes [Spad] u32 (window
                          doubling; stays device-resident: StreamScan)
                       -> bit-packed FastCDC candidate maps (to host,
                          n/8 bytes each, for boundary selection)
    _extract_fused   StreamScan + chunk offsets/lengths [Bpad]
                       -> sub-chunk maxgear LSH [B, K] (two-tier
                          scatter-free segment max)
                       -> shingle ids + per-row uniquification
                       -> multiply-shift embed + normalize -> [B, M]

Two rules make the steady state hit a warm jit cache (zero recompiles,
asserted by tests/test_ingest_fast.py):

  * every dynamic extent is padded up to a power-of-two bucket — the
    stream length, the chunk count B, and the longest-chunk extent Lmax;
  * all knobs that change the traced program (K, N, normalize, embed
    path, FastCDC masks) are static jit arguments.

Padding is sliced away on exit, and padded rows/positions are masked
inside the programs, so every integer stage is bit-identical per row to
the per-chunk numpy oracle (``subchunk_maxgear_np`` -> ``shingle_ids``;
boundaries to ``chunking.chunk_stream``) and the float embed agrees to
~1 ULP (XLA fuses the single program differently than the staged
dispatches) — pinned by tests/test_ingest_fast.py across ragged chunk
sizes including chunks shorter than the 32-byte gear warm-up, plus an
end-to-end verdict/container equality test on real workloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as _feat
from repro.core import hashing
from repro.core.features import bucket_pow2  # noqa: F401  (canonical rule)

# Monotonic count of XLA traces of the fused program. A trace happens
# exactly when a (shape-bucket, static-arg) combination misses the jit
# cache, so steady-state ingest of same-bucket streams must not move it
# (the zero-recompilation acceptance test reads this).
_TRACES: list[tuple] = []


def trace_count() -> int:
    return len(_TRACES)


# Bucket floors. B matches the historical FeatureExtractor pad floor so
# the embed stage sees the exact shapes the unfused path produced;
# the stream floor keeps tiny commits from fragmenting the cache.
_FLOOR_B = 16
_FLOOR_STREAM = 1 << 16

# The fused program indexes with int32; positions reach at most
# stream_len + one edge tile (<= 128), so cap well below 2**31 and let
# FeatureExtractor route oversized streams to the per-chunk host path.
FUSED_STREAM_LIMIT = 2**31 - 2**20

# Reusable host staging buffers, one per stream bucket. Safe to overwrite
# between scans: the scan program has fully executed (its candidate
# bitmaps are materialized to host) before scan_stream returns, so the
# host-to-device copy of the previous stream is complete. Buckets past
# the cap are allocated transiently so one huge stream does not pin its
# buffer for process lifetime.
_SCAN_BUFS: dict[int, np.ndarray] = {}
_SCAN_BUF_CACHE_CAP = 64 << 20


def _stage(data: np.ndarray, spad: int) -> jax.Array:
    """Copy a bucket-padded host buffer to the default device. The result
    is uncommitted, so the scan runs wherever ``jax.default_device`` (or,
    unset, the default backend) points: the chip on a TPU host."""
    buf = _SCAN_BUFS.get(spad)
    if buf is None:
        buf = np.zeros(spad, np.uint8)
        if spad <= _SCAN_BUF_CACHE_CAP:
            _SCAN_BUFS[spad] = buf
    buf[:len(data)] = data
    return jax.device_put(buf)


class StreamScan:
    """Device-resident gear scan of one stream (bucket-padded), with lazy
    host materialization for the per-chunk numpy paths. Detectors that
    fuse (CARD) read ``.device`` and never pay a round-trip; legacy
    consumers index it like the old [n] uint32 numpy array."""

    def __init__(self, device: jax.Array, n: int) -> None:
        self.device = device            # [bucket_pow2(n)] uint32
        self.n = n
        self._np: np.ndarray | None = None

    def asnumpy(self) -> np.ndarray:
        if self._np is None:
            self._np = np.asarray(self.device)[:self.n]
        return self._np

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, key):
        return self.asnumpy()[key]

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a if dtype is None else a.astype(dtype)


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l"))
def _scan_fused(data: jax.Array, *, mask_s: int, mask_l: int):
    """[Spad] u8 -> windowed gear hashes [Spad] u32 (left on device) +
    bit-packed FastCDC boundary-candidate maps (shipped to host).

    Window-doubling evaluation (see hashing.gear_hashes_np): 5 shifted
    adds instead of 31, all uint32 wraparound, bit-identical to the
    serial gear recurrence past the 32B warm-up."""
    _TRACES.append(("scan", data.shape, mask_s, mask_l))
    g = jnp.asarray(hashing.GEAR_TABLE)[data.astype(jnp.int32)]
    h = g
    m = data.shape[0]
    w = 1
    while w < hashing.GEAR_WINDOW:
        shifted = jnp.concatenate([jnp.zeros(w, jnp.uint32), h[:m - w]])
        h = h + shifted * jnp.uint32((1 << w) & 0xFFFFFFFF)
        w *= 2
    cand_s = jnp.packbits((h & jnp.uint32(mask_s)) == 0)
    cand_l = jnp.packbits((h & jnp.uint32(mask_l)) == 0)
    return h, cand_s, cand_l


def scan_stream(data: np.ndarray, mask_s: int, mask_l: int
                ) -> tuple[StreamScan, np.ndarray, np.ndarray]:
    """One device program for the chunker scan: returns the device-
    resident StreamScan plus the two [n] bool candidate maps the host
    boundary selection walks. Only bytes go up and packed bits come
    down — the 4-bytes-per-position hash array never round-trips."""
    n = len(data)
    spad = bucket_pow2(n, _FLOOR_STREAM)
    h, cs, cl = _scan_fused(_stage(data, spad),
                            mask_s=int(mask_s), mask_l=int(mask_l))
    cand_s = np.unpackbits(np.asarray(cs))[:n].view(np.bool_)
    cand_l = np.unpackbits(np.asarray(cl))[:n].view(np.bool_)
    return StreamScan(h, n), cand_s, cand_l


def stream_subchunk_maxgear(stream_hashes: jax.Array, offsets: jax.Array,
                            lengths: jax.Array, *, k: int, lmax: int
                            ) -> jax.Array:
    """[Spad] u32 hashes + [B] offsets/lengths -> [B, K] u32 sub-chunk
    maxgear LSH: the integer stage of ``_extract_fused``, traced inside it
    and bit-identical to ``features.subchunk_maxgear_np`` per chunk."""
    spad = stream_hashes.shape[0]

    # Sub-chunk maxgear LSH without scatter (XLA CPU scatter is serial and
    # was 10x the cost of the rest of the program combined). Segment j of
    # a length-L chunk spans [floor(j*L/k), floor((j+1)*L/k)) — the
    # _bounds convention — clipped below by the 32B gear warm-up; empty
    # segments must come out 0.
    j = jnp.arange(k + 1)
    lens = jnp.maximum(lengths, 0)
    bounds = (j[None, :] * lens[:, None]) // k          # [B, K+1]
    s_abs = offsets[:, None] + jnp.maximum(bounds[:, :k], _feat._WARMUP)
    e_abs = offsets[:, None] + bounds[:, 1:]            # [B, K] absolute

    tmax = lmax // k + 1                                # max segment width
    if tmax <= 32:
        # tiny chunks: one dense masked gather [B, K, Tmax] is cheapest
        t = jnp.arange(tmax)
        pos = s_abs[:, :, None] + t[None, None, :]
        valid = pos < e_abs[:, :, None]
        vals = jnp.where(valid, stream_hashes[jnp.clip(pos, 0, spad - 1)], 0)
        sub = jnp.max(vals, axis=-1).astype(jnp.uint32)
    else:
        # two-tier max: precompute tile maxes over the stream (one
        # contiguous reshape-reduce), cover each segment's interior with
        # whole tiles and its ragged edges with two <=T-wide gathers.
        # Work per segment drops from Tmax to ~2T + Tmax/T (about 10x at
        # the default chunk config); max is idempotent, so the edge
        # gathers overlapping the tile span (or each other, for segments
        # inside one tile) is harmless.
        tile = min(128, max(8, bucket_pow2(int(tmax ** 0.5))))
        ntiles = tmax // tile + 2
        tiles = jnp.max(stream_hashes.reshape(-1, tile), axis=-1)
        ti0 = (s_abs + tile - 1) // tile                # first whole tile
        ti1 = e_abs // tile                             # one past last
        ji = jnp.arange(ntiles)
        tidx = ti0[:, :, None] + ji[None, None, :]
        tmask = ji[None, None, :] < (ti1 - ti0)[:, :, None]
        interior = jnp.where(
            tmask, tiles[jnp.clip(tidx, 0, tiles.shape[0] - 1)], 0)
        tj = jnp.arange(tile)
        hpos = s_abs[:, :, None] + tj[None, None, :]    # head edge
        hmask = hpos < jnp.minimum(e_abs, ti0 * tile)[:, :, None]
        head = jnp.where(
            hmask, stream_hashes[jnp.clip(hpos, 0, spad - 1)], 0)
        ts = jnp.maximum(s_abs, ti1 * tile)             # tail edge
        tpos = ts[:, :, None] + tj[None, None, :]
        tmask2 = tpos < e_abs[:, :, None]
        tail = jnp.where(
            tmask2, stream_hashes[jnp.clip(tpos, 0, spad - 1)], 0)
        sub = jnp.maximum(jnp.max(interior, axis=-1),
                          jnp.maximum(jnp.max(head, axis=-1),
                                      jnp.max(tail, axis=-1)))
        sub = sub.astype(jnp.uint32)

    return sub


# Lmax is a gather extent (a shape), so it is a static argument like the
# feature-config knobs.
@functools.partial(jax.jit, static_argnames=("k", "n", "lmax", "normalize",
                                             "use_kernel"))
def _extract_fused(stream_hashes: jax.Array, offsets: jax.Array,
                   lengths: jax.Array, a: jax.Array, b: jax.Array,
                   *, k: int, n: int, lmax: int, normalize: bool,
                   use_kernel: bool) -> jax.Array:
    """[Spad] u32 hashes + [Bpad] offsets/lengths -> [Bpad, M] features."""
    _TRACES.append((stream_hashes.shape, offsets.shape, lmax, k, n,
                    normalize, use_kernel))
    sub = stream_subchunk_maxgear(stream_hashes, offsets, lengths,
                                  k=k, lmax=lmax)
    ids = _feat.shingle_ids(sub, n)
    ids, mask = _feat.unique_mask(ids)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.shingle_embed(ids, mask, a, b, normalize=normalize)
    return _feat.embed_shingles_j(ids, mask, a, b, normalize)


def extract_stream(stream_hashes: np.ndarray, offsets: np.ndarray,
                   lengths: np.ndarray, a: jax.Array, b: jax.Array,
                   *, k: int, n: int, normalize: bool = True,
                   use_kernel: bool = False,
                   lmax_floor: int = 0) -> np.ndarray:
    """Host entry: bucket-pad everything, run the fused program, slice.

    ``stream_hashes`` may be a StreamScan (already device-resident and
    bucket-padded — the zero-round-trip path the store uses) or a host
    [n] uint32 array. ``lmax_floor`` should be the chunker's max chunk
    size so every stream cut by the same config lands in the same Lmax
    bucket.
    """
    bsz = int(offsets.shape[0])
    if bsz == 0:
        return np.zeros((0, int(a.shape[-1])), np.float32)
    ends = np.asarray(offsets, np.int64) + np.asarray(lengths, np.int64)
    if int(ends.max()) > FUSED_STREAM_LIMIT:
        raise ValueError(
            "fused extract indexes with int32; streams past "
            "FUSED_STREAM_LIMIT must take the per-chunk host path "
            "(FeatureExtractor routes this)")
    lengths = np.asarray(lengths, np.int32)
    offsets = np.asarray(offsets, np.int32)

    if isinstance(stream_hashes, StreamScan):
        sh = stream_hashes.device
    else:
        spad = bucket_pow2(len(stream_hashes), _FLOOR_STREAM)
        sh = np.zeros(spad, np.uint32)
        sh[:len(stream_hashes)] = stream_hashes
    bpad = bucket_pow2(bsz, _FLOOR_B)
    lmax = bucket_pow2(max(int(lengths.max()), 1), max(1, int(lmax_floor)))

    off_p = np.zeros(bpad, np.int32)
    off_p[:bsz] = offsets
    len_p = np.zeros(bpad, np.int32)
    len_p[:bsz] = lengths

    out = _extract_fused(
        jnp.asarray(sh), jnp.asarray(off_p), jnp.asarray(len_p), a, b,
        k=k, n=n, lmax=lmax, normalize=normalize, use_kernel=use_kernel)
    return np.asarray(out)[:bsz]
