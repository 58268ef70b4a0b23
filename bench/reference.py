"""Plain references the benchmark's ``correct`` is decided against.

Written from the published algorithms (FastCDC, CARD's Algorithm 1, exact
cosine top-1), in numpy, without importing the store. The constants they
need are the algorithms' own: the gear table and the multiply-shift
parameters are drawn from their documented seeds here, not read from the
program.

Each function takes ``dtype``: float64 is the reference; a lower one
(``ml_dtypes.bfloat16``) is the control, the reference computed in the
precision below the float32 that the configuration states.
"""
from __future__ import annotations

import numpy as np

GEAR_SEED = 0xC0FFEE          # FastCDC gear table, 256 uint32 from PCG64
GEAR_WINDOW = 32              # uint32 shifts past 31 vanish
MULTIPLY_SHIFT_SEED = 0xD00DFEED
SHINGLE_Q = 0x9E3779B1        # order-sensitive shingle combiner


def gear_table() -> np.ndarray:
    rng = np.random.Generator(np.random.PCG64(GEAR_SEED))
    return rng.integers(0, 2**32, size=256, dtype=np.uint32)


def gear_hashes(data: bytes) -> np.ndarray:
    """h_i = sum_k gear[b_{i-k}] << k over the last 32 bytes (mod 2**32),
    which equals the serial h = (h << 1) + gear[b] past the first 31."""
    h = gear_table()[np.frombuffer(data, np.uint8)]
    w = 1
    while w < GEAR_WINDOW:
        h[w:] += h[:-w] << np.uint32(w)     # right to left: reads old values
        w *= 2
    return h


def fastcdc_bounds(h: np.ndarray, avg: int, min_factor: float,
                   max_factor: float, norm_level: int) -> np.ndarray:
    """Chunk boundaries (0 and n included) of normalized FastCDC: a hard
    mask from min to avg size, an easy one from avg to max, else max."""
    n = len(h)
    bits = int(np.log2(avg))
    mask_s = np.uint32((1 << (bits + norm_level)) - 1)
    mask_l = np.uint32((1 << (bits - norm_level)) - 1)
    lo_size, hi_size = max(64, int(avg * min_factor)), int(avg * max_factor)
    hard = np.flatnonzero((h & mask_s) == 0)
    easy = np.flatnonzero((h & mask_l) == 0)

    def first(pos: np.ndarray, lo: int, hi: int) -> int:
        i = int(np.searchsorted(pos, lo))
        return int(pos[i]) if i < len(pos) and pos[i] < hi else -1

    out, start = [0], 0
    while start < n:
        if n - start <= lo_size:
            start = n
        else:
            cut = first(hard, start + lo_size, min(start + avg, n))
            if cut < 0:
                cut = first(easy, max(start + lo_size, min(start + avg, n)),
                            min(start + hi_size, n))
            if cut < 0:
                cut = min(start + hi_size, n) - 1
            start = cut + 1
        out.append(start)
    return np.asarray(out, np.int64)


def _multiply_shift(m: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64(MULTIPLY_SHIFT_SEED))
    a = rng.integers(1, 2**32, size=m, dtype=np.uint32) | np.uint32(1)
    b = rng.integers(0, 2**32, size=m, dtype=np.uint32)
    return a, b


def subchunk_maxgear(h: np.ndarray, offsets: np.ndarray,
                     lengths: np.ndarray, k: int) -> np.ndarray:
    """[B, K]: the largest stream gear hash in each of a chunk's K equal
    sub-chunks, leaving out the chunk's first 31 positions (their window
    reaches into the chunk before); an empty sub-chunk gives 0."""
    j = np.arange(k + 1, dtype=np.int64)
    cuts = (j[None, :] * lengths[:, None]) // k               # [B, K+1]
    lo = offsets[:, None] + np.maximum(cuts[:, :-1], GEAR_WINDOW - 1)
    hi = offsets[:, None] + cuts[:, 1:]
    empty = hi <= lo
    # reduceat over [lo, hi) pairs; an extra sentinel keeps hi in range
    ext = np.concatenate([h, np.zeros(1, h.dtype)])
    idx = np.stack([np.where(empty, 0, lo), np.where(empty, 1, hi)],
                   axis=-1).reshape(-1)
    seg = np.maximum.reduceat(ext, idx)[::2].reshape(lo.shape)
    return np.where(empty, 0, seg).astype(np.uint32)


def shingles(sub: np.ndarray, n: int) -> np.ndarray:
    """[B, S]: for r = 1..n, sum_t sub[j + t] * Q**t over t = 0..r."""
    k = sub.shape[1]
    out = []
    for r in range(1, n + 1):
        acc = sub[:, :k - r].astype(np.uint32)
        for t in range(1, r + 1):
            acc = acc + sub[:, t:k - r + t] * np.uint32(pow(SHINGLE_Q, t,
                                                            1 << 32))
        out.append(acc)
    return np.concatenate(out, axis=1)


def initial_features(h: np.ndarray, offsets: np.ndarray, lengths: np.ndarray,
                     *, k: int, m: int, n: int, dtype=np.float64,
                     block: int = 1024) -> np.ndarray:
    """CARD's initial features: each unique shingle is mapped by M
    multiply-shift hashes into [-1, 1)^M, normalized, and the unit
    vectors are averaged and normalized again. [B, M] in ``dtype``."""
    a, b = _multiply_shift(m)
    ids = shingles(subchunk_maxgear(h, np.asarray(offsets, np.int64),
                                    np.asarray(lengths, np.int64), k), n)
    ids = np.sort(ids, axis=1)
    first = np.ones(ids.shape, bool)
    first[:, 1:] = ids[:, 1:] != ids[:, :-1]
    out = np.empty((len(ids), m), dtype)
    eps = dtype(1e-12)
    for s in range(0, len(ids), block):
        x = ids[s:s + block, :, None] * a + b                 # uint32 wraps
        v = (x.view(np.int32).astype(np.float64) * 2.0**-31).astype(dtype)
        v = v / (np.sqrt((v * v).sum(-1, keepdims=True)) + eps)
        mask = first[s:s + block]
        feat = (v * mask[..., None].astype(dtype)).sum(1)
        feat = feat / mask.sum(1, keepdims=True).astype(dtype)
        out[s:s + block] = feat / (np.sqrt((feat * feat).sum(
            -1, keepdims=True)) + eps)
    return out


def top1(q: np.ndarray, index: np.ndarray, dtype=np.float64,
         block: int = 512) -> tuple[np.ndarray, np.ndarray]:
    """Exact cosine top-1 of each query row: (best score, best row), with
    the products taken in ``dtype`` and summed in float32 or wider."""
    acc = np.float64 if dtype == np.float64 else np.float32
    idx = index.astype(dtype).astype(acc)
    best = np.empty(len(q), np.float64)
    arg = np.empty(len(q), np.int64)
    for s in range(0, len(q), block):
        sc = q[s:s + block].astype(dtype).astype(acc) @ idx.T
        arg[s:s + block] = sc.argmax(1)
        best[s:s + block] = sc[np.arange(len(sc)), arg[s:s + block]]
    return best, arg


def top1_gap(q: np.ndarray, index: np.ndarray, arg: np.ndarray,
             block: int = 512) -> float:
    """Widest amount by which a chosen row's float64 cosine lies below
    the float64 best of its query: 0 where every choice is a best one."""
    q64, idx64 = q.astype(np.float64), index.astype(np.float64)
    gap = 0.0
    for s in range(0, len(q), block):
        sc = q64[s:s + block] @ idx64.T
        got = sc[np.arange(len(sc)), arg[s:s + block]]
        gap = max(gap, float((sc.max(1) - got).max(initial=0.0)))
    return gap


def best_bases(feats: np.ndarray, ids: np.ndarray, is_new: np.ndarray,
               index: np.ndarray, index_ids: np.ndarray, threshold: float,
               dtype=np.float64, block: int = 512
               ) -> tuple[np.ndarray, np.ndarray]:
    """CARD's base for each chunk of one stream: the most similar of the
    stored index rows and the stream's earlier chunks, by cosine of the
    projected features with the products taken in ``dtype``. A new
    chunk takes it where the score reaches ``threshold``; every other
    chunk takes none (-1). Returns (base id [n], best score [n])."""
    acc = np.float64 if dtype == np.float64 else np.float32
    q = feats.astype(dtype).astype(acc)
    idx = index.astype(dtype).astype(acc)
    n = len(q)
    best = np.full(n, -np.inf)
    base = np.full(n, -1, np.int64)
    for s in range(0, n, block):
        e = min(n, s + block)
        rows = np.arange(s, e)
        intra = q[s:e] @ q[:e].T
        intra[rows[:, None] <= np.arange(e)[None, :]] = -np.inf  # j < i
        j = intra.argmax(1)
        best[s:e], base[s:e] = intra[rows - s, j], ids[j]
        if len(idx):
            ext = q[s:e] @ idx.T
            r = ext.argmax(1)
            ext_best = ext[rows - s, r]
            take = ext_best > best[s:e]
            best[s:e] = np.where(take, ext_best, best[s:e])
            base[s:e] = np.where(take, index_ids[r], base[s:e])
    ok = is_new & (best >= threshold) & (base != ids)
    return np.where(ok, base, -1), best


def base_gap(feats: np.ndarray, ids: np.ndarray, is_new: np.ndarray,
             index: np.ndarray, index_ids: np.ndarray, chosen: np.ndarray,
             threshold: float) -> float:
    """Widest amount, in float64 cosine, by which a chunk's chosen base
    falls short of the float64 reference's decision: the best candidate
    where it reaches ``threshold``, else no base. A chunk with no base
    where the best reaches the threshold falls short by best - threshold;
    a chosen base below the threshold by threshold - its score. A base
    that is no candidate (not stored, not earlier in the stream, or for
    a chunk that is not new) reads 2, past any cosine gap."""
    q = feats.astype(np.float64)
    _, best = best_bases(feats, ids, is_new, index, index_ids, threshold)
    n = len(q)
    got = np.full(n, -np.inf)
    has = chosen >= 0
    in_index = np.zeros(n, bool)
    if len(index_ids):          # the chosen id among the index rows
        order = np.argsort(index_ids, kind="stable")
        row = order[np.searchsorted(index_ids[order], chosen)
                    .clip(0, len(order) - 1)]
        in_index = has & (index_ids[row] == chosen)
        got[in_index] = np.einsum("nd,nd->n", q[in_index],
                                  index[row[in_index]].astype(np.float64))
    # or its first chunk earlier in this stream
    uniq, first = np.unique(ids, return_index=True)
    k = np.searchsorted(uniq, chosen).clip(0, len(uniq) - 1)
    j = first[k]
    in_stream = (has & ~in_index & (uniq[k] == chosen)
                 & (j < np.arange(n)))
    got[in_stream] = np.einsum("nd,nd->n", q[in_stream], q[j[in_stream]])
    missed = np.where(is_new, np.maximum(best - threshold, 0.0), 0.0)
    gap = np.where(has, np.maximum(best, threshold) - got, missed)
    gap[has & ~(in_index | in_stream)] = 2.0
    gap[has & ~is_new] = 2.0
    return float(gap.max(initial=0.0))
