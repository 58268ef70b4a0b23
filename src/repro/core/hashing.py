"""Hashing substrate for CARD.

All rolling hashes used by the paper (Gear for FastCDC, Rabin-style window
fingerprints for N-transform/Finesse, polynomial sub-chunk LSH) are *linear*
in the input bytes over Z/2^32:

    serial:   h = (h << 1) + gear[b]          (Gear)
              h = h * p + b                   (polynomial / Rabin-style)

    windowed: h_i = sum_k  w_k * g_{i-k}      (mod 2^32)

so every position's windowed hash is a k-tap weighted correlation that can be
evaluated fully in parallel — the TPU-native replacement for the paper's
serial CPU loops (see DESIGN.md §3). This module holds the tables/constants,
numpy host implementations, and jnp implementations used as kernel oracles.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------------
# Deterministic tables / constants
# ----------------------------------------------------------------------------

_GEAR_SEED = 0xC0FFEE
GEAR_WINDOW = 32  # uint32: shifts >= 32 vanish, so the effective window is 32B

# Odd multiplier for polynomial hashes (invertible mod 2^32).
POLY_P = np.uint32(0x01000193)  # FNV prime, odd
RABIN_WINDOW = 48

_rng = np.random.Generator(np.random.PCG64(_GEAR_SEED))
GEAR_TABLE = _rng.integers(0, 2**32, size=256, dtype=np.uint32)


def _u32(x) -> np.ndarray:
    return np.asarray(x).astype(np.uint32)


def modinv_pow2(a: int, bits: int = 32) -> int:
    """Inverse of odd `a` modulo 2**bits (Newton iteration)."""
    assert a % 2 == 1
    x = a  # correct mod 2^3
    for _ in range(6):
        x = (x * (2 - a * x)) % (1 << bits)
    return x % (1 << bits)


POLY_P_INV = np.uint32(modinv_pow2(int(POLY_P)))


def poly_powers(n: int, p: np.uint32 = POLY_P) -> np.ndarray:
    """[p^0, p^1, ..., p^{n-1}] as uint32 (wrapping)."""
    out = np.empty(n, dtype=np.uint32)
    acc = np.uint32(1)
    for i in range(n):
        out[i] = acc
        acc = np.uint32((int(acc) * int(p)) & 0xFFFFFFFF)
    return out


POLY_POW_RABIN = poly_powers(RABIN_WINDOW)
GEAR_WEIGHTS = (np.uint32(1) << np.arange(GEAR_WINDOW, dtype=np.uint32))

# ----------------------------------------------------------------------------
# numpy host implementations (ground truth for tests & host-side fallback)
# ----------------------------------------------------------------------------


def gear_hashes_np(data: np.ndarray) -> np.ndarray:
    """Windowed gear hash at every position of a byte stream.

    h_i == the serial FastCDC gear hash after consuming byte i, provided at
    least GEAR_WINDOW bytes precede i (exact match beyond the warm-up run —
    FastCDC only inspects positions >= min_size >> 32, see chunking.py).

    Window-doubling evaluation: a width-w partial hash extends to width 2w
    via ``h_2w(i) = h_w(i) + h_w(i-w) << w``, so the 32-tap correlation is
    5 vectorized passes instead of 31 (the ingest scan is on the hot path,
    DESIGN.md §8). All arithmetic is uint32: shifted-out high bits vanish
    mod 2^32 exactly as in the serial ``h = (h << 1) + gear[b]`` loop.
    """
    data = np.asarray(data, dtype=np.uint8)
    h = GEAR_TABLE[data].copy()
    n = len(h)
    w = 1
    while w < min(GEAR_WINDOW, n):
        nh = h.copy()
        nh[w:] += h[: n - w] << np.uint32(w)
        h = nh
        w *= 2
    return h


def gear_hashes_serial_np(data: np.ndarray) -> np.ndarray:
    """Bit-exact serial reference: h = (h << 1) + gear[b] mod 2^32."""
    data = np.asarray(data, dtype=np.uint8)
    out = np.empty(len(data), dtype=np.uint32)
    h = 0
    for i, b in enumerate(data):
        h = ((h << 1) + int(GEAR_TABLE[b])) & 0xFFFFFFFF
        out[i] = h
    return out


def rabin_fps_np(data: np.ndarray, window: int = RABIN_WINDOW) -> np.ndarray:
    """Windowed polynomial (Rabin-style) fingerprints at every position.

    fp_i = sum_{k=0..w-1} b_{i-k} * p^k  (mod 2^32); positions < w-1 cover a
    shorter (warm-up) window, matching a serial rolling implementation that
    starts from 0.
    """
    data = np.asarray(data, dtype=np.uint8).astype(np.uint64)
    n = len(data)
    pows = poly_powers(window).astype(np.uint64)
    h = np.zeros(n, dtype=np.uint64)
    for k in range(min(window, n)):
        if k == 0:
            h += data * pows[0]
        else:
            h[k:] += data[: n - k] * pows[k]
    return (h & 0xFFFFFFFF).astype(np.uint32)


def poly_hash_np(data: np.ndarray) -> int:
    """Whole-buffer polynomial hash: h = h*p + b (uint32). Sub-chunk LSH."""
    h = 0
    p = int(POLY_P)
    for b in np.asarray(data, dtype=np.uint8):
        h = (h * p + int(b)) & 0xFFFFFFFF
    return h


def segment_poly_hashes_np(data: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """Polynomial hash of each segment [bounds[i], bounds[i+1]).

    Prefix-sum formulation (exactly poly_hash of each segment):
        S_i = sum_{j<i} b_j * p^{-(j+1)}           (mod 2^32)
        hash(l, r) = (S_r - S_l) * p^r             (mod 2^32)
    """
    data = np.asarray(data, dtype=np.uint8).astype(np.uint64)
    n = len(data)
    pinv = int(POLY_P_INV)
    # p^{-(j+1)} for j = 0..n-1
    ipows = np.empty(n, dtype=np.uint64)
    acc = pinv
    for j in range(n):
        ipows[j] = acc
        acc = (acc * pinv) & 0xFFFFFFFF
    contrib = (data * ipows) & 0xFFFFFFFF
    S = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(contrib, out=S[1:])
    S &= 0xFFFFFFFF
    pows = poly_powers(n + 1).astype(np.uint64)
    b = np.asarray(bounds, dtype=np.int64)
    seg = ((S[b[1:]] - S[b[:-1]]) & 0xFFFFFFFF) * pows[b[1:]]
    return (seg & 0xFFFFFFFF).astype(np.uint32)


# ----------------------------------------------------------------------------
# jnp implementations (oracles for the Pallas kernels; also usable directly)
# ----------------------------------------------------------------------------

def windowed_weighted_sum_j(g: jax.Array, weights: np.ndarray) -> jax.Array:
    """h_i = sum_k weights[k] * g_{i-k} (uint32 wraparound), pure jnp.

    `g` is any uint32 stream ([n] or [..., n]); `weights` a host-side uint32
    vector of taps. This is the shared oracle for both the gear-hash and the
    rabin-fingerprint kernels.
    """
    g = g.astype(jnp.uint32)
    n = g.shape[-1]
    h = jnp.zeros_like(g)
    for k, w in enumerate(np.asarray(weights, dtype=np.uint32)):
        term = g * jnp.uint32(w)
        if k:
            pad = [(0, 0)] * (g.ndim - 1) + [(k, 0)]
            term = jnp.pad(term, pad)[..., :n]
        h = h + term
    return h


def gear_hashes_j(data: jax.Array) -> jax.Array:
    g = jnp.asarray(GEAR_TABLE)[data.astype(jnp.int32)]
    return windowed_weighted_sum_j(g, GEAR_WEIGHTS)


def rabin_fps_j(data: jax.Array, window: int = RABIN_WINDOW) -> jax.Array:
    return windowed_weighted_sum_j(data.astype(jnp.uint32), poly_powers(window))


# Multiply-shift universal hashing (used by shingle feature embedding).
_MS_SEED = 0xD00DFEED


def multiply_shift_params(m: int, seed: int = _MS_SEED) -> tuple[np.ndarray, np.ndarray]:
    """M pairs (a, b): h_i(x) = a_i * x + b_i (uint32, high bits are best)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    a = rng.integers(1, 2**32, size=m, dtype=np.uint32) | np.uint32(1)  # odd
    b = rng.integers(0, 2**32, size=m, dtype=np.uint32)
    return a, b


def multiply_shift_unit_j(x: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Map uint32 x [..., 1] through M hash funcs -> float32 in [-1, 1).

    out[..., i] = int32(a_i * x + b_i) / 2^31
    """
    h = x[..., None] * a + b  # uint32 wraparound
    return h.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0**-31)
