"""Public jit'd wrappers around the Pallas kernels.

On TPU the kernels compile natively (Mosaic); on CPU, the test platform,
they run in interpret=True mode (the kernel body executed op-by-op). No
other platform has a path: it is an error, not a silent fallback.
`ref.py` holds the pure-jnp oracles used by tests and as large-input
fallbacks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import shingle_embed as _shingle
from repro.kernels import sim_topk as _topk


@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    # cached: jax.default_backend() walks the backend registry on every
    # call, and this gates every kernel dispatch on the ingest hot path
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on tpu (compiled) or cpu "
                           f"(interpret mode), not on {platform!r}")
    return platform == "cpu"


def shingle_embed(ids: jax.Array, mask: jax.Array, a: jax.Array, b: jax.Array,
                  normalize: bool = True) -> jax.Array:
    """[B, S] shingle ids + mask -> [B, M] initial features."""
    a2 = a.reshape(1, -1).astype(jnp.uint32)
    b2 = b.reshape(1, -1).astype(jnp.uint32)
    total = _shingle.shingle_embed_sum(ids, mask, a2, b2, interpret=_interpret())
    cnt = jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1).astype(jnp.float32)
    feat = total / cnt
    if normalize:
        feat = feat / (jnp.linalg.norm(feat, axis=-1, keepdims=True) + 1e-12)
    return feat


def sim_topk(q: jax.Array, index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[B, D] queries x [N, D] index -> (best score [B], best row [B])."""
    return _topk.sim_topk(q, index, interpret=_interpret())


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """Model-layout wrapper: q [B, Tq, H, hd], k/v [B, Tk, KV, hd]."""
    from repro.kernels import flash_attn as _fa
    out = _fa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)
