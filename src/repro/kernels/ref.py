"""Pure-jnp oracles for the Pallas kernels in this package.

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
The ``gear_scan`` kernel's oracle is the numpy ``hashing.gear_hashes_np``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.features import embed_shingles_j


def shingle_embed_ref(ids: jax.Array, mask: jax.Array, a: jax.Array,
                      b: jax.Array) -> jax.Array:
    """Masked normalized-sub-vector sum (unnormalized; callers normalize)."""
    return embed_shingles_j(ids, mask, a, b, normalize=False)


def sim_topk_ref(q: jax.Array, index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """q [B, D], index [N, D] -> (best score [B], best row [B])."""
    scores = q @ index.T
    arg = jnp.argmax(scores, axis=1)
    best = jnp.take_along_axis(scores, arg[:, None], axis=1)[:, 0]
    return best, arg.astype(jnp.int32)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """[B, H, Tq, Dh] x [B, Hkv, Tk, Dh] -> [B, H, Tq, Dh], GQA-aware."""
    b, h, tq, dh = q.shape
    hkv = k.shape[1]
    group = h // hkv
    qg = q.reshape(b, hkv, group, tq, dh)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(dh)
    if causal:
        tk = k.shape[2]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, tq, dh).astype(q.dtype)
