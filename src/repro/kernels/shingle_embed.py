"""Shingle -> M-dim feature embedding kernel (paper Algorithm 1, step 5).

For every (masked-unique) shingle id e: map through M multiply-shift hash
functions to a pseudo-random sub-vector in [-1, 1)^M, L2-normalize it, and
accumulate the sum over shingles:

    out[b, :] = sum_s mask[b,s] * msu(ids[b,s]) / ||msu(ids[b,s])||

(The divide-by-count and final normalization are cheap epilogues done by the
caller.) Blocked (Bb x Sb x M) so each tile lives in VMEM; the S grid axis is
innermost and accumulates into the same output block (TPU grid is
sequential), initialised at s == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shingle_embed_kernel(ids_ref, mask_ref, a_ref, b_ref, out_ref):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                       # [Bb, Sb] uint32
    mask = mask_ref[...]                     # [Bb, Sb] float32 (0/1)
    a = a_ref[...]                           # [1, M] uint32
    b = b_ref[...]                           # [1, M] uint32
    h = ids[:, :, None] * a[None, :, :] + b[None, :, :]   # [Bb, Sb, M] uint32
    v = h.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -31)
    norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True)) + jnp.float32(1e-12)
    v = v / norm * mask[:, :, None]
    out_ref[...] += jnp.sum(v, axis=1)


@functools.partial(jax.jit, static_argnames=("block_b", "block_s", "interpret"))
def shingle_embed_sum(ids: jax.Array, mask: jax.Array, a: jax.Array,
                      b: jax.Array, block_b: int = 8, block_s: int = 128,
                      interpret: bool = True) -> jax.Array:
    """ids/mask [B, S], a/b [1, M] -> unnormalized feature sums [B, M]."""
    bsz, s = ids.shape
    m = a.shape[-1]
    pad_b = (-bsz) % block_b
    pad_s = (-s) % block_s
    if pad_b or pad_s:
        ids = jnp.pad(ids, ((0, pad_b), (0, pad_s)))
        mask = jnp.pad(mask, ((0, pad_b), (0, pad_s)))
    bp, sp = ids.shape
    out = pl.pallas_call(
        _shingle_embed_kernel,
        grid=(bp // block_b, sp // block_s),
        in_specs=[
            pl.BlockSpec((block_b, block_s), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_s), lambda i, j: (i, j)),
            pl.BlockSpec((1, m), lambda i, j: (0, 0)),
            pl.BlockSpec((1, m), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, m), jnp.float32),
        interpret=interpret,
        name="shingle_embed",
    )(ids, mask.astype(jnp.float32), a, b)
    return out[:bsz]
