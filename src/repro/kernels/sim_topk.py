"""Tiled cosine-similarity top-1 kernel (resemblance search).

score = q @ index^T with a running (max, argmax) — the flash-attention
online-max trick applied to similarity search (DESIGN.md §3): index tiles
stream through VMEM and the [B, N] score matrix never exists in HBM.

Grid = (B blocks, N blocks), N innermost; the output block depends only on
the B index, so the running best accumulates across the sequential N steps.
Padding rows of the index are masked to -inf via the static `n_valid`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sim_topk_kernel(q_ref, idx_ref, best_ref, arg_ref, *, block_n: int,
                     n_valid: int):
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    q = q_ref[...]                            # [Bb, D]
    idx = idx_ref[...]                        # [Nb, D]
    scores = jax.lax.dot_general(
        q, idx, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # [Bb, Nb]
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + nj * block_n
    scores = jnp.where(col < n_valid, scores, -jnp.inf)
    loc_arg = jnp.argmax(scores, axis=1).astype(jnp.int32)
    loc_max = jnp.max(scores, axis=1)
    prev = best_ref[:, 0]
    take = loc_max > prev
    best_ref[:, 0] = jnp.where(take, loc_max, prev)
    arg_ref[:, 0] = jnp.where(take, loc_arg + nj * block_n, arg_ref[:, 0])


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def sim_topk(q: jax.Array, index: jax.Array, block_b: int = 8,
             block_n: int = 1024, interpret: bool = True
             ) -> tuple[jax.Array, jax.Array]:
    """q [B, D] x index [N, D] -> (best score [B], best row id [B] int32)."""
    bsz, d = q.shape
    n = index.shape[0]
    block_n = min(block_n, max(128, 1 << (n - 1).bit_length()))
    pad_b = (-bsz) % block_b
    pad_n = (-n) % block_n
    if pad_b:
        q = jnp.pad(q, ((0, pad_b), (0, 0)))
    if pad_n:
        index = jnp.pad(index, ((0, pad_n), (0, 0)))
    bp, np_ = q.shape[0], index.shape[0]
    kernel = functools.partial(_sim_topk_kernel, block_n=block_n, n_valid=n)
    best, arg = pl.pallas_call(
        kernel,
        grid=(bp // block_b, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
        name="sim_topk",
    )(q, index)
    return best[:bsz, 0], arg[:bsz, 0]
