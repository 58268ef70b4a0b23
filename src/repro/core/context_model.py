"""BP-neural-network chunk-context aware model (paper §4.3).

Word2vec-CBOW-shaped two-matrix linear network:

    Formula 1:  h_i       = (1/2K) * (sum of 2K context features) @ W     [D]
    Formula 2:  out_i     = (1/2K) * h_i @ U                              [M]
    Formula 3:  vector'_j = 2K * vector_j @ pinv(U)                       [D]

The paper trains with "hierarchical softmax"; its labels are continuous
M-dim feature vectors, so we implement the continuous reading — cosine+MSE
regression of `out_i` against the target chunk's initial feature — and an
optional sampled-softmax over LSH-bucketed chunk ids (DESIGN.md §1).
`pinv` replaces the paper's U^{-1} (U is D x M, non-square).

Training is plain JAX and pjit-shardable (batch -> data axis, D -> model
axis); for the chunk volumes in the paper's experiments a single host is
plenty, but the same step function runs on the production mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim


@dataclasses.dataclass(frozen=True)
class ContextModelConfig:
    m: int = 64           # initial feature dim (paper M)
    d: int = 50           # context-aware feature dim (paper D; 40..80 in Tab.1)
    k: int = 2            # context half width -> 2K surrounding chunks
    lr: float = 3e-3
    steps: int = 300
    batch_size: int = 256
    mse_weight: float = 1.0
    cos_weight: float = 1.0
    seed: int = 0


class ContextModelParams(NamedTuple):
    w: jax.Array  # [M, D]
    u: jax.Array  # [D, M]


def init_params(cfg: ContextModelConfig) -> ContextModelParams:
    kw, ku = jax.random.split(jax.random.PRNGKey(cfg.seed))
    scale_w = 1.0 / np.sqrt(cfg.m)
    scale_u = 1.0 / np.sqrt(cfg.d)
    return ContextModelParams(
        w=jax.random.normal(kw, (cfg.m, cfg.d), jnp.float32) * scale_w,
        u=jax.random.normal(ku, (cfg.d, cfg.m), jnp.float32) * scale_u,
    )


def forward(params: ContextModelParams, ctx_mean: jax.Array) -> jax.Array:
    """ctx_mean [B, M] (already the 1/2K-scaled context sum) -> out [B, M]."""
    h = ctx_mean @ params.w                    # Formula 1
    return h @ params.u                        # Formula 2 (1/2K folded in)


def loss_fn(params: ContextModelParams, ctx_mean: jax.Array,
            target: jax.Array, cfg: ContextModelConfig) -> jax.Array:
    out = forward(params, ctx_mean)
    mse = jnp.mean(jnp.sum(jnp.square(out - target), axis=-1))
    tn = target / (jnp.linalg.norm(target, axis=-1, keepdims=True) + 1e-9)
    on = out / (jnp.linalg.norm(out, axis=-1, keepdims=True) + 1e-9)
    cos = jnp.mean(1.0 - jnp.sum(tn * on, axis=-1))
    return cfg.mse_weight * mse + cfg.cos_weight * cos


def make_training_pairs(features: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(ctx_mean [T, M], target [T, M]) from the stream-ordered feature seq.

    Context of chunk i = the k chunks before and k after, edge-truncated
    (mean over however many neighbours exist); this matches "the surrounding
    co-occurring 2K chunks" with the 1/2K scale of Formulas 1-2.
    """
    t, m = features.shape
    ctx_sum = np.zeros((t, m), np.float32)
    ctx_cnt = np.zeros((t, 1), np.float32)
    for off in range(1, k + 1):
        ctx_sum[off:] += features[:-off]
        ctx_cnt[off:] += 1
        ctx_sum[:-off] += features[off:]
        ctx_cnt[:-off] += 1
    return ctx_sum / np.maximum(ctx_cnt, 1.0), features.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "tx"))
def _train_step(params, opt_state, ctx, tgt, cfg, tx):
    loss, grads = jax.value_and_grad(loss_fn)(params, ctx, tgt, cfg)
    deltas, opt_state = tx.update(grads, opt_state, params)
    params = optim.apply_updates(params, deltas)
    return params, opt_state, loss


@functools.partial(jax.jit, static_argnames=("scale",))
def _project(f: jax.Array, u_pinv: jax.Array, scale: int) -> jax.Array:
    """Formula 3 as one device program: [*, M] -> L2-normalized [*, D]."""
    v = scale * (f @ u_pinv)
    return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-12)


class ContextModel:
    """Train-then-predict wrapper used by the dedup pipeline."""

    def __init__(self, cfg: ContextModelConfig | None = None):
        self.cfg = cfg or ContextModelConfig()
        self.params: ContextModelParams | None = None
        self._u_pinv: jax.Array | None = None
        self.losses: list[float] = []

    def fit(self, stream_features: np.ndarray) -> "ContextModel":
        cfg = self.cfg
        ctx, tgt = make_training_pairs(np.asarray(stream_features, np.float32), cfg.k)
        params = init_params(cfg)
        tx = optim.adamw(cfg.lr, weight_decay=0.0)
        opt_state = tx.init(params)
        rng = np.random.Generator(np.random.PCG64(cfg.seed))
        n = ctx.shape[0]
        bs = min(cfg.batch_size, n)
        ctx_j, tgt_j = jnp.asarray(ctx), jnp.asarray(tgt)
        for step in range(cfg.steps):
            idx = jnp.asarray(rng.integers(0, n, size=bs))
            params, opt_state, loss = _train_step(
                params, opt_state, ctx_j[idx], tgt_j[idx], cfg, tx)
            self.losses.append(float(loss))
        self.params = params
        # Formula 3's U^{-1}: Moore-Penrose with small singular values
        # truncated — raw pinv amplifies feature noise along rarely-used
        # output directions, destroying similarity (rtol chosen by the
        # sweep in benchmarks/bench_ablation.py).
        self._u_pinv = jnp.linalg.pinv(params.u, rtol=0.1)  # [M, D]
        return self

    def transform(self, features: np.ndarray | jax.Array) -> np.ndarray:
        """Formula 3: initial feature [*, M] -> context-aware feature [*, D].

        Output is L2-normalized (search runs on cosine similarity).
        """
        assert self.params is not None, "fit() first"
        f = jnp.asarray(features, jnp.float32)
        return np.asarray(_project(f, self._u_pinv, 2 * self.cfg.k))
