"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md §6).

Layer blocks are assigned to pipeline stages along a mesh axis (typically
"pod"); microbatches stream through the stages with collective_permute
hand-offs. Schedule: with S stages and M microbatches, the loop runs
M + S - 1 ticks; stage s works on microbatch t - s at tick t (bubble
fraction = (S-1)/(M+S-1), the standard GPipe trade).

The implementation is a shard_map over the pipeline axis: every device
holds ONE stage's parameters (leading stage axis sharded over the axis),
applies its stage, and ppermutes activations to the next stage. ppermute
is differentiable, so jax.grad pipelines the backward pass automatically
(reverse hand-offs).

    y = pipeline_apply(stage_fn, stage_params, x, mesh=mesh,
                       axis="pod", num_microbatches=8)

`stage_fn(params_s, x_mb) -> y_mb` must be shape-preserving (equal-width
stages), which matches the repeating-block structure of
models/transformer.py.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   *, mesh, axis: str = "pod",
                   num_microbatches: int | None = None) -> jax.Array:
    """x [B, ...] -> stacked stage_fn applications, pipelined over `axis`.

    stage_params: pytree with a leading [S] axis (S = mesh.shape[axis]).
    """
    s = mesh.shape[axis]
    b = x.shape[0]
    m = num_microbatches or s
    assert b % m == 0, (b, m)
    mb = b // m

    xs = x.reshape(m, mb, *x.shape[1:])
    perm = [(i, (i + 1) % s) for i in range(s)]

    def local(params_local, xs_local):
        # params_local: this stage's params (leading axis stripped to 1)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        ticks = m + s - 1

        def tick(carry, t):
            buf = carry                       # activation entering this stage
            inject = xs_local[jnp.minimum(t, m - 1)]
            cur = jnp.where(stage == 0, inject, buf)
            out = stage_fn(params_local, cur)
            nxt = jax.lax.ppermute(out, axis, perm)
            # last stage emits its result at ticks >= s-1
            emit = jnp.where((stage == s - 1) & (t >= s - 1), out,
                             jnp.zeros_like(out))
            return nxt, emit

        _, emits = jax.lax.scan(tick, jnp.zeros_like(xs_local[0]),
                                jnp.arange(ticks))
        # emits[t] holds microbatch t-(s-1); reorder to [M, mb, ...]
        ys = emits[s - 1:]
        return ys

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),        # params staged; microbatches replicated
        out_specs=P(axis),              # [S, M, mb, ...]; only last stage valid
        check_vma=False)
    stacked = fn(stage_params, xs)      # [S*M, mb, ...] (axis-concatenated)
    ys = stacked.reshape(s, m, mb, *x.shape[1:])[s - 1]
    return ys.reshape(b, *x.shape[1:])


def reference_apply(stage_fn: Callable, stage_params: Any, x: jax.Array) -> jax.Array:
    """Sequential oracle: apply every stage in order (tests)."""
    s = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    for i in range(s):
        p_i = jax.tree_util.tree_map(lambda p: p[i], stage_params)
        x = stage_fn(p_i, x)
    return x
