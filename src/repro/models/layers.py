"""Shared transformer layers: RMSNorm, RoPE (incl. partial/2D), GQA
attention (dense / chunked-online-softmax / cached-decode), SwiGLU & GeLU
MLPs, and sort-based token-dispatch MoE with expert parallelism.

Everything is pure functional JAX over plain dict pytrees; activation
sharding is annotated through repro.distributed.constrain (logical names),
so the same code runs single-device smoke tests and 512-chip dry-runs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import constrain

F32 = jnp.float32
NEG_INF = -1e30


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def pe(spec: str, x, w):
    """Projection einsum with bf16 collective boundaries.

    `preferred_element_type=x.dtype` makes the emitted dot produce the
    activation dtype directly, so GSPMD's cross-shard partial-sum
    all-reduces (row-parallel TP) and FSDP weight all-gathers move bf16
    instead of the dot's f32 accumulator — this halved grok-1's dominant
    collective term (EXPERIMENTS.md §Perf iteration 2). MXU accumulation
    stays f32 internally; only the reduce/network dtype changes.
    """
    return jnp.einsum(spec, x, w, preferred_element_type=x.dtype)


# -----------------------------------------------------------------------------
# init helpers
# -----------------------------------------------------------------------------

def dense_init(key, shape, in_axes=(0,), dtype=jnp.float32):
    fan_in = int(np.prod([shape[a] for a in in_axes]))
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


# -----------------------------------------------------------------------------
# RMSNorm
# -----------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(F32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(F32)).astype(x.dtype)


# -----------------------------------------------------------------------------
# RoPE (supports partial rotary — chatglm's rope_fraction=0.5 "2D RoPE")
# -----------------------------------------------------------------------------

def rope_freqs(head_dim: int, fraction: float, theta: float) -> np.ndarray:
    rot = int(head_dim * fraction) // 2 * 2
    return 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot)


def apply_rope(x: jax.Array, positions: jax.Array, fraction: float,
               theta: float) -> jax.Array:
    """x [B, T, H, hd]; positions [T] or [B, T]."""
    hd = x.shape[-1]
    rot = int(hd * fraction) // 2 * 2
    if rot == 0:
        return x
    freqs = jnp.asarray(rope_freqs(hd, fraction, theta))       # [rot/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(F32) * freqs           # [B, T, rot/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xr = x[..., :rot].astype(F32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rotated = jnp.stack([out1, out2], axis=-1).reshape(x[..., :rot].shape)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rot:]], axis=-1)


# -----------------------------------------------------------------------------
# Attention
# -----------------------------------------------------------------------------

def init_attention(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads, hd), dtype=dt),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, hd), dtype=dt),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, hd), dtype=dt),
        "wo": dense_init(ks[3], (cfg.num_heads, hd, d), in_axes=(0, 1), dtype=dt),
    }


def _dense_attention(q, k, v, *, causal: bool, q_offset=0) -> jax.Array:
    """q [B, Tq, H, hd], k/v [B, Tk, KV, hd] — scores materialized (train /
    decode paths; prefill uses the chunked version).

    bf16 operands with f32 score accumulation (preferred_element_type) and
    bf16 probabilities: softmax stats stay f32 for stability while the big
    [*, Tq, Tk] tensors move at half width (EXPERIMENTS.md §Perf)."""
    b, tq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, tq, kvh, g, hd) * jnp.asarray(hd ** -0.5, q.dtype)
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, k, preferred_element_type=F32)
    if causal:
        qpos = q_offset + jnp.arange(tq)[:, None]
        kpos = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((kpos <= qpos)[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqj,bjkd->bqkgd", p, v, preferred_element_type=F32)
    return out.reshape(b, tq, h, hd).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                       kv_block: int = 1024) -> jax.Array:
    """Online-softmax over KV blocks (forward-only prefill path; the Pallas
    flash_attn kernel implements the same schedule on TPU)."""
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    pad = (-tk) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = k.shape[1] // kv_block
    qg = q.reshape(b, tq, kvh, g, hd) * jnp.asarray(hd ** -0.5, q.dtype)
    qpos = q_offset + jnp.arange(tq)

    ks = k.reshape(b, nb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, j = blk
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qg, kb,
                       preferred_element_type=F32)
        kpos = j * kv_block + jnp.arange(kv_block)
        mask = (kpos[None, :] <= qpos[:, None]) if causal else \
            (kpos[None, :] < tk)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p.astype(q.dtype), vb,
            preferred_element_type=F32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, tq, 1), NEG_INF, F32)
    l0 = jnp.zeros((b, kvh, g, tq, 1), F32)
    a0 = jnp.zeros((b, kvh, g, tq, hd), F32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (ks, vs, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-20)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd).astype(q.dtype)


def attention(params, x, cfg, *, causal=True, kv_cache=None, pos=None,
              memory=None, rope=True):
    """Self- or cross-attention sublayer (projection + mixing + out-proj).

    kv_cache: {"k": [B, T_max, KV, hd], "v": ...} -> returns updated cache.
    memory:   [B, T_mem, D] for cross-attention (keys/values from memory).
    pos:      scalar position for single-token decode.
    """
    src = memory if memory is not None else x
    q = pe("btd,dhk->bthk", x, params["wq"])
    k = pe("btd,dhk->bthk", src, params["wk"])
    v = pe("btd,dhk->bthk", src, params["wv"])
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)

    if rope and memory is None:
        if pos is None:
            positions = jnp.arange(x.shape[1])
        else:
            positions = jnp.full((x.shape[0], x.shape[1]), pos)
        q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None:
        assert pos is not None
        ck = jax.lax.dynamic_update_slice(kv_cache["k"], k.astype(kv_cache["k"].dtype),
                                          (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(kv_cache["v"], v.astype(kv_cache["v"].dtype),
                                          (0, pos, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        k = constrain(k, "batch", "cache_seq", "kv_heads", None)
        v = constrain(v, "batch", "cache_seq", "kv_heads", None)
        out = _dense_attention(q, k, v, causal=True, q_offset=pos)
    elif memory is not None:
        out = _dense_attention(q, k, v, causal=False)
    elif x.shape[1] > 8192:
        out = _chunked_attention(q, k, v, causal=causal)
    else:
        out = _dense_attention(q, k, v, causal=causal)

    out = constrain(out, "batch", "seq", "heads", None)
    y = pe("bthk,hkd->btd", out, params["wo"])
    return constrain(y, "batch", "seq", "d_model"), new_cache


# -----------------------------------------------------------------------------
# MLP
# -----------------------------------------------------------------------------

def init_mlp(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    dt = dtype_of(cfg)
    if cfg.act == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w_gate": dense_init(k1, (d, f), dtype=dt),
                "w_up": dense_init(k2, (d, f), dtype=dt),
                "w_down": dense_init(k3, (f, d), dtype=dt)}
    k1, k2 = jax.random.split(key)
    return {"w_in": dense_init(k1, (d, f), dtype=dt),
            "w_down": dense_init(k2, (f, d), dtype=dt)}


def mlp(params, x, cfg):
    if "w_gate" in params:
        g = pe("btd,df->btf", x, params["w_gate"])
        u = pe("btd,df->btf", x, params["w_up"])
        h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    else:
        h = jax.nn.gelu(pe("btd,df->btf", x, params["w_in"]).astype(F32)).astype(x.dtype)
    h = constrain(h, "batch", "seq", "d_ff")
    y = pe("btf,fd->btd", h, params["w_down"])
    return constrain(y, "batch", "seq", "d_model")


# -----------------------------------------------------------------------------
# MoE: sort-based token dispatch with capacity (expert-parallel over "model")
# -----------------------------------------------------------------------------

def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = cfg.moe_ffn_shards
    ev, fv = e * s, f // s          # virtual-expert layout (exact, see moe())
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {"router": dense_init(ks[0], (d, e))}  # router kept fp32, logical E
    if cfg.act == "swiglu":
        p["e_gate"] = dense_init(ks[1], (ev, d, fv), in_axes=(1,), dtype=dt)
        p["e_up"] = dense_init(ks[2], (ev, d, fv), in_axes=(1,), dtype=dt)
    else:
        p["e_in"] = dense_init(ks[1], (ev, d, fv), in_axes=(1,), dtype=dt)
    p["e_down"] = dense_init(ks[3], (ev, fv, d), in_axes=(1,), dtype=dt)
    return p


def _route_and_dispatch(xt, router, e, k, cap, shards: int = 1):
    """Local (per-device) routing: top-k -> slot positions -> [E_v, C, D] buf.

    Pure local ops (cumsum position counters + scatter) — no sort, no
    cross-device traffic; capacity overflow drops (GShard semantics).
    With `shards` > 1 each logical choice fans out to `shards` half-width
    virtual experts carrying the SAME gate (their outputs sum to the full
    expert's output exactly — hidden units are independent).
    Returns (buf, slot, st, gate_flat, keep, probs, expert).
    """
    t, d = xt.shape
    logits = jnp.einsum("td,de->te", xt.astype(F32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)                    # [T, k] logical
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    ev, kv = e * shards, k * shards
    if shards > 1:
        expert_v = (expert[..., None] * shards
                    + jnp.arange(shards)).reshape(t, kv)      # [T, k*s]
        gate_v = jnp.repeat(gate, shards, axis=-1)
    else:
        expert_v, gate_v = expert, gate

    flat_e = expert_v.reshape(-1)                             # [T*kv] token-major
    oh = jax.nn.one_hot(flat_e, ev, dtype=jnp.int32)          # [T*kv, E_v]
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]  # pos within expert
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, ev * cap)      # OOB -> dropped
    st = jnp.arange(t * kv, dtype=jnp.int32) // kv
    buf = jnp.zeros((ev * cap + 1, d), xt.dtype).at[slot].set(xt[st])[:-1]
    return buf.reshape(ev, cap, d), slot, st, gate_v.reshape(-1), keep, probs, expert


def _combine(y_flat, slot, st, gate_flat, keep, t, d):
    """Inverse of dispatch: gather per-assignment outputs, weight, sum over k."""
    pad = jnp.concatenate([y_flat, jnp.zeros((1, d), y_flat.dtype)])
    contrib = pad[slot]                                       # [T*k, D]
    w = (gate_flat * keep).astype(F32)[:, None]
    return jnp.zeros((t, d), F32).at[st].add(contrib.astype(F32) * w)


def _expert_ffn(params, h, act, f32=F32):
    if "e_gate" in params:
        g = pe("ecd,edf->ecf", h, params["e_gate"])
        u = pe("ecd,edf->ecf", h, params["e_up"])
        a = jax.nn.silu(g.astype(f32)).astype(h.dtype) * u
    else:
        a = jax.nn.gelu(pe("ecd,edf->ecf", h, params["e_in"]).astype(f32)).astype(h.dtype)
    return pe("ecf,efd->ecd", a, params["e_down"])


def moe(params, x, cfg):
    """Top-k routed experts with capacity. Two distributed modes
    (DESIGN.md §6), both built on shard_map so dispatch stays local:

      * "ep" (num_experts % model-axis == 0, e.g. qwen3/jamba): experts are
        sharded over "model"; tokens are split over every mesh axis, routed
        locally, exchanged with ONE all_to_all pair over "model", expert
        FFNs run fully local.
      * "tp" (grok-1's 8 experts on a 16-way axis): expert FFNs are
        tensor-parallel over "model" (d_ff sharded); tokens dispatch
        locally per data shard and the row-parallel e_down psums over
        "model".

    Outside a mesh context (CPU smoke tests) the same local dispatch runs
    without collectives.
    """
    from repro.distributed import sharding as shd

    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    vs = cfg.moe_ffn_shards
    ev, kv = e * vs, k * vs
    mesh = shd.current_mesh()
    rules = shd.current_rules()

    if mesh is None or "model" not in mesh.shape:
        t = b * s
        cap = min(int(np.ceil(t * kv * cfg.capacity_factor / ev)), t)
        xt = x.reshape(t, d)
        buf, slot, st, gf, keep, probs, expert = _route_and_dispatch(
            xt, params["router"], e, k, cap, vs)
        y = _expert_ffn(params, buf, cfg.act).reshape(ev * cap, d)
        out = _combine(y, slot, st, gf, keep, t, d)
        aux = _load_balance_loss(probs, expert, e, k)
        return out.astype(x.dtype).reshape(b, s, d), aux

    from jax.sharding import PartitionSpec as P

    m_ax = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = int(np.prod([mesh.shape[a] for a in batch_axes]))
    mode = rules.moe_mode if rules else ("ep" if ev % m_ax == 0 else "tp")

    # token split: batch over dp; seq additionally over model in EP mode
    seq_split = m_ax if (mode == "ep" and s % m_ax == 0) else 1
    x_spec = P(batch_axes if b % dp == 0 else None,
               "model" if seq_split > 1 else None, None)
    b_loc = b // dp if b % dp == 0 else b
    t_loc = b_loc * (s // seq_split)
    cap = max(1, int(np.ceil(t_loc * kv * cfg.capacity_factor / ev)))

    def _wspec(n):
        if mode == "ep":
            return P("model", None, None)
        # TP: d_ff axis over model — e_down is [E, F, D], others [E, D, F]
        return P(None, "model", None) if n == "e_down" else P(None, None, "model")

    wspecs = {n: _wspec(n) for n in params if n.startswith("e_")}
    in_specs = (x_spec, P(None, None),
                tuple(wspecs[n] for n in sorted(wspecs)))
    out_specs = (x_spec, P())
    enames = sorted(wspecs)

    def local_fn(x_loc, router, ws):
        wp = dict(zip(enames, ws))
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        buf, slot, st, gf, keep, probs, expert = _route_and_dispatch(
            xt, router, e, k, cap, vs)
        if mode == "ep":
            # send each expert's slice to its owner; receive from all peers
            recv = jax.lax.all_to_all(buf, "model", split_axis=0,
                                      concat_axis=1, tiled=True)  # [Ev/m, m*C, D]
            y = _expert_ffn(wp, recv, cfg.act)
            back = jax.lax.all_to_all(y, "model", split_axis=1,
                                      concat_axis=0, tiled=True)  # [Ev, C, D]
        else:
            y = _expert_ffn(wp, buf, cfg.act)  # F sharded over model
            back = jax.lax.psum(y, "model")     # row-parallel e_down
        out = _combine(back.reshape(ev * cap, d), slot, st, gf, keep, t, d)
        aux = _load_balance_loss(probs, expert, e, k)
        axes = batch_axes + (("model",) if seq_split > 1 or mode == "tp" else ())
        aux = jax.lax.pmean(aux, axes) if axes else aux
        if mode == "tp":  # identical across model columns already (psum'd y)
            pass
        return out.astype(x_loc.dtype).reshape(bl, sl, d), aux

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, aux = fn(x, params["router"],
                  tuple(params[n] for n in enames))
    return constrain(out, "batch", "seq", "d_model"), aux


def _load_balance_loss(probs, expert, e, k):
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    onehot = jax.nn.one_hot(expert, e, dtype=F32).sum(1)      # [T, E]
    f = onehot.mean(0) / k
    p = probs.mean(0)
    return e * jnp.sum(f * p)
