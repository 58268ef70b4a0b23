"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Tensors are annotated with *logical* axis names; a `ShardingRules` table maps
each name to mesh axes (or None = replicated). Swapping the table is how the
perf hillclimb changes layouts without touching model code (EXPERIMENTS.md
§Perf), and how decode cells get different layouts than train cells.

GSPMD pads uneven partitions, so rules may map e.g. 40 heads onto a 16-way
axis; rules chosen per-arch avoid the wasteful cases (see default_rules).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Axes = Any  # None | str | tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axes (None = replicate)."""
    # activation axes
    batch: Axes = ("pod", "data")
    seq: Axes = None            # sequence parallelism when set
    d_model: Axes = None
    heads: Axes = "model"
    kv_heads: Axes = None
    head_dim: Axes = None
    d_ff: Axes = "model"
    vocab: Axes = "model"
    expert: Axes = "model"
    capacity: Axes = None
    cache_seq: Axes = None      # KV-cache / SSM-state seq axis (long-context SP)
    frames: Axes = None         # audio/vision memory tokens
    state: Axes = None          # SSM state dim
    # parameter axes
    p_vocab: Axes = "model"
    p_d_model: Axes = None      # FSDP shards this over "data"
    p_heads: Axes = "model"
    p_kv_heads: Axes = None
    p_d_ff: Axes = "model"
    p_expert: Axes = "model"
    p_moe_ff: Axes = None
    p_ssm_inner: Axes = "model"
    # MoE execution mode: "ep" (experts sharded over model, all_to_all
    # dispatch) when num_experts % model_axis == 0, else "tp" (expert FFNs
    # tensor-parallel over model, local dispatch) — see models/layers.moe.
    moe_mode: str = "ep"

    def get(self, name: str) -> Axes:
        return getattr(self, name)


def default_rules(cfg=None, *, multi_pod: bool = False, fsdp: bool = False,
                  decode: bool = False, seq_shard: bool = False) -> ShardingRules:
    """Per-arch / per-shape sensible defaults.

    * TP shards Q heads / FFN / vocab over "model"; KV heads shard only when
      they divide the axis (GQA with few KV heads replicates them instead of
      paying GSPMD padding on the KV cache).
    * FSDP additionally shards the d_model param axis over "data" (ZeRO-3;
      optimizer state follows params automatically).
    * decode: batch stays on ("pod","data"); the KV-cache sequence axis is
      sharded over "model" (sequence-parallel decode: no arch's KV-head
      count divides 16, so seq is the productive cache axis — attention
      does partial softmax per shard + a small all-reduce).
    """
    batch = ("pod", "data") if multi_pod else ("data",)
    kv_ok = bool(cfg and cfg.num_kv_heads and cfg.num_kv_heads % 16 == 0)
    ep_ok = bool(cfg is None or not cfg.num_experts
                 or (cfg.num_experts * getattr(cfg, "moe_ffn_shards", 1)) % 16 == 0)
    return ShardingRules(
        batch=batch,
        kv_heads="model" if kv_ok else None,
        p_kv_heads="model" if kv_ok else None,
        # FSDP spans the pod axis too on multi-pod meshes: optimizer state
        # per chip halves with every pod added (grok-1: 12.3 -> 6.2 GB/chip)
        p_d_model=(("pod", "data") if multi_pod else ("data",)) if fsdp else None,
        cache_seq=("model" if not kv_ok else None) if decode else None,
        heads="model", p_heads="model",
        moe_mode="ep" if ep_ok else "tp",
        p_expert="model" if ep_ok else None,
        p_moe_ff=None if ep_ok else "model",
    )


_ACTIVE: contextvars.ContextVar[Optional[ShardingRules]] = \
    contextvars.ContextVar("sharding_rules", default=None)
_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("sharding_mesh", default=None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules], mesh: Optional[Mesh] = None):
    tok = _ACTIVE.set(rules)
    tok_m = _ACTIVE_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVE.reset(tok)
        _ACTIVE_MESH.reset(tok_m)


def current_rules() -> Optional[ShardingRules]:
    return _ACTIVE.get()


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH.get()


def _flatten(axes_list: tuple[Axes, ...]) -> P:
    out = []
    for a in axes_list:
        if isinstance(a, (list, tuple)):
            a = tuple(x for x in a if x is not None) or None
            if a is not None and len(a) == 1:
                a = a[0]
        out.append(a)
    return P(*out)


def activation_spec(*logical: Optional[str], rules: ShardingRules | None = None) -> P:
    rules = rules or _ACTIVE.get()
    assert rules is not None
    return _flatten(tuple(None if n is None else rules.get(n) for n in logical))


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside use_rules()."""
    rules = _ACTIVE.get()
    if rules is None:
        return x
    spec = activation_spec(*logical, rules=rules)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter specs: leaf-name -> logical axes (leading stacked-layer axis is
# added automatically for block params).
# ---------------------------------------------------------------------------

_PARAM_AXES: dict[str, tuple[Optional[str], ...]] = {
    "embed": ("p_vocab", "p_d_model"),
    "lm_head": ("p_d_model", "p_vocab"),
    "pos_embed": (None, "p_d_model"),
    # attention
    "wq": ("p_d_model", "p_heads", None),
    "wk": ("p_d_model", "p_kv_heads", None),
    "wv": ("p_d_model", "p_kv_heads", None),
    "wo": ("p_heads", None, "p_d_model"),
    # dense mlp
    "w_gate": ("p_d_model", "p_d_ff"),
    "w_up": ("p_d_model", "p_d_ff"),
    "w_in": ("p_d_model", "p_d_ff"),
    "w_down": ("p_d_ff", "p_d_model"),
    # moe
    "router": ("p_d_model", None),
    "e_gate": ("p_expert", "p_d_model", "p_moe_ff"),
    "e_up": ("p_expert", "p_d_model", "p_moe_ff"),
    "e_in": ("p_expert", "p_d_model", "p_moe_ff"),
    "e_down": ("p_expert", "p_moe_ff", "p_d_model"),
    # ssm (mamba2)
    "in_proj": ("p_d_model", "p_ssm_inner"),
    "conv_w": (None, "p_ssm_inner"),
    "conv_b": ("p_ssm_inner",),
    "a_log": (None,),
    "dt_bias": (None,),
    "out_proj": ("p_ssm_inner", "p_d_model"),
    # norms / scalars
    "scale": (None,),
    "norm": (None,),
}


def _spec_for_leaf(name: str, ndim: int, rules: ShardingRules) -> P:
    axes = _PARAM_AXES.get(name)
    if axes is None:
        return P()  # replicate unknown leaves
    pad = ndim - len(axes)
    full = (None,) * pad + tuple(axes)  # leading stacked-layer axes replicate
    return _flatten(tuple(None if a is None else rules.get(a) for a in full))


_CACHE_AXES: dict[str, tuple[Optional[str], ...]] = {
    # leading n_rep axis is handled by padding, like stacked params
    "k": ("batch", "cache_seq", "kv_heads", None),
    "v": ("batch", "cache_seq", "kv_heads", None),
    "conv": ("batch", None, "p_ssm_inner"),
    "h": ("batch", "p_ssm_inner", None, None),
    "pos": (),
}


def cache_pspecs(cache_tree: Any, rules: ShardingRules) -> Any:
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    specs = []
    for path, leaf in flat:
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        axes = _CACHE_AXES.get(name or "")
        if axes is None:
            specs.append(P())
            continue
        ndim = getattr(leaf, "ndim", 0)
        pad = ndim - len(axes)
        full = (None,) * pad + tuple(axes)
        specs.append(_flatten(tuple(None if a is None else rules.get(a)
                                    for a in full)))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_pspecs(params_tree: Any, rules: ShardingRules) -> Any:
    """PartitionSpec pytree mirroring `params_tree` (works on shape structs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
    specs = []
    for path, leaf in flat:
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        specs.append(_spec_for_leaf(name or "", getattr(leaf, "ndim", 0), rules))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


def _axes_size(ax: Axes, mesh: Mesh) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        return mesh.shape[ax]
    n = 1
    for a in ax:
        n *= mesh.shape[a]
    return n


def _as_tuple(ax: Axes) -> tuple:
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def sanitize_pspecs(shapes_tree: Any, specs_tree: Any, mesh: Mesh) -> Any:
    """Make specs legal as pjit INPUT shardings (exact divisibility).

    Interior with_sharding_constraint tolerates uneven shards (GSPMD pads),
    but pjit argument shardings must divide. For each leaf dim whose size
    the assigned axes do not divide, the axes are shifted to the next dim
    if that works (e.g. 40 heads on a 16-way axis -> shard head_dim), else
    dropped (e.g. vocab 51865 -> replicate).
    """
    import numpy as np

    def fix(shape_leaf, spec):
        if not isinstance(spec, P):
            return spec
        dims = tuple(getattr(shape_leaf, "shape", ()) or ())
        entries = list(spec) + [None] * (len(dims) - len(spec))
        out = [list(_as_tuple(e)) for e in entries]
        for i in range(len(dims)):
            keep = []
            for ax in list(out[i]):
                cur = int(np.prod([mesh.shape[a] for a in keep] or [1]))
                if dims[i] % (cur * mesh.shape[ax]) == 0:
                    keep.append(ax)
                else:
                    # shift to the next dim only if it is currently
                    # unsharded (e.g. heads -> head_dim); never pile axes
                    # onto an already-sharded dim
                    if i + 1 < len(dims) and not out[i + 1]:
                        if dims[i + 1] % mesh.shape[ax] == 0:
                            out[i + 1].append(ax)
            out[i] = keep
        cleaned = tuple(None if not e else (e[0] if len(e) == 1 else tuple(e))
                        for e in out)
        return P(*cleaned)

    return jax.tree_util.tree_map(fix, shapes_tree, specs_tree,
                                  is_leaf=lambda x: isinstance(x, P))
