"""Declarative parameter specs for every architecture family.

Each parameter is described once as a ``ParamSpec`` (shape, logical sharding
axes, init, dtype). From the spec tree we derive, without ever allocating the
full model:
  * ``jax.ShapeDtypeStruct`` trees (for the multi-pod dry-run),
  * ``NamedSharding`` trees via ``repro.parallel.sharding`` logical rules,
  * real initialized params (for smoke tests / the ~100M example run),
  * parameter counts (for 6ND roofline math).

The spec tree and the runtime param tree share the exact same dict structure;
``repro.models.layers`` and ``repro.models.attention`` index both identically.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.arch import ArchConfig


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | lru_a | rope_none
    scale: Optional[float] = None
    dtype: Optional[str] = None  # None -> cfg.dtype; norms/gates are fp32

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


Tree = Dict[str, Any]


def _norm(d: int) -> Tree:
    return {"scale": ParamSpec((d,), (None,), init="ones", dtype="float32")}


def _mlp_specs(cfg: ArchConfig, d_ff: int) -> Tree:
    d = cfg.d_model
    return {
        "wg": ParamSpec((d, d_ff), ("embed", "mlp")),
        "wu": ParamSpec((d, d_ff), ("embed", "mlp")),
        "wd": ParamSpec((d_ff, d), ("mlp", "embed"), scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _gqa_specs(cfg: ArchConfig, cross: bool = False) -> Tree:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    t: Tree = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"),
                        scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qk_norm and not cross:
        t["q_norm"] = _norm(hd)
        t["k_norm"] = _norm(hd)
    return t


def _mla_specs(cfg: ArchConfig) -> Tree:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    return {
        "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "lora")),
        "q_a_norm": _norm(m.q_lora_rank),
        "wq_b": ParamSpec((m.q_lora_rank, h, dn + dr), ("lora", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank), ("embed", "lora")),
        "kv_a_norm": _norm(m.kv_lora_rank),
        "wk_rope": ParamSpec((d, dr), ("embed", None)),
        "wk_nope": ParamSpec((m.kv_lora_rank, h, dn), ("lora", "heads", "head_dim")),
        "wv": ParamSpec((m.kv_lora_rank, h, dv), ("lora", "heads", "head_dim")),
        "wo": ParamSpec((h, dv, d), ("heads", "head_dim", "embed"),
                        scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _moe_specs(cfg: ArchConfig) -> Tree:
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.num_experts, mo.d_expert
    held = mo.num_experts_held or e      # the router still scores all e
    t: Tree = {
        "router": ParamSpec((d, e), ("embed", None), dtype="float32"),
        "wg": ParamSpec((held, d, f), ("experts", "embed", "mlp")),
        "wu": ParamSpec((held, d, f), ("experts", "embed", "mlp")),
        "wd": ParamSpec((held, f, d), ("experts", "mlp", "embed"),
                        scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if mo.router_score == "sigmoid":
        t["router_bias"] = ParamSpec((e,), (None,), init="zeros", dtype="float32")
    if mo.num_shared_experts > 0:
        t["shared"] = _mlp_specs(cfg, mo.num_shared_experts * mo.d_expert)
    return t


def _rglru_specs(cfg: ArchConfig) -> Tree:
    r = cfg.rglru
    d = cfg.d_model
    width = r.lru_width or d
    nb = cfg.num_heads                 # block-diagonal gate blocks
    bs = width // nb
    return {
        "wx": ParamSpec((d, width), ("embed", "mlp")),
        "wy": ParamSpec((d, width), ("embed", "mlp")),
        "conv_w": ParamSpec((r.conv_width, width), (None, "mlp")),
        "conv_b": ParamSpec((width,), ("mlp",), init="zeros"),
        "gate_r_w": ParamSpec((nb, bs, bs), ("heads", None, None)),
        "gate_r_b": ParamSpec((width,), ("mlp",), init="zeros"),
        "gate_i_w": ParamSpec((nb, bs, bs), ("heads", None, None)),
        "gate_i_b": ParamSpec((width,), ("mlp",), init="zeros"),
        "a_param": ParamSpec((width,), ("mlp",), init="lru_a", dtype="float32"),
        "wo": ParamSpec((width, d), ("mlp", "embed"),
                        scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _mlstm_specs(cfg: ArchConfig) -> Tree:
    x = cfg.xlstm
    d = cfg.d_model
    inner = int(x.mlstm_proj_factor * d)
    nh = x.num_heads
    d_v = inner // nh
    d_qk = int(x.qk_dim_factor * d_v)
    return {
        "w_up": ParamSpec((d, 2, inner), ("embed", None, "mlp")),
        "conv_w": ParamSpec((4, inner), (None, "mlp")),
        "conv_b": ParamSpec((inner,), ("mlp",), init="zeros"),
        "wq": ParamSpec((inner, nh, d_qk), ("mlp", "heads", None)),
        "wk": ParamSpec((inner, nh, d_qk), ("mlp", "heads", None)),
        "wv": ParamSpec((inner, nh, d_v), ("mlp", "heads", None)),
        "w_igate": ParamSpec((inner, nh), ("mlp", "heads"), dtype="float32"),
        "b_igate": ParamSpec((nh,), ("heads",), init="zeros", dtype="float32"),
        "w_fgate": ParamSpec((inner, nh), ("mlp", "heads"), dtype="float32"),
        "b_fgate": ParamSpec((nh,), ("heads",), init="ones", dtype="float32"),
        "out_norm": _norm(inner),
        "w_down": ParamSpec((inner, d), ("mlp", "embed"),
                            scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _slstm_specs(cfg: ArchConfig) -> Tree:
    x = cfg.xlstm
    d = cfg.d_model
    nh = x.num_heads
    dh = d // nh
    f = int(x.slstm_proj_factor * d)
    return {
        "wx": ParamSpec((d, 4, nh, dh), ("embed", None, "heads", None)),
        "r": ParamSpec((4, nh, dh, dh), (None, "heads", None, None)),
        "b": ParamSpec((4, nh, dh), (None, "heads", None), init="zeros", dtype="float32"),
        "group_norm": _norm(d),
    }


def layer_specs(cfg: ArchConfig, kind: str) -> Tree:
    """Specs for one layer of a given kind."""
    if kind in ("attn", "attn_dense"):
        t: Tree = {"ln1": _norm(cfg.d_model), "ln2": _norm(cfg.d_model)}
        t["attn"] = _mla_specs(cfg) if cfg.attention == "mla" else _gqa_specs(cfg)
        if cfg.cross_attention:
            t["ln_cross"] = _norm(cfg.d_model)
            t["cross"] = _gqa_specs(cfg, cross=True)
        if cfg.moe is not None and kind == "attn":
            t["moe"] = _moe_specs(cfg)
        else:
            d_ff = (cfg.dense_d_ff or cfg.d_ff) if kind == "attn_dense" else cfg.d_ff
            t["mlp"] = _mlp_specs(cfg, d_ff)
        return t
    if kind == "rglru":
        return {"ln1": _norm(cfg.d_model), "rec": _rglru_specs(cfg),
                "ln2": _norm(cfg.d_model), "mlp": _mlp_specs(cfg, cfg.d_ff)}
    if kind == "mlstm":
        return {"ln1": _norm(cfg.d_model), "mlstm": _mlstm_specs(cfg)}
    if kind == "slstm":
        return {"ln1": _norm(cfg.d_model), "slstm": _slstm_specs(cfg),
                "ln2": _norm(cfg.d_model),
                "ffn": _mlp_specs(cfg, int(cfg.xlstm.slstm_proj_factor * cfg.d_model))}
    raise ValueError(f"unknown layer kind {kind!r}")


def _stack_spec(spec: ParamSpec, n: int) -> ParamSpec:
    return dataclasses.replace(spec, shape=(n, *spec.shape),
                               logical=("layers", *spec.logical))


def model_specs(cfg: ArchConfig) -> Tree:
    """Full spec tree. Segments are stacked along a leading `layers` axis."""
    t: Tree = {}
    if cfg.frontend != "embeddings":
        t["embed"] = {"table": ParamSpec((cfg.vocab_size, cfg.d_model),
                                         ("vocab", "embed"), scale=0.02)}
    segs = []
    for (n_rep, cycle) in cfg.pattern_layers():
        cyc_tree: Tree = {}
        for j, kind in enumerate(cycle):
            layer = layer_specs(cfg, kind)
            cyc_tree[f"{j}:{kind}"] = jax.tree.map(
                lambda s: _stack_spec(s, n_rep), layer,
                is_leaf=lambda x: isinstance(x, ParamSpec))
        segs.append(cyc_tree)
    t["segments"] = segs
    t["final_norm"] = _norm(cfg.d_model)
    # Tied archs read logits from the embed table; frontend archs have no
    # embed table so they always need an explicit head.
    if cfg.frontend == "embeddings" or not cfg.tie_embeddings:
        t["lm_head"] = {"w": ParamSpec((cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"), scale=0.02)}
    return t


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_leaves(tree: Tree):
    return jax.tree.leaves(tree, is_leaf=is_spec)


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    """Total (or active, for MoE 6·N_active·D math) parameter count."""
    total = 0
    for path, spec in jax.tree_util.tree_flatten_with_path(
            model_specs(cfg), is_leaf=is_spec)[0]:
        n = int(np.prod(spec.shape))
        if active_only and cfg.moe is not None:
            keys = "/".join(getattr(k, "key", str(k)) for k in path)
            if "/moe/" in keys or keys.endswith("router"):
                if "/shared/" not in keys and "router" not in keys.rsplit("/", 1)[-1]:
                    n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total


def abstract_params(cfg: ArchConfig, shardings: Optional[Tree] = None) -> Tree:
    """ShapeDtypeStruct tree (optionally with shardings attached)."""
    def mk(spec: ParamSpec, sh=None):
        dt = jnp.dtype(spec.dtype or cfg.dtype)
        if sh is not None:
            return jax.ShapeDtypeStruct(spec.shape, dt, sharding=sh)
        return jax.ShapeDtypeStruct(spec.shape, dt)

    specs = model_specs(cfg)
    if shardings is None:
        return jax.tree.map(mk, specs, is_leaf=is_spec)
    return jax.tree.map(mk, specs, shardings, is_leaf=is_spec)


def init_params(cfg: ArchConfig, key: jax.Array) -> Tree:
    """Real initialization (used for smoke tests and the ~100M example)."""
    specs = model_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(flat))

    def one(spec: ParamSpec, k):
        dt = jnp.dtype(spec.dtype or cfg.dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        if spec.init == "lru_a":
            # Griffin init: a = sigmoid(Lambda) spread in (0.9, 0.999)
            u = jax.random.uniform(k, spec.shape, jnp.float32, 0.9, 0.999)
            return jnp.log(u / (1.0 - u)).astype(dt)
        scale = spec.scale if spec.scale is not None else 0.02
        return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dt)

    return jax.tree_util.tree_unflatten(treedef, [one(s, k) for s, k in zip(flat, keys)])
