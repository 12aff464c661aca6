"""Composable block-pattern decoder: forward pass + cache management.

The model is a sequence of *segments* (homogeneous layer cycles). Each segment
is executed with one ``lax.scan`` over its stacked parameters (and stacked
cache in inference modes), keeping compile time O(distinct layer kinds), not
O(depth) — essential for 61-layer MoE models lowered against 512 devices.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.arch import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models.params import ParamSpec, is_spec, _stack_spec
from repro.parallel.sharding import ShardCtx, constrain

Tree = Dict[str, Any]


# ---------------------------------------------------------------------------
# cache specs


def _cache_layer_specs(cfg: ArchConfig, kind: str, batch: int, cap: int,
                       kc=None) -> Tree:
    dt = cfg.dtype
    if kind in A.ATTENTION_KINDS:
        t = A.cache_layer_specs(cfg, kind, batch, cap, kc)
        if kind == "attn" and cfg.moe is not None and cfg.moe.num_experts_held:
            # routed copies the held experts computed, per row, summed over
            # decode steps on the device
            t["routed"] = ParamSpec((batch,), ("act_batch",), init="zeros",
                                    dtype="int32")
        return t
    if kind == "rglru":
        r = cfg.rglru
        width = r.lru_width or cfg.d_model
        return {
            "conv": ParamSpec((batch, r.conv_width - 1, width),
                              ("act_batch", None, "act_mlp"), init="zeros", dtype=dt),
            "h": ParamSpec((batch, width), ("act_batch", "act_mlp"),
                           init="zeros", dtype="float32"),
        }
    if kind == "mlstm":
        x = cfg.xlstm
        inner = int(x.mlstm_proj_factor * cfg.d_model)
        nh = x.num_heads
        dv = inner // nh
        dqk = int(x.qk_dim_factor * dv)
        return {
            "c": ParamSpec((batch, nh, dqk, dv), ("act_batch", "act_heads", None, None),
                           init="zeros", dtype="float32"),
            "n": ParamSpec((batch, nh, dqk), ("act_batch", "act_heads", None),
                           init="zeros", dtype="float32"),
            "m": ParamSpec((batch, nh), ("act_batch", "act_heads"),
                           init="zeros", dtype="float32"),
            "conv": ParamSpec((batch, 3, inner), ("act_batch", None, "act_mlp"),
                              init="zeros", dtype=dt),
        }
    if kind == "slstm":
        x = cfg.xlstm
        nh = x.num_heads
        dh = cfg.d_model // nh
        mk = lambda init: ParamSpec((batch, nh, dh), ("act_batch", "act_heads", None),
                                    init=init, dtype="float32")
        return {"c": mk("zeros"), "n": mk("ones"), "h": mk("zeros"), "m": mk("zeros")}
    raise ValueError(kind)


def cache_specs(cfg: ArchConfig, batch: int, cap: int, kc=None) -> Tree:
    """Cache leaves for ``cap`` positions, stacked per segment. With the
    kernel config ``kc`` the K/V caches take ``attention.decode_capacity``
    slots, the capacity its decode kernel reads with no padding."""
    segs = []
    for (n_rep, cycle) in cfg.pattern_layers():
        cyc: Tree = {}
        for j, kind in enumerate(cycle):
            layer = _cache_layer_specs(cfg, kind, batch, cap, kc)
            cyc[f"{j}:{kind}"] = jax.tree.map(lambda s: _stack_spec(s, n_rep), layer,
                                              is_leaf=is_spec)
        segs.append(cyc)
    return {"segments": segs}


def init_cache(cfg: ArchConfig, batch: int, cap: int, kc=None) -> Tree:
    def one(spec: ParamSpec):
        dt = jnp.dtype(spec.dtype or cfg.dtype)
        if spec.init == "neg_ones":
            return jnp.full(spec.shape, -1, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        return jnp.zeros(spec.shape, dt)

    return jax.tree.map(one, cache_specs(cfg, batch, cap, kc), is_leaf=is_spec)


# ---------------------------------------------------------------------------
# forward


def _sinusoidal(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _apply_layer(kind: str, p: Tree, x: jax.Array, *, cfg: ArchConfig,
                 px: ShardCtx, mode: str, cache, positions, cond, layer=None,
                 expert_layer=None):
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    if kind in A.ATTENTION_KINDS:
        h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
        if cfg.attention == "mla":
            a_out, a_cache = A.mla_attention(p["attn"], h, cfg=cfg, px=px, mode=mode,
                                             cache=cache, positions=positions,
                                             layer=layer)
        else:
            a_out, a_cache = A.gqa_attention(p["attn"], h, cfg=cfg, px=px, mode=mode,
                                             cache=cache, positions=positions,
                                             window=A.layer_window(cfg, kind),
                                             layer=layer)
        x = x + a_out
        if cache is not None:
            new_cache = dict(cache)
            new_cache.update(a_cache)
        if cfg.cross_attention:
            hc = L.rms_norm(x, p["ln_cross"]["scale"], cfg.norm_eps)
            if mode == "decode":
                ckv = (cache["cross_k"], cache["cross_v"])
            else:
                ckv = A.cond_kv(p["cross"], cond, cfg=cfg)
                if cache is not None:
                    new_cache["cross_k"], new_cache["cross_v"] = ckv
            x = x + A.cross_attention(p["cross"], hc, ckv, cfg=cfg, px=px)
        h2 = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        if "moe" in p and cfg.moe.num_experts_held:
            m_out, copies = L.moe_held(p["moe"], h2, cfg=cfg, px=px,
                                       train=mode == "train",
                                       layer=expert_layer)
            if cache is not None and mode == "decode":
                new_cache["routed"] = cache["routed"] + copies
        elif "moe" in p:
            m_out, aux = L.moe_block(p["moe"], h2, cfg=cfg, px=px)
        else:
            m_out = L.mlp(p["mlp"], h2, cfg, px)
        x = x + m_out
    elif kind == "rglru":
        h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
        r_out, new_cache = L.rglru_block(p["rec"], h, cfg=cfg, px=px, mode=mode,
                                         cache=cache)
        x = x + r_out
        h2 = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        x = x + L.mlp(p["mlp"], h2, cfg, px)
    elif kind == "mlstm":
        h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
        m_out, new_cache = L.mlstm_block(p["mlstm"], h, cfg=cfg, px=px, mode=mode,
                                         cache=cache)
        x = x + m_out
    elif kind == "slstm":
        h = L.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
        s_out, s_cache = L.slstm_block(p["slstm"], h, cfg=cfg, px=px, mode=mode,
                                       cache=cache)
        x = x + s_out
        h2 = L.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
        x = x + L.mlp(p["ffn"], h2, cfg, px)
        new_cache = s_cache
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _layer_cache(stacks: Tree, i) -> Tree:
    """Layer ``i``'s decode cache out of its leaves stacked over the
    segment: the leaves a token is written into are handed whole (the
    attention layer scatters at ``[i, b, slot]``, head-major K/V at
    ``[i, b, :, slot]``), every other leaf
    (recurrent state, cross-attention K/V) is layer ``i``'s slice."""
    return {n: a if n in A.TOKEN_LEAVES
            else lax.dynamic_index_in_dim(a, i, keepdims=False)
            for n, a in stacks.items()}


def _write_layer_cache(stacks: Tree, new: Tree, i) -> Tree:
    """``_layer_cache``'s inverse: the stacks with layer ``i``'s update."""
    return {n: new[n] if n in A.TOKEN_LEAVES
            else lax.dynamic_update_index_in_dim(a, new[n], i, 0)
            for n, a in stacks.items()}


#: a held-expert layer's weights that its expert loop reads at
#: ``[layer, expert]``
_HELD_EXPERT_LEAVES = ("wg", "wu", "wd")


def _split_held_experts(cfg: ArchConfig, seg_params: Tree) -> Tuple[Tree, Tree]:
    """The segment's parameters without the expert stacks of its
    held-expert layers, and those stacks ({layer key: {name: stack}}). The
    layer loop slices what it is given layer by layer; the stacks are
    handed whole, with the layer's index, to ``layers.moe_held``, whose
    expert loop then reads each expert's weights where they lie. (A layer's
    slice of them, handed to that loop, would be copied out for it.)"""
    if cfg.moe is None or not cfg.moe.num_experts_held:
        return seg_params, {}
    experts = {k: {n: p["moe"][n] for n in _HELD_EXPERT_LEAVES}
               for k, p in seg_params.items() if "moe" in p}
    if not experts:
        return seg_params, experts
    rest = {k: (dict(p, moe={n: a for n, a in p["moe"].items()
                             if n not in _HELD_EXPERT_LEAVES})
                if k in experts else p) for k, p in seg_params.items()}
    return rest, experts


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)  # "full": recompute everything


def forward(params: Tree, *, cfg: ArchConfig, px: ShardCtx, mode: str,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            cond: Optional[jax.Array] = None,
            positions: jax.Array,
            cache: Optional[Tree] = None) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Returns (hidden (B,S,d) pre-final-norm, new_cache, aux_loss)."""
    if cfg.frontend == "embeddings":
        assert embeds is not None
        x = embeds + _sinusoidal(positions, cfg.d_model).astype(embeds.dtype)
    else:
        x = params["embed"]["table"][tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), px)

    aux_total = jnp.zeros((), jnp.float32)
    new_cache_segs = []
    segs = cfg.pattern_layers()
    for si, (n_rep, cycle) in enumerate(segs):
        seg_params, experts = _split_held_experts(cfg, params["segments"][si])
        seg_cache = cache["segments"][si] if cache is not None else None

        def cycle_fn(x, cyc_params, cyc_cache, layer=None, index=None):
            """With ``layer`` (decode), ``cyc_cache`` is the cycle's cache
            stacked over the segment, and layer ``layer`` writes into it.
            ``index`` is the layer's index in the segment, at which its held
            experts are read out of their stacks."""
            aux = jnp.zeros((), jnp.float32)
            new_cc: Tree = {}
            for j, kind in enumerate(cycle):
                key = f"{j}:{kind}"
                lc = cyc_cache[key] if cyc_cache is not None else None
                if layer is not None:
                    lc = _layer_cache(lc, layer)
                lp = cyc_params[key]
                if key in experts:
                    lp = dict(lp, moe=dict(lp["moe"], **experts[key]))
                x, nlc, a = _apply_layer(kind, lp, x, cfg=cfg, px=px,
                                         mode=mode, cache=lc, positions=positions,
                                         cond=cond, layer=layer,
                                         expert_layer=index)
                if layer is not None:
                    nlc = _write_layer_cache(cyc_cache[key], nlc, layer)
                new_cc[key] = nlc
                aux = aux + a
            return x, (new_cc if cyc_cache is not None else None), aux

        if mode == "decode":
            # the stacked cache rides the layer loop as carry: an attention
            # layer scatters its token into it, a recurrent layer writes its
            # state back at its index, so a donated cache is updated in place
            if px.pcfg.scan_layers and n_rep > 1:
                def body(carry, xs):
                    xx, aux, cc = carry
                    cp, i = xs
                    xx, cc, a = cycle_fn(xx, cp, cc, i, i)
                    return (xx, aux + a, cc), None
                (x, aux_total, new_seg_cache), _ = lax.scan(
                    body, (x, aux_total, seg_cache),
                    (seg_params, jnp.arange(n_rep)))
            else:
                new_seg_cache = seg_cache
                for i in range(n_rep):
                    cp = jax.tree.map(lambda a: a[i], seg_params)
                    x, new_seg_cache, a = cycle_fn(x, cp, new_seg_cache, i, i)
                    aux_total = aux_total + a
        elif px.pcfg.scan_layers and n_rep > 1:
            if seg_cache is not None:
                def body(carry, xs):
                    xx, aux = carry
                    cp, cc, i = xs
                    xx, ncc, a = _remat_wrap(
                        lambda x_, p_, c_, i_: cycle_fn(x_, p_, c_, index=i_),
                        px.pcfg.remat if mode == "train" else "none")(
                            xx, cp, cc, i)
                    return (xx, aux + a), ncc
                (x, aux), new_seg_cache = lax.scan(
                    body, (x, aux_total),
                    (seg_params, seg_cache, jnp.arange(n_rep)))
                aux_total = aux
            else:
                def body(carry, xs):
                    xx, aux = carry
                    cp, i = xs
                    xx, _, a = _remat_wrap(
                        lambda x_, p_, i_: cycle_fn(x_, p_, None, index=i_),
                        px.pcfg.remat if mode == "train" else "none")(
                            xx, cp, i)
                    return (xx, aux + a), None
                (x, aux_total), _ = lax.scan(body, (x, aux_total),
                                             (seg_params, jnp.arange(n_rep)))
                new_seg_cache = None
        else:
            # unrolled: index the stacked leaves layer by layer
            new_stack = [] if seg_cache is not None else None
            for i in range(n_rep):
                cp = jax.tree.map(lambda a: a[i], seg_params)
                cc = (jax.tree.map(lambda a: a[i], seg_cache)
                      if seg_cache is not None else None)
                fn = _remat_wrap(lambda x_, p_, c_=cc, i_=i: cycle_fn(
                    x_, p_, c_, index=i_),
                                 px.pcfg.remat if mode == "train" else "none")
                x, ncc, a = fn(x, cp)
                aux_total = aux_total + a
                if new_stack is not None:
                    new_stack.append(ncc)
            new_seg_cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_stack)
                             if new_stack else None)
        new_cache_segs.append(new_seg_cache)

    new_cache = {"segments": new_cache_segs} if cache is not None else None
    return x, new_cache, aux_total


def output_head(params: Tree, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Final norm + logits projection. x (B,S,d) -> (B,S,V) fp32."""
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if "lm_head" in params:
        w = params["lm_head"]["w"]
    else:
        w = params["embed"]["table"].T
    return (x @ w.astype(x.dtype)).astype(jnp.float32)
