"""Forward functions for every layer family but attention (in
``repro.models.attention``): norms, rope, MLP, MoE, recurrent blocks.

Pure functions over param dicts produced by ``repro.models.params``. All
layers share the signature pattern ``(params, x, *, cfg, px, mode, cache,
positions) -> (y, new_cache)`` where
  * mode  — "train" | "prefill" | "decode"
  * cache — per-layer state dict (None in train mode)
  * positions — (B, S) int32 absolute positions (decode: (B, 1) = current pos)
  * px    — ShardCtx threading mesh + ParallelConfig for GSPMD constraints
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.arch import ArchConfig, YaRNConfig
from repro.parallel.sharding import ShardCtx, constrain

Cache = Optional[Dict[str, jax.Array]]

# ---------------------------------------------------------------------------
# basics


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def _act(name: str):
    return jax.nn.gelu if name == "geglu" else jax.nn.silu


def mlp(p, x: jax.Array, cfg: ArchConfig, px: ShardCtx) -> jax.Array:
    h = _act(cfg.mlp_act)(x @ p["wg"]) * (x @ p["wu"])
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"), px)
    return h @ p["wd"]


# ---------------------------------------------------------------------------
# RoPE


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                yarn: Optional[YaRNConfig] = None):
    """positions (B,S) -> cos/sin (B,S,head_dim/2), fp32. With ``yarn`` the
    frequencies are YaRN's blend of the plain ones and the plain ones over
    ``factor``, and cos/sin carry its mscale ratio."""
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        m = 1.0
    else:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn), jnp.float32)
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: YaRNConfig) -> np.ndarray:
    """YaRN's inverse frequencies for a rotary part ``dim`` wide: the plain
    ones above the fast correction dimension, the plain ones over
    ``factor`` below the slow one, and a linear ramp between."""
    def corr_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return extra / yarn.factor * (1 - keep) + extra * keep


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B,S,H,hd); rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based scatter dispatch, EP over `model` axis)


def route(p, xg: jax.Array, mo) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router over every expert: xg (G, T, d) -> (scores (G,T,E), the
    experts picked (G,T,K), their weights (G,T,K)). Sigmoid scores pick by
    score plus ``router_bias`` and weigh by score alone; with ``n_group`` >
    1 a token picks only in its ``topk_group`` best groups, a group scoring
    the sum of its two best biased scores (DeepSeek-V3 ``noaux_tc``). The
    weights are normalized over the K picked, times ``routed_scaling``."""
    E, K = mo.num_experts, mo.top_k
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if mo.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"][None, None, :]
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    if mo.n_group > 1:
        grouped = sel.reshape(*sel.shape[:-1], mo.n_group, E // mo.n_group)
        group_score = lax.top_k(grouped, 2)[0].sum(-1)        # (G,T,n_group)
        _, best = lax.top_k(group_score, mo.topk_group)
        keep = jnp.any(best[..., None] == jnp.arange(mo.n_group), axis=-2)
        sel = jnp.where(jnp.repeat(keep, E // mo.n_group, axis=-1), sel,
                        -jnp.inf)
    _, top_idx = lax.top_k(sel, K)
    weights = jnp.take_along_axis(scores, top_idx, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-9)
    if mo.routed_scaling != 1.0:
        weights = weights * mo.routed_scaling
    return scores, top_idx, weights


#: rows of one tile of the held-expert loop (fewer where the step has fewer
#: tokens)
HELD_TILE = 256


def moe_held(p, x, *, cfg: ArchConfig, px: ShardCtx, train: bool = False,
             layer=None) -> Tuple[jax.Array, jax.Array]:
    """The chip's share of an expert-parallel MoE layer: route every token
    over all ``num_experts``, compute, dropless, each routed copy that
    lands on one of the ``num_experts_held`` experts held here, and add the
    shared experts once. Returns (output, routed copies computed: an int32
    per batch row).

    Copies are laid out expert by expert, each expert's run padded to whole
    tiles of ``HELD_TILE`` rows; a loop runs those tiles, gathering a
    tile's tokens, multiplying them through its expert's weights and
    adding the weighted rows back. An expert with no copies runs no tile.
    Only routing tables the size of the token count are allocated, never
    a buffer of activations per expert. In training (``train``) the loop runs every
    tile the routing tables have room for, a static count that reverse
    mode can differentiate; the empty ones add zeros.

    With ``layer`` the expert weights are the segment's stacks
    (layers, experts, ...) and the loop reads ``[layer, expert]`` out of
    them in place."""
    mo = cfg.moe
    B, S, d = x.shape
    T, K = B * S, mo.top_k
    E_l, e0 = mo.num_experts_held, mo.first_expert_held
    tm = min(HELD_TILE, -(-T // 8) * 8)
    # a token picks an expert once: at most T copies an expert, T·K in all
    max_tiles = -(-T * min(K, E_l) // tm) + E_l
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        _, top_idx, weights = route(p, xt[None], mo)
        local = top_idx[0].reshape(T * K) - e0
        held = (local >= 0) & (local < E_l)
        expert = jnp.where(held, local, E_l)                  # E_l: elsewhere
        onehot = jax.nn.one_hot(expert, E_l + 1, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                                   expert[:, None], 1)[:, 0]
        count = onehot[:, :E_l].sum(0)                        # (E_l,)
        tiles = -(-count // tm)
        tile_end = jnp.cumsum(tiles)
        row = jnp.where(held, (tile_end - tiles)[jnp.minimum(expert, E_l - 1)]
                        * tm + rank, max_tiles * tm)
        token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        row_token = jnp.full((max_tiles * tm,), T, jnp.int32).at[row].set(
            token, mode="drop")
        row_w = jnp.zeros((max_tiles * tm,), jnp.float32).at[row].set(
            weights[0].reshape(T * K), mode="drop")
        tile_expert = jnp.minimum(
            jnp.sum(jnp.arange(max_tiles)[:, None] >= tile_end[None, :], -1),
            E_l - 1)
    act = _act(cfg.mlp_act)

    def body(i, y):
        rows = lax.dynamic_slice_in_dim(row_token, i * tm, tm)
        w = lax.dynamic_slice_in_dim(row_w, i * tm, tm)
        e = tile_expert[i] if layer is None else (layer, tile_expert[i])
        xs = y_in[rows]                                       # (tm, d)
        h = act(xs @ p["wg"][e]) * (xs @ p["wu"][e])
        out = (h @ p["wd"][e]) * w[:, None].astype(x.dtype)
        return y.at[rows].add(out)

    with jax.named_scope("moe.experts"):
        y_in = jnp.concatenate([xt, jnp.zeros((1, d), x.dtype)])
        y = lax.fori_loop(0, max_tiles if train else tile_end[-1], body,
                          jnp.zeros_like(y_in))[:T]
    if mo.num_shared_experts > 0:
        y = y + mlp(p["shared"], xt[None], cfg, px)[0]
    copies = held.reshape(B, S * K).sum(-1, dtype=jnp.int32)
    return y.reshape(B, S, d), copies


def moe_block(p, x, *, cfg: ArchConfig, px: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). Dispatch: top-k → position-in-expert via
    one-hot cumsum → scatter into (G, E, C, d) expert buffers (E sharded over
    `model` = expert parallelism; G = data-parallel dispatch groups)."""
    mo = cfg.moe
    B, S, d = x.shape
    E, K = mo.num_experts, mo.top_k
    cf = px.pcfg.capacity_factor or mo.capacity_factor
    G = max(px.axis_sizes.get("data", 1) * px.axis_sizes.get("pod", 1), 1)
    T = B * S
    if T % G != 0:
        G = 1
    Tg = T // G
    C = int(max(math.ceil(Tg * K / E * cf), K))
    C = min(C, Tg)

    xg = x.reshape(G, Tg, d)
    xg = constrain(xg, ("act_group", None, "act_embed"), px)
    scores, top_idx, weights = route(p, xg, mo)                  # (G,Tg,K)

    # position-in-expert via cumsum of one-hot over flattened (token, k) copies
    flat_e = top_idx.reshape(G, Tg * K)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (G, Tg*K, E)
    pos_all = jnp.cumsum(oh, axis=1) - 1                        # occupancy - 1
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=-1)[..., 0]
    keep = pos < C
    pos_c = jnp.where(keep, pos, C)                             # C = drop slot
    pos_k = pos_c.reshape(G, Tg, K)
    keep_k = keep.reshape(G, Tg, K)

    # Dispatch = ONE int-index scatter + ONE gather. Scattering the d-wide
    # activations into an (E-sharded) buffer makes GSPMD materialize and
    # all-reduce the full buffer per layer (measured: 56 TB/step on
    # deepseek-v3 — EXPERIMENTS.md §Perf B); an (E,C) int32 routing table is
    # 7168x smaller, and the gather from data-sharded tokens is local.
    g_idx = jnp.arange(G)[:, None]
    token_ids = jnp.broadcast_to(jnp.arange(Tg, dtype=jnp.int32)[None, :], (G, Tg))
    idx_buf = jnp.full((G, E, C + 1), Tg, jnp.int32)      # sentinel -> zero row
    for j in range(K):  # K small (≤8): unrolled int scatters
        idx_buf = idx_buf.at[g_idx, top_idx[:, :, j], pos_k[:, :, j]].set(token_ids)
    idx_buf = idx_buf[:, :, :C]
    x_pad = jnp.concatenate([xg, jnp.zeros((G, 1, d), x.dtype)], axis=1)
    buf = jnp.take_along_axis(x_pad, idx_buf.reshape(G, E * C)[..., None],
                              axis=1).reshape(G, E, C, d)
    buf = constrain(buf, ("act_group", "act_experts", None, None), px)

    h = _act(cfg.mlp_act)(jnp.einsum("gecd,edf->gecf", buf, p["wg"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["wu"])
    h = constrain(h, ("act_group", "act_experts", None, "act_mlp"), px)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    if px.pcfg.moe_combine == "a2a":
        # axis-swap reshard E->d over `model`: GSPMD emits a true all-to-all
        # and the combine gathers below become device-local (§Perf B6)
        out_buf = constrain(out_buf, ("act_group", None, None, "act_mlp"), px)
    else:
        out_buf = constrain(out_buf, ("act_group", "act_experts", None, None), px)
    out_buf = jnp.pad(out_buf, ((0, 0), (0, 0), (0, 1), (0, 0)))  # drop slot→0

    y = jnp.zeros_like(xg)
    for j in range(K):
        gathered = out_buf[g_idx, top_idx[:, :, j], pos_k[:, :, j]]  # (G,Tg,d)
        w = (weights[:, :, j] * keep_k[:, :, j]).astype(x.dtype)
        y = y + gathered * w[..., None]
    if px.pcfg.moe_combine == "a2a":
        y = constrain(y, ("act_group", None, "act_mlp"), px)

    if mo.num_shared_experts > 0:
        y = y + mlp(p["shared"], xg, cfg, px)

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=(1, 2))
    ce = jnp.mean(scores, axis=1)
    aux = jnp.mean(jnp.sum(me * ce, axis=-1)) * E * mo.router_aux_weight
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma recurrent block)


def _block_diag(x, w, b):
    """x (...,L) with w (nb, bs, bs): block-diagonal linear."""
    nb, bs, _ = w.shape
    xs = x.reshape(*x.shape[:-1], nb, bs)
    y = jnp.einsum("...nb,nbc->...nc", xs, w)
    return y.reshape(*x.shape) + b


def _causal_conv(x, w, b, state):
    """Depthwise causal conv, width cw. x (B,S,L), state (B,cw-1,L) or None."""
    cw = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = sum(xp[:, j:j + S] * w[j] for j in range(cw)) + b
    new_state = xp[:, xp.shape[1] - (cw - 1):]
    return y, new_state


def rglru_block(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                cache: Cache) -> Tuple[jax.Array, Cache]:
    r = cfg.rglru
    B, S, _ = x.shape
    gate_y = jax.nn.gelu(x @ p["wy"])
    xx = x @ p["wx"]
    xx = constrain(xx, ("act_batch", "act_seq", "act_mlp"), px)
    conv_state = cache["conv"] if cache is not None else None
    xx, new_conv = _causal_conv(xx, p["conv_w"], p["conv_b"], conv_state)

    rg = jax.nn.sigmoid(_block_diag(xx, p["gate_r_w"], p["gate_r_b"]).astype(jnp.float32))
    ig = jax.nn.sigmoid(_block_diag(xx, p["gate_i_w"], p["gate_i_b"]).astype(jnp.float32))
    log_a = -r.c_exponent * jax.nn.softplus(p["a_param"]) * rg  # (B,S,L) fp32
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    gated = mult * ig * xx.astype(jnp.float32)

    h0 = cache["h"].astype(jnp.float32) if cache is not None else jnp.zeros(
        (B, xx.shape[-1]), jnp.float32)
    if mode == "decode":
        h = a[:, 0] * h0 + gated[:, 0]
        hs = h[:, None, :]
        new_h = h
    else:
        def comb(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2
        A, Bc = lax.associative_scan(comb, (a, gated), axis=1)
        hs = A * h0[:, None, :] + Bc
        new_h = hs[:, -1]
    y = (gate_y * hs.astype(x.dtype)) @ p["wo"]
    new_cache = None if cache is None else {"conv": new_conv.astype(cache["conv"].dtype),
                                            "h": new_h.astype(cache["h"].dtype)}
    return y, new_cache


# ---------------------------------------------------------------------------
# xLSTM blocks


def _mlstm_chunkwise(q, k, v, ig, fg, c0, n0, m0, chunk: int,
                     bf16_streams: bool = False):
    """Chunkwise-parallel stabilized mLSTM (beyond-paper §Perf hillclimb A).

    Exact reformulation of the per-step recurrence: the matrix state is
    updated once per chunk (HBM traffic ÷ chunk) and intra-chunk work is
    (C×C)·(C×d) matmuls (MXU-shaped). Stabilizers cancel algebraically;
    only fp rounding differs from the sequential scan (tests assert ≈).

    q,k (B,S,nh,dqk) [q pre-scaled], v (B,S,nh,dv), ig/fg (B,S,nh) raw gates;
    state c0 (B,nh,dqk,dv), n0 (B,nh,dqk), m0 (B,nh).
    """
    B, S, nh, dqk = q.shape
    dv = v.shape[-1]
    C = chunk
    nc = S // C
    f32 = jnp.float32

    def resh(a, d):
        return a.reshape(B, nc, C, nh, d).transpose(1, 0, 3, 2, 4)  # (nc,B,nh,C,d)

    # bf16_streams: keep q/k/v and the (C,*) intermediates in bf16 (gates,
    # normalizers and the carried state stay fp32) — §Perf hillclimb A4.
    sdt = jnp.bfloat16 if bf16_streams else f32
    qs, ks, vs = resh(q.astype(sdt), dqk), resh(k.astype(sdt), dqk), resh(v.astype(sdt), dv)
    gi = ig.reshape(B, nc, C, nh).transpose(1, 0, 3, 2)              # (nc,B,nh,C)
    logf = jax.nn.log_sigmoid(fg).reshape(B, nc, C, nh).transpose(1, 0, 3, 2)

    causal = jnp.tril(jnp.ones((C, C), bool))

    def step(carry, inp):
        c0, n0, m0 = carry                     # (B,nh,dqk,dv),(B,nh,dqk),(B,nh)
        q_c, k_c, v_c, ig_c, lf_c = inp        # (B,nh,C,*)
        b = jnp.cumsum(lf_c, axis=-1)          # (B,nh,C) inclusive log-decay
        btot = b[..., -1]
        w = ig_c - b                           # log source weight vs chunk start
        m_c = jnp.max(w, axis=-1)              # (B,nh)
        e_src = jnp.exp(w - m_c[..., None])    # (B,nh,C) ≤ 1
        decay = jnp.exp(b)                     # (B,nh,C) ≤ 1

        # intra-chunk: W[j,s] = decay_j * e_src_s (separable), causal mask
        Wm = (decay[..., :, None] * e_src[..., None, :] * causal).astype(sdt)
        s_qk = jnp.einsum("bhjd,bhsd->bhjs", q_c, k_c,
                          preferred_element_type=f32)
        wqk = (s_qk * Wm.astype(f32)).astype(sdt)
        num_i = jnp.einsum("bhjs,bhsv->bhjv", wqk, v_c,
                           preferred_element_type=f32)
        # n_intra_j = Σ_s W[j,s] k_s ; den_i = q_j · n_intra_j
        n_i = jnp.einsum("bhjs,bhsd->bhjd", Wm, k_c,
                         preferred_element_type=f32)
        den_i = jnp.einsum("bhjd,bhjd->bhj", q_c.astype(f32), n_i)

        # inter-chunk (previous state), per-position combine like flash
        mu = jnp.maximum(m0[..., None] + b, m_c[..., None])     # (B,nh,C)
        sc_prev = jnp.exp(m0[..., None] + b - mu)
        sc_intra = jnp.exp(m_c[..., None] - mu)
        num_p = jnp.einsum("bhjd,bhdv->bhjv", q_c.astype(f32), c0)
        den_p = jnp.einsum("bhjd,bhd->bhj", q_c.astype(f32), n0)
        num = sc_prev[..., None] * num_p + sc_intra[..., None] * num_i
        den = sc_prev * den_p + sc_intra * den_i
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-mu))[..., None]

        # end-of-chunk state
        M = jnp.maximum(m0, m_c)
        e2 = jnp.exp(w - M[..., None])                           # (B,nh,C)
        kw_ = (e2[..., None].astype(sdt) * k_c)
        c_new = (jnp.exp(m0 - M)[..., None, None] * c0
                 + jnp.einsum("bhsd,bhsv->bhdv", kw_, v_c,
                              preferred_element_type=f32))
        n_new = (jnp.exp(m0 - M)[..., None] * n0
                 + jnp.sum(kw_, axis=-2).astype(f32))
        m_new = btot + M
        return (c_new, n_new, m_new), h

    (c, n, m), hs = lax.scan(step, (c0, n0, m0), (qs, ks, vs, gi, logf))
    # hs (nc,B,nh,C,dv) -> (B,S,nh,dv)
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, S, nh, dv)
    return h, (c, n, m)


def mlstm_block(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                cache: Cache) -> Tuple[jax.Array, Cache]:
    xc = cfg.xlstm
    B, S, d = x.shape
    up = jnp.einsum("bsd,dti->bsti", x, p["w_up"])
    gate_br, inner_in = up[:, :, 0], up[:, :, 1]
    inner_in = constrain(inner_in, ("act_batch", "act_seq", "act_mlp"), px)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(inner_in, p["conv_w"], p["conv_b"], conv_state)
    conv_out = jax.nn.silu(conv_out)

    nh = xc.num_heads
    q = jnp.einsum("bsi,ihk->bshk", conv_out, p["wq"])
    k = jnp.einsum("bsi,ihk->bshk", conv_out, p["wk"])
    v = jnp.einsum("bsi,ihk->bshk", inner_in, p["wv"])
    dqk = q.shape[-1]
    q = q / math.sqrt(dqk)
    ig = (jnp.einsum("bsi,ih->bsh", conv_out.astype(jnp.float32), p["w_igate"])
          + p["b_igate"])
    fg = (jnp.einsum("bsi,ih->bsh", conv_out.astype(jnp.float32), p["w_fgate"])
          + p["b_fgate"])

    if cache is not None:
        c0 = cache["c"].astype(jnp.float32)
        n0 = cache["n"].astype(jnp.float32)
        m0 = cache["m"].astype(jnp.float32)
    else:
        dv = v.shape[-1]
        c0 = jnp.zeros((B, nh, dqk, dv), jnp.float32)
        n0 = jnp.zeros((B, nh, dqk), jnp.float32)
        m0 = jnp.zeros((B, nh), jnp.float32)

    chunk = px.pcfg.mlstm_chunk
    if mode != "decode" and chunk and S % chunk == 0 and S > chunk:
        h, (c, n, m) = _mlstm_chunkwise(q, k, v, ig, fg, c0, n0, m0, chunk,
                                        bf16_streams=px.pcfg.mlstm_bf16_streams)
        h = h.reshape(B, S, -1)
        h = rms_norm(h, p["out_norm"]["scale"], cfg.norm_eps)
        h = h * jax.nn.silu(gate_br)
        y = jnp.einsum("bsi,id->bsd", h.astype(x.dtype), p["w_down"])
        new_cache = None if cache is None else {
            "c": c.astype(cache["c"].dtype), "n": n.astype(cache["n"].dtype),
            "m": m.astype(cache["m"].dtype),
            "conv": new_conv.astype(cache["conv"].dtype)}
        return y, new_cache

    def step(carry, inp):
        c, n, m = carry
        q_t, k_t, v_t, ig_t, fg_t = inp
        logf = jax.nn.log_sigmoid(fg_t)                      # (B,nh)
        m_new = jnp.maximum(logf + m, ig_t)
        i_p = jnp.exp(ig_t - m_new)
        f_p = jnp.exp(logf + m - m_new)
        kv = jnp.einsum("bhk,bhv->bhkv", k_t.astype(jnp.float32),
                        v_t.astype(jnp.float32))
        c = f_p[..., None, None] * c + i_p[..., None, None] * kv
        n = f_p[..., None] * n + i_p[..., None] * k_t.astype(jnp.float32)
        num = jnp.einsum("bhk,bhkv->bhv", q_t.astype(jnp.float32), c)
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", q_t.astype(jnp.float32), n))
        den = jnp.maximum(den, jnp.exp(-m_new))
        h = num / den[..., None]
        return (c, n, m_new), h

    seq = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
           v.transpose(1, 0, 2, 3), ig.transpose(1, 0, 2), fg.transpose(1, 0, 2))
    (c, n, m), hs = lax.scan(step, (c0, n0, m0), seq)
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, -1)           # (B,S,inner)
    h = rms_norm(h, p["out_norm"]["scale"], cfg.norm_eps)
    h = h * jax.nn.silu(gate_br)
    y = jnp.einsum("bsi,id->bsd", h.astype(x.dtype), p["w_down"])
    new_cache = None if cache is None else {
        "c": c.astype(cache["c"].dtype), "n": n.astype(cache["n"].dtype),
        "m": m.astype(cache["m"].dtype), "conv": new_conv.astype(cache["conv"].dtype)}
    return y, new_cache


def slstm_block(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                cache: Cache) -> Tuple[jax.Array, Cache]:
    xc = cfg.xlstm
    B, S, d = x.shape
    nh = xc.num_heads
    dh = d // nh
    xg = jnp.einsum("bsd,dghk->bsghk", x, p["wx"]).astype(jnp.float32)  # (B,S,4,nh,dh)

    if cache is not None:
        c0, n0, h0, m0 = (cache[k].astype(jnp.float32) for k in ("c", "n", "h", "m"))
    else:
        z = jnp.zeros((B, nh, dh), jnp.float32)
        c0, n0, h0, m0 = z, z + 1e-6, z, z

    def step(carry, xg_t):
        c, n, h, m = carry
        rec = jnp.einsum("bhk,ghkl->bghl", h, p["r"].astype(jnp.float32))
        pre = xg_t + rec + p["b"]
        i_raw, f_raw, z_raw, o_raw = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
        m_new = jnp.maximum(f_raw + m, i_raw)
        i_g = jnp.exp(i_raw - m_new)
        f_g = jnp.exp(f_raw + m - m_new)
        c = f_g * c + i_g * jnp.tanh(z_raw)
        n = f_g * n + i_g
        h_new = jax.nn.sigmoid(o_raw) * (c / jnp.maximum(n, 1e-6))
        return (c, n, h_new, m_new), h_new

    (c, n, h, m), hs = lax.scan(step, (c0, n0, h0, m0), xg.transpose(1, 0, 2, 3, 4))
    y = hs.transpose(1, 0, 2, 3).reshape(B, S, d)
    y = rms_norm(y, p["group_norm"]["scale"], cfg.norm_eps).astype(x.dtype)
    new_cache = None if cache is None else {
        "c": c.astype(cache["c"].dtype), "n": n.astype(cache["n"].dtype),
        "h": h.astype(cache["h"].dtype), "m": m.astype(cache["m"].dtype)}
    return y, new_cache
