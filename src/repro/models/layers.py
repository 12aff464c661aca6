"""Forward functions for every layer family.

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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.arch import ArchConfig
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


def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """positions (B,S) -> cos/sin (B,S,head_dim/2), fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B,S,H,hd); rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# attention cores


def _direct_attention(q, k, v, *, q_pos, k_pos, window, scale):
    """Materialized-scores attention (small seq / smoke tests).

    q (B,Sq,H,hd), k/v (B,Sk,KV,hd); GQA by head grouping.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    hd_v = v.shape[-1]  # MLA: v head dim differs from q/k head dim
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    mask = k_pos[:, None, :] <= q_pos[:, :, None]  # (B,Sq,Sk) causal
    if window is not None:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd_v)


def _flash_attention(q, k, v, *, q_pos, k_pos, window, scale, px: ShardCtx):
    """Blockwise online-softmax attention (lax.scan over KV blocks).

    Keeps O(Sq·block_kv) transients instead of O(Sq·Sk). With
    ``px.pcfg.attn_q_chunks > 1`` the causal upper-triangle of KV blocks is
    statically skipped per q-chunk (saves ~(1 - (c+1)/2c) of attention FLOPs).
    """
    pcfg = px.pcfg
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]  # MLA: v head dim differs from q/k head dim
    G = H // KV
    bk = min(pcfg.attn_block_kv, Sk)
    n_chunks = pcfg.attn_q_chunks if (Sq == Sk and Sq % pcfg.attn_q_chunks == 0) else 1

    def run_chunk(qc, qc_pos, k_part, v_part, kp_part):
        nk = k_part.shape[1] // bk
        kb = k_part.reshape(B, nk, bk, KV, hd)
        vb = v_part.reshape(B, nk, bk, KV, hd_v)
        kpb = kp_part.reshape(B, nk, bk)
        Sqc = qc.shape[1]
        qg = qc.reshape(B, Sqc, KV, G, hd)

        def body(carry, blk):
            m, l, acc = carry
            k_j, v_j, kp_j = blk  # (B,bk,KV,hd),(B,bk)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_j).astype(jnp.float32) * scale
            msk = kp_j[:, None, :] <= qc_pos[:, :, None]
            if window is not None:
                msk &= kp_j[:, None, :] > qc_pos[:, :, None] - window
            s = jnp.where(msk[:, None, None, :, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v_j.dtype), v_j)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, Sqc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, Sqc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, Sqc, hd_v), jnp.float32)
        (m, l, acc), _ = lax.scan(
            body, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kpb.transpose(1, 0, 2)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Sqc, H, hd_v).astype(q.dtype)

    if n_chunks == 1:
        return run_chunk(q, q_pos, k, v, k_pos)
    # causal q-chunking: chunk i only sees KV up to its own end (static slice)
    outs = []
    cq = Sq // n_chunks
    for i in range(n_chunks):
        hi = (i + 1) * cq
        hi_k = ((hi + bk - 1) // bk) * bk  # round up to block boundary
        outs.append(run_chunk(q[:, i * cq:hi], q_pos[:, i * cq:hi],
                              k[:, :hi_k], v[:, :hi_k], k_pos[:, :hi_k]))
    return jnp.concatenate(outs, axis=1)


def _pallas_flash_ok(S: int, hd: int, hd_v: int, window, kc) -> bool:
    """Static preconditions for dispatching the Pallas flash kernel: opted in
    via KernelConfig, plain causal attention (no local window), equal q/k/v
    head dims (the kernel streams one (S, hd) layout), and a sequence the
    tuned blocks tile exactly. Anything else falls back to the pure-JAX
    paths — dispatch never changes semantics, only the implementation."""
    return (kc is not None and kc.use_flash and window is None
            and hd == hd_v and S % kc.flash_block_q == 0
            and S % kc.flash_block_kv == 0)


def _pallas_flash_attention(q, k, v, kc):
    """GQA-expanded dispatch into the tuned Pallas flash kernel.

    The kernel is an MHA core (its fp32 (m, l, acc) state lives in VMEM and
    never round-trips through HBM, which the lax.scan formulation above
    cannot express); GQA feeds it by expanding KV heads to the q head count.
    Assumes contiguous positions starting at 0 — what train/prefill steps
    produce; windowed/decode paths never reach here (``_pallas_flash_ok``).
    """
    from repro.kernels import ops as kernel_ops
    G = q.shape[2] // k.shape[2]
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    return kernel_ops.flash_attention(
        q, k, v, block_q=kc.flash_block_q, block_kv=kc.flash_block_kv,
        causal=True, interpret=kc.interpret)


def _pallas_decode_ok(hd: int, hd_v: int, kc) -> bool:
    """Static preconditions for the Pallas flash-decode kernel: opted in via
    KernelConfig and equal k/v head dims (the split kernel accumulates one
    (G, hd) layout — the MLA ``dn+dr != dv`` variant stays pure-JAX).
    Windows, rolling caches, partial occupancy, and capacities that don't
    tile into the tuned blocks are all handled inside the kernel wrapper
    (validity-bias + padding), so they don't gate dispatch."""
    return kc is not None and kc.use_decode and hd == hd_v


def decode_capacity(cap: int, hd: int, kc) -> int:
    """Slots to allocate for a K/V cache of ``cap`` positions: where the
    Pallas decode kernel dispatches, ``cap`` rounded up to the kernel's tile
    (``decode_block_kv × decode_num_splits``), so that it reads the cache
    as it is and no decode step pads it. The extra slots stay empty
    (``pos`` -1) and are masked like any empty slot."""
    if not _pallas_decode_ok(hd, hd, kc):
        return cap
    tile = kc.decode_block_kv * kc.decode_num_splits
    return -(-cap // tile) * tile


def _pallas_decode_attention(q, k_cache, v_cache, *, cache_pos, cur_pos,
                             window, kc, layer):
    """Dispatch one decode step into the tuned split-KV flash-decode kernel
    (semantics-matched to ``_decode_attention``; parity pinned in tests).
    The caches are stacked over layers; the kernel reads layer ``layer``."""
    from repro.kernels import ops as kernel_ops
    return kernel_ops.decode_attention(
        q, k_cache, v_cache, cache_pos, cur_pos, layer, window=window,
        block_kv=kc.decode_block_kv, num_splits=kc.decode_num_splits,
        combine=kc.decode_combine, interpret=kc.interpret)


def _decode_attention(q, k_cache, v_cache, *, cache_pos, cur_pos, window, scale):
    """Single-token attention over a cache. q (B,1,H,hd), cache (B,S,KV,hd).

    cache_pos (B,S): absolute position stored in each cache slot (-1 = empty).
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32) * scale
    valid = (cache_pos >= 0) & (cache_pos <= cur_pos[:, None])
    if window is not None:
        valid &= cache_pos > cur_pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# GQA / MQA / MHA attention layer (optionally local-windowed, cross-attn)


def gqa_attention(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                  cache: Cache, positions, window=None,
                  layer=None) -> Tuple[jax.Array, Cache]:
    """In decode, ``cache`` holds the segment's stacked (L, B, S, ...)
    leaves and ``layer`` is this layer's index into them: the token is
    written at ``[layer, b, slot]`` and the new stacks are returned
    (``models.model.forward`` carries them through the layer loop)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None), px)
    k = constrain(k, ("act_batch", "act_seq", "act_kv_heads", None), px)

    new_cache = cache
    if mode == "decode":
        assert cache is not None and S == 1
        slot = _cache_slot(positions[:, 0], cache["k"].shape[2], window)
        k_cache = _insert_slot(cache["k"], k, slot, layer)
        v_cache = _insert_slot(cache["v"], v, slot, layer)
        cache_pos = _insert_slot(cache["pos"], positions, slot, layer)
        if _pallas_decode_ok(hd, v.shape[-1], px.pcfg.kernel):
            out = _pallas_decode_attention(
                q, k_cache, v_cache, cache_pos=cache_pos[layer],
                cur_pos=positions[:, 0], window=window, kc=px.pcfg.kernel,
                layer=layer)
        else:
            out = _decode_attention(q, k_cache[layer], v_cache[layer],
                                    cache_pos=cache_pos[layer],
                                    cur_pos=positions[:, 0], window=window,
                                    scale=scale)
        new_cache = {"k": k_cache, "v": v_cache, "pos": cache_pos}
    else:
        q_pos = positions
        k_pos = positions
        if _pallas_flash_ok(S, hd, v.shape[-1], window, px.pcfg.kernel):
            out = _pallas_flash_attention(q, k, v, px.pcfg.kernel)
        elif S >= px.pcfg.flash_threshold:
            out = _flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                   window=window, scale=scale, px=px)
        else:
            out = _direct_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                    window=window, scale=scale)
        if mode == "prefill":
            assert cache is not None
            cap = cache["k"].shape[1]
            new_cache = _prefill_cache(cache, k, v, positions, cap, window)
    out = constrain(out, ("act_batch", "act_seq", "act_heads", None), px)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def cross_attention(p, x, cond_kv, *, cfg: ArchConfig, px: ShardCtx) -> jax.Array:
    """Attention over precomputed (k, v) from conditioning embeddings."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = cond_kv
    B, Sq, H, _ = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, H // KV, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", prob.astype(v.dtype), v).reshape(B, Sq, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cond_kv(p, cond, *, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", cond, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", cond, p["wv"])
    return k, v


def _cache_slot(pos, capacity, window):
    """Rolling slot for windowed caches; direct slot otherwise."""
    return jnp.remainder(pos, capacity) if window is not None else pos


def _insert_slot(buf, val, slot, layer):
    """Insert val (B,1,...) at per-batch slot (B,) of layer ``layer`` of a
    stacked buffer (L,B,S,...): a scatter that updates ``buf`` in place."""
    return buf.at[layer, jnp.arange(buf.shape[1]), slot].set(val[:, 0])


def _prefill_cache(cache, k, v, positions, cap, window):
    """Write prefill K/V into a fresh cache (last `cap` tokens if windowed)."""
    B, S = positions.shape
    if S >= cap:
        kk, vv, pp = k[:, S - cap:], v[:, S - cap:], positions[:, S - cap:]
        if window is not None:
            # decode inserts at slot = pos % cap; rearrange so slot s holds the
            # entry whose position ≡ s (mod cap): source j = (s - p0) mod cap.
            idx = (jnp.arange(cap)[None, :] - pp[:, 0:1]) % cap  # (B, cap)
            kk = jnp.take_along_axis(kk, idx[..., None, None], axis=1)
            vv = jnp.take_along_axis(vv, idx[..., None, None], axis=1)
            pp = jnp.take_along_axis(pp, idx, axis=1)
        return {"k": kk, "v": vv, "pos": pp}
    pad = cap - S
    kk = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    return {"k": kk, "v": vv, "pos": pp}


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)


def mla_attention(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                  cache: Cache, positions, layer=None) -> Tuple[jax.Array, Cache]:
    """In decode, ``cache`` holds stacked (L, B, S, ...) latent caches and
    the token is written at ``[layer, b, slot]``, as in ``gqa_attention``."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q_lat = rms_norm(x @ p["wq_a"], p["q_a_norm"]["scale"], cfg.norm_eps)
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])  # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    c_kv = rms_norm(x @ p["wkv_a"], p["kv_a_norm"]["scale"], cfg.norm_eps)  # (B,S,r_kv)
    k_rope = x @ p["wk_rope"]  # (B,S,dr) shared across heads

    cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    q_nope = constrain(q_nope, ("act_batch", "act_seq", "act_heads", None), px)

    if mode == "decode":
        assert cache is not None and S == 1
        slot = positions[:, 0]
        ckv_stack = _insert_slot(cache["c_kv"], c_kv, slot, layer)
        krope_stack = _insert_slot(cache["k_rope"], k_rope, slot, layer)
        pos_stack = _insert_slot(cache["pos"], positions, slot, layer)
        ckv_cache, krope_cache, pos_cache = (
            ckv_stack[layer], krope_stack[layer], pos_stack[layer])
        # absorbed-weight decode: score/combine in the compressed space
        q_c = jnp.einsum("bshn,lhn->bshl", q_nope, p["wk_nope"])  # (B,1,H,r_kv)
        s = (jnp.einsum("bshl,btl->bhst", q_c, ckv_cache) +
             jnp.einsum("bshr,btr->bhst", q_rope, krope_cache)).astype(jnp.float32)
        s = s * scale
        valid = (pos_cache >= 0) & (pos_cache <= positions[:, :1])  # (B, cap)
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        ctx_c = jnp.einsum("bhst,btl->bshl", prob.astype(ckv_cache.dtype), ckv_cache)
        out = jnp.einsum("bshl,lhv->bshv", ctx_c, p["wv"])  # (B,1,H,dv)
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        return y, {"c_kv": ckv_stack, "k_rope": krope_stack, "pos": pos_stack}

    # train / prefill: expand k_nope & v per head, run flash path
    k_nope = jnp.einsum("bsl,lhn->bshn", c_kv, p["wk_nope"])
    v = jnp.einsum("bsl,lhv->bshv", c_kv, p["wv"])
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    if _pallas_flash_ok(S, dn + dr, dv, None, px.pcfg.kernel):
        # MLA head dims rarely line up (dn+dr != dv); when they do the
        # tuned kernel applies unchanged — scale is 1/sqrt(q head dim)
        out = _pallas_flash_attention(q_full, k_full, v, px.pcfg.kernel)
    elif S >= px.pcfg.flash_threshold:
        out = _flash_attention(q_full, k_full, v, q_pos=positions, k_pos=positions,
                               window=None, scale=scale, px=px)
    else:
        out = _direct_attention(q_full, k_full, v, q_pos=positions, k_pos=positions,
                                window=None, scale=scale)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    new_cache = cache
    if mode == "prefill":
        assert cache is not None
        cap = cache["c_kv"].shape[1]
        pad = cap - S
        new_cache = {
            "c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
            "k_rope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
            "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1),
        }
    return y, new_cache


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based scatter dispatch, EP over `model` axis)


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
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if mo.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"][None, None, :]
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    top_vals, top_idx = lax.top_k(sel, K)  # (G,Tg,K)
    if mo.router_score == "sigmoid":
        gate = jnp.take_along_axis(scores, top_idx, axis=-1)
        weights = gate / (gate.sum(-1, keepdims=True) + 1e-9)
    else:
        weights = jnp.take_along_axis(scores, top_idx, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-9)

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
