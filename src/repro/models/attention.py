"""Attention: the layers, their cores, the choice among the cores
(``layer_window``, ``prefill_path``, ``decode_path``, made at trace time,
never changing what a layer computes), and the format of their cache
(``cache_layer_specs``)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.arch import ArchConfig
from repro.kernels import ops as kernel_ops
from repro.models.layers import (Cache, apply_rope, rms_norm, rope_tables,
                                 yarn_mscale)
from repro.models.params import ParamSpec, Tree
from repro.parallel.sharding import ParallelConfig, ShardCtx, constrain

#: layer kinds whose mixer is attention
ATTENTION_KINDS = ("attn", "attn_dense")

#: cache leaves a decode step writes one token into
TOKEN_LEAVES = frozenset({"k", "v", "pos", "latent"})

# ---------------------------------------------------------------------------
# the decisions


def layer_window(cfg: ArchConfig, kind: str) -> Optional[int]:
    """Local window of a ``kind`` layer's attention and of its cache, None
    where it is global: ``local_window`` windows the ``attn`` layers of a
    pattern that mixes kinds (RecurrentGemma), GQA only."""
    if (kind == "attn" and cfg.block_pattern != ("attn",)
            and cfg.attention != "mla"):
        return cfg.local_window
    return None


def prefill_path(cfg: ArchConfig, pcfg: ParallelConfig, S: int,
                 window: Optional[int]) -> str:
    """Core of a train or prefill attention over ``S`` positions:
    ``"pallas"`` where ``use_flash`` opts in, the attention is global and
    the tuned blocks tile ``S`` (never MLA: the kernel takes one head width
    and 1/sqrt(head) scale); else ``"blockwise"`` from ``flash_threshold``
    positions on, ``"direct"`` below."""
    kc = pcfg.kernel
    if (kc is not None and kc.use_flash and window is None
            and cfg.attention != "mla" and S % kc.flash_block_q == 0
            and S % kc.flash_block_kv == 0):
        return "pallas"
    return "blockwise" if S >= pcfg.flash_threshold else "direct"


def decode_path(cfg: ArchConfig, pcfg: ParallelConfig) -> str:
    """Core of a decode step's attention: ``"pallas"`` where
    ``use_decode`` opts in (GQA and MLA have a kernel each; the wrappers
    take any window, occupancy and capacity), ``"jax"`` otherwise."""
    kc = pcfg.kernel
    return "pallas" if kc is not None and kc.use_decode else "jax"


# ---------------------------------------------------------------------------
# the cores


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


#: bytes of the float32 scores one step of the blockwise scan may hold
#: (rows × heads × queries × KV block); past it the queries run in chunks
SCORE_BLOCK_BYTES = 1 << 30


def _flash_attention(q, k, v, *, q_pos, k_pos, window, scale, px: ShardCtx):
    """Blockwise online-softmax attention (lax.scan over KV blocks).

    Keeps O(Sq·block_kv) transients instead of O(Sq·Sk). With
    ``px.pcfg.attn_q_chunks > 1`` the causal upper-triangle of KV blocks is
    statically skipped per q-chunk (saves ~(1 - (c+1)/2c) of attention FLOPs).
    A prefill whose scores for one KV block would pass
    ``SCORE_BLOCK_BYTES`` (DeepSeek-V3's 128 heads over a batch of 2k
    prompts hold 8.6 GB) doubles its chunks until they fit.
    """
    pcfg = px.pcfg
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]  # MLA: v head dim differs from q/k head dim
    G = H // KV
    bk = min(pcfg.attn_block_kv, Sk)
    n_chunks = pcfg.attn_q_chunks if (Sq == Sk and Sq % pcfg.attn_q_chunks == 0) else 1
    while (Sq == Sk and B * H * (Sq // n_chunks) * bk * 4 > SCORE_BLOCK_BYTES
           and Sq % (2 * n_chunks) == 0):
        n_chunks *= 2

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


def _pallas_flash_attention(q, k, v, kc):
    """GQA-expanded dispatch into the tuned Pallas flash kernel.

    The kernel is an MHA core (its fp32 (m, l, acc) state lives in VMEM and
    never round-trips through HBM, which the lax.scan formulation above
    cannot express); GQA feeds it by expanding KV heads to the q head count.
    Assumes contiguous positions starting at 0 — what train/prefill steps
    produce; windowed/decode paths never reach here (``prefill_path``).
    """
    G = q.shape[2] // k.shape[2]
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    return kernel_ops.flash_attention(
        q, k, v, block_q=kc.flash_block_q, block_kv=kc.flash_block_kv,
        causal=True, interpret=kc.interpret)


def _prefill_attention(path: str, q, k, v, *, positions, window, scale,
                       px: ShardCtx):
    """Causal attention of a train or prefill step on the core ``path``
    (``prefill_path``) names."""
    if path == "pallas":
        return _pallas_flash_attention(q, k, v, px.pcfg.kernel)
    if path == "blockwise":
        return _flash_attention(q, k, v, q_pos=positions, k_pos=positions,
                                window=window, scale=scale, px=px)
    return _direct_attention(q, k, v, q_pos=positions, k_pos=positions,
                             window=window, scale=scale)


def _decode_blocks(kc) -> dict:
    """``kc``'s tuned blocks as both flash-decode kernels take them (each
    matched to its pure-JAX core, ``_decode_attention`` for GQA and
    ``_mla_latent_decode`` for MLA; parity pinned in tests)."""
    return dict(block_kv=kc.decode_block_kv, num_splits=kc.decode_num_splits,
                combine=kc.decode_combine, interpret=kc.interpret)


def _decode_attention(q, k_cache, v_cache, *, cache_pos, cur_pos, window, scale):
    """Single-token attention over a cache. q (B,1,H,hd), head-major cache
    (B,KV,S,hd).

    cache_pos (B,S): absolute position stored in each cache slot (-1 = empty).
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, k_cache).astype(jnp.float32) * scale
    valid = (cache_pos >= 0) & (cache_pos <= cur_pos[:, None])
    if window is not None:
        valid &= cache_pos > cur_pos[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


def _mla_latent_decode(q_lat, lat_stack, pos, cur_pos, *, r, scale, layer):
    """Absorbed-weight decode attention over a latent cache: one KV head
    whose K is each slot's [c_kv ‖ k_rope ‖ 0] row and whose V is its first
    ``r`` (c_kv). q_lat (B,1,H,W) likewise [q_c ‖ q_rope ‖ 0], ``lat_stack``
    the stacked (L,B,S,W) cache read at ``layer``; returns the context in
    the latent space (B,1,H,r)."""
    # whole rows on both sides: the zero lanes add nothing, and the cache
    # is read as it lies, with no slice of it
    lat = lat_stack[layer]
    s = jnp.einsum("bshl,btl->bhst", q_lat, lat).astype(jnp.float32) * scale
    valid = (pos >= 0) & (pos <= cur_pos[:, None])           # (B, cap)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btl->bshl", prob.astype(lat.dtype), lat)
    return ctx[..., :r]


# ---------------------------------------------------------------------------
# the cache


def _cache_slot(pos, capacity, window):
    """Rolling slot for windowed caches; direct slot otherwise."""
    return jnp.remainder(pos, capacity) if window is not None else pos


def _insert_slot(buf, val, slot, layer, axis=2):
    """Insert val (B,1,...) at per-batch slot (B,) of layer ``layer`` of a
    stacked buffer (L,B,...) whose slot axis is ``axis``: a scatter that
    updates ``buf`` in place. The axes between layer and slot (batch, and
    a head-major cache's heads) are indexed, not sliced, so the scatter
    writes rows of the dims after the slot alone; with a slice among them,
    XLA would lay the stack out slot-major for the scatter and copy it."""
    n = axis - 1
    idx = [jnp.arange(buf.shape[d]).reshape((-1,) + (1,) * (n - d))
           for d in range(1, axis)]
    return buf.at[(layer, *idx, slot.reshape((-1,) + (1,) * (n - 1)))].set(
        val[:, 0])


def _prefill_cache(k, v, positions, cap, window):
    """Write prefill K/V (B,S,KV,hd) into a fresh head-major cache
    (B,KV,cap,hd) (last `cap` tokens if windowed). XLA fuses the
    transpose into the copy into the cache: no pass of its own."""
    B, S = positions.shape
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if S >= cap:
        kk, vv = k[:, :, S - cap:], v[:, :, S - cap:]
        pp = positions[:, S - cap:]
        if window is not None:
            # decode inserts at slot = pos % cap; rearrange so slot s holds the
            # entry whose position ≡ s (mod cap): source j = (s - p0) mod cap.
            idx = (jnp.arange(cap)[None, :] - pp[:, 0:1]) % cap  # (B, cap)
            kk = jnp.take_along_axis(kk, idx[:, None, :, None], axis=2)
            vv = jnp.take_along_axis(vv, idx[:, None, :, None], axis=2)
            pp = jnp.take_along_axis(pp, idx, axis=1)
        return {"k": kk, "v": vv, "pos": pp}
    pad = cap - S
    kk = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vv = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pp = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    return {"k": kk, "v": vv, "pos": pp}


#: lanes of a TPU tile: a latent row is padded to a multiple of them
LANES = 128


def mla_latent_width(m) -> int:
    """Width of a latent cache slot: c_kv (``kv_lora_rank``) then k_rope
    (``qk_rope_head_dim``), zero-padded to whole lanes. An unaligned width
    (576 for DeepSeek-V3) would be laid out by XLA with the slot axis
    minor, and every decode step would then transpose the whole stack
    for the kernel."""
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // LANES) * LANES


def _pad_lanes(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def decode_capacity(cfg: ArchConfig, cap: int, kc) -> int:
    """Slots to allocate for a K/V (or latent) cache of ``cap`` positions:
    where ``decode_path`` picks a Pallas kernel, ``cap`` rounded up to its
    tile (``decode_block_kv × decode_num_splits``), so that it reads the
    cache as it is and no decode step pads it. The extra slots stay empty
    (``pos`` -1) and are masked like any empty slot."""
    if decode_path(cfg, ParallelConfig(kernel=kc)) != "pallas":
        return cap
    tile = kc.decode_block_kv * kc.decode_num_splits
    return -(-cap // tile) * tile


def cache_layer_specs(cfg: ArchConfig, kind: str, batch: int, cap: int,
                      kc=None) -> Tree:
    """One ``kind`` attention layer's cache of ``cap`` positions (the last
    ``layer_window`` where windowed) at ``decode_capacity`` slots: latent
    rows or head-major K/V (each head's slots the one tile the decode
    kernel reads), ``pos``, cross-attention K/V."""
    dt, kv, hd = cfg.dtype, cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.attention == "mla":
        c = decode_capacity(cfg, cap, kc)
        t: Tree = {"latent": ParamSpec(
            (batch, c, mla_latent_width(cfg.mla)),
            ("act_batch", "act_cache_seq", None), init="zeros", dtype=dt)}
    else:
        window = layer_window(cfg, kind)
        c = decode_capacity(cfg, min(cap, window) if window else cap, kc)
        t = {n: ParamSpec((batch, kv, c, hd), ("act_batch", "act_kv_heads",
                                               "act_cache_seq", None),
                          init="zeros", dtype=dt) for n in ("k", "v")}
    t["pos"] = ParamSpec((batch, c), ("act_batch", "act_cache_seq"),
                         init="neg_ones", dtype="int32")
    if cfg.cross_attention:
        t.update({n: ParamSpec((batch, cfg.cross_seq, kv, hd),
                               ("act_batch", None, "act_kv_heads", None),
                               init="zeros", dtype=dt)
                  for n in ("cross_k", "cross_v")})
    return t


# ---------------------------------------------------------------------------
# GQA / MQA / MHA attention layer (optionally local-windowed, cross-attn)


def gqa_attention(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                  cache: Cache, positions, window=None,
                  layer=None) -> Tuple[jax.Array, Cache]:
    """In decode, ``cache`` holds the segment's stacked leaves, K/V
    head-major (L, B, KV, S, hd) and ``pos`` (L, B, S), and ``layer`` is
    this layer's index into them: the token is written at
    ``[layer, b, :, slot]`` (``pos`` at ``[layer, b, slot]``) and the new
    stacks are returned (``models.model.forward`` carries them through the
    layer loop)."""
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
        slot = _cache_slot(positions[:, 0], cache["k"].shape[3], window)
        k_cache = _insert_slot(cache["k"], k, slot, layer, axis=3)
        v_cache = _insert_slot(cache["v"], v, slot, layer, axis=3)
        cache_pos = _insert_slot(cache["pos"], positions, slot, layer)
        if decode_path(cfg, px.pcfg) == "pallas":
            # reads layer ``layer`` out of the stacks in place
            out = kernel_ops.decode_attention(
                q, k_cache, v_cache, cache_pos[layer], positions[:, 0], layer,
                window=window, **_decode_blocks(px.pcfg.kernel))
        else:
            out = _decode_attention(q, k_cache[layer], v_cache[layer],
                                    cache_pos=cache_pos[layer],
                                    cur_pos=positions[:, 0], window=window,
                                    scale=scale)
        new_cache = {"k": k_cache, "v": v_cache, "pos": cache_pos}
    else:
        out = _prefill_attention(prefill_path(cfg, px.pcfg, S, window),
                                 q, k, v, positions=positions, window=window,
                                 scale=scale, px=px)
        if mode == "prefill":
            assert cache is not None
            new_cache = _prefill_cache(k, v, positions, cache["k"].shape[2],
                                       window)
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


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """1/sqrt(q head dim), times YaRN's mscale squared where the config
    scales its rope over every dimension (``mscale_all_dim``)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_attention(p, x, *, cfg: ArchConfig, px: ShardCtx, mode: str,
                  cache: Cache, positions, layer=None) -> Tuple[jax.Array, Cache]:
    """In decode, ``cache`` holds the stacked (L, B, S, ...) latent cache:
    ``latent``, each slot's normalized c_kv followed by its roped k_rope
    (``mla_latent_width``), and ``pos``; the token is written at
    ``[layer, b, slot]``, as ``pos`` is in ``gqa_attention``."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    r = m.kv_lora_rank
    scale = mla_softmax_scale(cfg)

    q_lat = rms_norm(x @ p["wq_a"], p["q_a_norm"]["scale"], cfg.norm_eps)
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])  # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    c_kv = rms_norm(x @ p["wkv_a"], p["kv_a_norm"]["scale"], cfg.norm_eps)  # (B,S,r_kv)
    k_rope = x @ p["wk_rope"]  # (B,S,dr) shared across heads

    cos, sin = rope_tables(positions, dr, cfg.rope_theta, cfg.rope_scaling)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    q_nope = constrain(q_nope, ("act_batch", "act_seq", "act_heads", None), px)
    width = mla_latent_width(m)
    latent = _pad_lanes(jnp.concatenate([c_kv, k_rope], axis=-1), width)

    if mode == "decode":
        assert cache is not None and S == 1
        slot = positions[:, 0]
        lat_stack = _insert_slot(cache["latent"], latent, slot, layer)
        pos_stack = _insert_slot(cache["pos"], positions, slot, layer)
        # absorbed-weight decode: score/combine in the compressed space
        q_c = jnp.einsum("bshn,lhn->bshl", q_nope, p["wk_nope"])  # (B,1,H,r)
        q_lat = _pad_lanes(jnp.concatenate([q_c, q_rope], axis=-1), width)
        if decode_path(cfg, px.pcfg) == "pallas":
            ctx_c = kernel_ops.mla_decode_attention(
                q_lat, lat_stack, pos_stack[layer], positions[:, 0], layer,
                v_width=r, scale=scale, **_decode_blocks(px.pcfg.kernel))
        else:
            ctx_c = _mla_latent_decode(
                q_lat, lat_stack, pos_stack[layer], positions[:, 0], r=r,
                scale=scale, layer=layer)
        out = jnp.einsum("bshl,lhv->bshv", ctx_c, p["wv"])  # (B,1,H,dv)
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        return y, {"latent": lat_stack, "pos": pos_stack}

    # train / prefill: expand k_nope & v per head
    k_nope = jnp.einsum("bsl,lhn->bshn", c_kv, p["wk_nope"])
    v = jnp.einsum("bsl,lhv->bshv", c_kv, p["wv"])
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    out = _prefill_attention(prefill_path(cfg, px.pcfg, S, None), q_full,
                             k_full, v, positions=positions, window=None,
                             scale=scale, px=px)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    new_cache = cache
    if mode == "prefill":
        assert cache is not None
        pad = cache["latent"].shape[1] - S
        new_cache = {
            "latent": jnp.pad(latent, ((0, 0), (0, pad), (0, 0))),
            "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1),
        }
    return y, new_cache
