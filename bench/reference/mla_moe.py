"""Plain forward of a DeepSeek-V3-style decoder, as cut to one chip's share,
in float32 at full matmul precision.

Written from the configuration file and the published equations
(``modeling_deepseek.py``, and DeepSeek's ``inference/model.py`` for the
gate); it imports nothing of the system under test and takes nothing it
made. Weights come from the seed (``bench.weights_mla_moe``), one layer at
a time, and rows go through each layer in blocks. The router's selection
bias is levelled on them, as training levels it (``router_bias``).

Each layer is pre-norm. Attention is MLA without absorption: q from the
normed q latent, per-head k_nope and v from the normed kv latent, one
k_rope shared by every head, YaRN rope (rotate-half; see the
configuration's ``assumed``), a causal softmax scaled by 1/sqrt(q head
dim) times YaRN's mscale squared. The first ``first_k_dense_replace``
layers have a SiLU-gated MLP; the others a sigmoid router over all
``routed_experts_published`` experts (bias for the choice only, a group
scoring the sum of its two best, the best ``topk_group`` groups, the top
``num_experts_per_tok`` in them with the rest masked to -inf, weights
normalized and times ``routed_scaling_factor``), the part of the routed
sum that the held experts give (``n_routed_experts`` of them from
``first_expert_held``), and the shared expert. A final norm and the head
over the sliced vocabulary.

``fp8=True`` is the control, as in ``bench.reference.dense``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla_moe as W
from bench.weights import seed_key
from bench.reference.dense import HIGHEST, _fp8, _mm, _rms_norm

#: heads whose scores are materialized at once
HEAD_BLOCK = 32
#: sequences of token ids, drawn from the seed, that the selection bias is
#: levelled on, their length, and the balancing steps. A sequence's tokens
#: route alike, so the levelled share of a group of experts carries the
#: sample's error, about 1/sqrt(rows) of a sequence's own spread
LEVEL_ROWS, LEVEL_TOKENS, LEVEL_STEPS = 32, 2048, 256
#: fold-in tag that keeps the levelling tokens apart from other keys
LEVEL_TAG = 0x6C65766C


def yarn_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """``DeepseekV3YarnRotaryEmbedding``'s inverse frequencies."""
    y, base, dim = cfg["rope_scaling"], cfg["rope_theta"], \
        cfg["qk_rope_head_dim"]

    def corr_dim(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = freq_extra / y["factor"]
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(cfg: dict) -> float:
    y = cfg["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, positions, inv_freq, mscale):
    """x (N, T, heads, dr); rotate-half, cos and sin times ``mscale``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    c = (jnp.cos(ang) * mscale)[None, :, None]
    s = (jnp.sin(ang) * mscale)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(x, w, *, eps, inv_freq, rope_mscale, scale, fp8):
    N, T, _ = x.shape
    pos = jnp.arange(T)
    dn = w["wk_nope"].shape[-1]
    h = _rms_norm(x, w["ln1"], eps)
    q = _mm("ntl,lhk->nthk",
            _rms_norm(_mm("ntd,dl->ntl", h, w["wq_a"], fp8, -1),
                      w["q_a_norm"], eps), w["wq_b"], fp8, -1)
    c = _rms_norm(_mm("ntd,dl->ntl", h, w["wkv_a"], fp8, -1), w["kv_a_norm"],
                  eps)
    k_pe = _mm("ntd,dr->ntr", h, w["wk_rope"], fp8, -1)[:, :, None]
    k_nope = _mm("ntl,lhk->nthk", c, w["wk_nope"], fp8, -1)
    v = _mm("ntl,lhk->nthk", c, w["wv"], fp8, -1)
    q_pe = _rope(q[..., dn:], pos, inv_freq, rope_mscale)
    k_pe = _rope(k_pe, pos, inv_freq, rope_mscale)
    H = q.shape[2]
    causal = pos[:, None] >= pos[None, :]
    outs = []
    for h0 in range(0, H, HEAD_BLOCK):
        sl = slice(h0, h0 + HEAD_BLOCK)
        s = (jnp.einsum("nqhk,nshk->nhqs", q[:, :, sl, :dn], k_nope[:, :, sl],
                        precision=HIGHEST)
             + jnp.einsum("nqhr,nsr->nhqs", q_pe[:, :, sl], k_pe[:, :, 0],
                          precision=HIGHEST)) * scale
        s = jnp.where(causal[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("nhqs,nshk->nqhk", jax.nn.softmax(s, -1),
                               v[:, :, sl], precision=HIGHEST))
    o = jnp.concatenate(outs, 2)
    return x + _mm("nthk,hkd->ntd", o, w["wo"], fp8, (-2, -1))


def _mlp(h, wg, wu, wd, fp8):
    g = jax.nn.silu(_mm("ntd,df->ntf", h, wg, fp8, -1))
    return _mm("ntf,fd->ntd", g * _mm("ntd,df->ntf", h, wu, fp8, -1), wd,
               fp8, -1)


def pick(choice, *, n_group, topk_group, top_k):
    """The experts picked (..., K) by biased scores ``choice``: a group
    scores the sum of its two best, the best ``topk_group`` groups stay,
    the rest are masked to -inf, and the top ``top_k`` are picked."""
    E = choice.shape[-1]
    grouped = choice.reshape(*choice.shape[:-1], n_group, E // n_group)
    group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, groups = jax.lax.top_k(group_scores, topk_group)
    keep = jnp.any(groups[..., None] == jnp.arange(n_group), -2)
    choice = jnp.where(jnp.repeat(keep, E // n_group, -1), choice, -jnp.inf)
    return jax.lax.top_k(choice, top_k)[1]


def route(h, router, bias, *, n_group, topk_group, top_k, scaling, fp8):
    """(experts picked (N,T,K), their weights (N,T,K)) by the published
    ``noaux_tc`` gate."""
    scores = jax.nn.sigmoid(_mm("ntd,de->nte", h, router, fp8, -1))
    idx = pick(scores + bias, n_group=n_group, topk_group=topk_group,
               top_k=top_k)
    wts = jnp.take_along_axis(scores, idx, -1)
    return idx, wts / (wts.sum(-1, keepdims=True) + 1e-20) * scaling


def level(scores, *, steps=LEVEL_STEPS, **pick_kw):
    """A selection bias (E,) that levels the experts' loads over ``scores``
    (N, E): DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2412.19437
    section 2.1.2) run on fixed scores, each step lowering by ``gamma`` the
    bias of every expert above the mean load and raising the others, with
    ``gamma`` falling from 2^-4 to 2^-12."""
    E = scores.shape[-1]

    def step(i, b):
        load = jnp.zeros(E).at[pick(scores + b, **pick_kw).ravel()].add(1.0)
        gamma = 2.0 ** (-4.0 - 8.0 * i / steps)
        return b - gamma * jnp.sign(load - load.mean())
    return jax.lax.fori_loop(0, steps, step, jnp.zeros(E, jnp.float32))


def _moe(x, w, bias, *, eps, route_kw, first_held, fp8):
    h = _rms_norm(x, w["ln2"], eps)
    idx, wts = route(h, w["router"], bias, fp8=fp8, **route_kw)
    y = _mlp(h, w["swg"], w["swu"], w["swd"], fp8)
    for e in range(w["ewg"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first_held + e, wts, 0.0), -1)
        y = y + gate[..., None] * _mlp(h, w["ewg"][e], w["ewu"][e],
                                       w["ewd"][e], fp8)
    return x + y


def _dense(x, w, *, eps, fp8):
    return x + _mlp(_rms_norm(x, w["ln2"], eps), w["wg"], w["wu"], w["wd"],
                    fp8)


def _head(x, norm, head, *, eps, fp8):
    return _mm("ntd,dv->ntv", _rms_norm(x, norm, eps), head, fp8, -1)


def check_supported(cfg: dict) -> None:
    """Refuse a configuration this forward does not compute."""
    if cfg.get("hidden_act") != "silu":
        raise NotImplementedError(f"hidden_act {cfg.get('hidden_act')!r}")
    if (cfg.get("scoring_func"), cfg.get("topk_method")) != ("sigmoid",
                                                            "noaux_tc"):
        raise NotImplementedError("a gate other than sigmoid noaux_tc")
    if not cfg.get("norm_topk_prob"):
        raise NotImplementedError("unnormalized routed weights")
    if (cfg.get("rope_scaling") or {}).get("type") != "yarn":
        raise NotImplementedError("rope without YaRN")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
        raise NotImplementedError("biases or tied embeddings")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise NotImplementedError("dense layers between the expert layers")


def _layers(cfg: dict, fp8: bool):
    """The jitted attention, dense layer, MoE layer and head."""
    n = W.dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    y = cfg["rope_scaling"]
    attn = jax.jit(functools.partial(
        _attention, eps=eps, inv_freq=jnp.asarray(yarn_inv_freq(cfg),
                                                  jnp.float32),
        rope_mscale=yarn_mscale(y["factor"], y["mscale"])
        / yarn_mscale(y["factor"], y["mscale_all_dim"]),
        scale=softmax_scale(cfg), fp8=fp8))
    dense = jax.jit(functools.partial(_dense, eps=eps, fp8=fp8))
    moe = jax.jit(functools.partial(
        _moe, eps=eps, first_held=cfg["first_expert_held"], fp8=fp8,
        route_kw=dict(n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      top_k=n["K"], scaling=cfg["routed_scaling_factor"])))
    head = jax.jit(functools.partial(_head, eps=eps, fp8=fp8))
    return attn, dense, moe, head


def router_bias(cfg: dict, seed: int) -> np.ndarray:
    """The MoE layers' selection biases (L - Ld, E), float32, as trained
    balancing leaves them: levelled (``level``) on the scores of
    ``LEVEL_ROWS`` sequences of ``LEVEL_TOKENS`` ids drawn from the seed.
    A random bias would leave a seed's held experts with a fraction of
    their share of the copies, or a multiple of it. The ids flow through each MoE layer's
    shared expert alone, so that every share of the experts gets the same
    bias."""
    # the bias is levelled in float32 whatever type the program serves in
    return _router_bias(json.dumps(dict(cfg, torch_dtype=None),
                                   sort_keys=True), int(seed))


@functools.lru_cache(maxsize=2)
def _router_bias(cfg_json: str, seed: int) -> np.ndarray:
    cfg = json.loads(cfg_json)
    check_supported(cfg)
    n = W.dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    attn, dense, _, _ = _layers(cfg, False)
    lev = jax.jit(functools.partial(
        level, n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        top_k=n["K"]))

    @jax.jit
    def shared_only(x, w):
        """Scores over every expert, and the layer's output with its shared
        expert alone."""
        h = _rms_norm(x, w["ln2"], eps)
        scores = jax.nn.sigmoid(_mm("ntd,de->nte", h, w["router"], False,
                                    -1))
        return scores, x + _mlp(h, w["swg"], w["swu"], w["swd"], False)

    key = jax.random.fold_in(seed_key(seed), LEVEL_TAG)
    tokens = jax.random.randint(key, (LEVEL_ROWS, LEVEL_TOKENS), 0, n["V"],
                                jnp.int32)
    table = W.globals_(cfg, seed)["embed"]
    xs = [table[tokens[i:i + 1]] for i in range(LEVEL_ROWS)]
    del table
    biases = []
    for l in range(n["L"]):
        w = jax.jit(lambda l=l: W.layer(cfg, seed, l))()
        xs = [attn(x, w) for x in xs]
        if l < n["Ld"]:
            xs = [dense(x, w) for x in xs]
        else:
            out = [shared_only(x, w) for x in xs]
            xs = [x for _, x in out]
            biases.append(lev(jnp.concatenate(
                [sc for sc, _ in out]).reshape(-1, n["E"])))
        del w
    return np.stack([np.asarray(b) for b in biases])


def logits(cfg: dict, seed: int, tokens: np.ndarray, first: int, *,
           fp8: bool = False, rows: int = 1) -> jax.Array:
    """Logits (N, T - first, V) at positions ``first``..T-1 of each row of
    ``tokens`` (N, T), each row its own sequence from position 0."""
    check_supported(cfg)
    n = W.dims(cfg)
    bias = router_bias(cfg, seed)
    attn, dense, moe, head = _layers(cfg, fp8)
    tokens = np.asarray(tokens, np.int32)
    N = tokens.shape[0]
    g = W.globals_(cfg, seed)
    table = _fp8(g["embed"], None) if fp8 else g["embed"]
    blocks = [table[jnp.asarray(tokens[i:i + rows])]
              for i in range(0, N, rows)]
    del table
    for l in range(n["L"]):
        w = jax.jit(lambda l=l: W.layer(cfg, seed, l))()
        if l < n["Ld"]:
            blocks = [dense(attn(x, w), w) for x in blocks]
        else:
            b = jnp.asarray(bias[l - n["Ld"]])
            blocks = [moe(attn(x, w), w, b) for x in blocks]
        del w
    return jnp.concatenate(
        [head(x[:, first:], g["final_norm"], g["head"]) for x in blocks])
