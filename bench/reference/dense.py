"""Plain forward of a dense decoder, in float32 at full matmul precision.

Written from the configuration file alone; it imports nothing of the
system under test and takes nothing it made. Weights come from the seed
(``bench.weights``), one layer at a time, and rows go through each layer in
blocks, so the forward fits beside nothing else on one chip.

The model is the configuration as run: pre-norm RMSNorm blocks, rotary
positions on the whole head (rotate-half), grouped-query attention with a
causal mask, a SiLU-gated MLP, a final RMSNorm and an untied head.

``fp8=True`` is the control: each matmul's operands are rounded to
float8 e4m3 first (weights scaled per tensor, activations per row), the
step below bfloat16 that a faster path would take.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights as W

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x: jax.Array, axes) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq: str, x, w, fp8: bool, x_axes):
    """Activation ``x`` times weight ``w``; ``x_axes`` are x's contracted
    axes, over which the control scales each row."""
    if fp8:
        x, w = _fp8(x, x_axes), _fp8(w, None)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (N, T, heads, hd); rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, w, *, eps, theta, fp8):
    N, T, _ = x.shape
    pos = jnp.arange(T)
    h = _rms_norm(x, w["ln1"], eps)
    q = _rope(_mm("ntd,dhk->nthk", h, w["wq"], fp8, -1), pos, theta)
    k = _rope(_mm("ntd,dhk->nthk", h, w["wk"], fp8, -1), pos, theta)
    v = _mm("ntd,dhk->nthk", h, w["wv"], fp8, -1)
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("nqhk,nshk->nhqs", q, k, precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("nhqs,nshk->nqhk", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    x = x + _mm("nthk,hkd->ntd", o, w["wo"], fp8, (-2, -1))
    h = _rms_norm(x, w["ln2"], eps)
    g = jax.nn.silu(_mm("ntd,df->ntf", h, w["wg"], fp8, -1))
    u = _mm("ntd,df->ntf", h, w["wu"], fp8, -1)
    return x + _mm("ntf,fd->ntd", g * u, w["wd"], fp8, -1)


def _head(x, norm, head, *, eps, fp8):
    return _mm("ntd,dv->ntv", _rms_norm(x, norm, eps), head, fp8, -1)


def norm_eps(cfg: dict) -> float:
    return float(cfg.get("rms_norm_eps", cfg.get("layer_norm_eps")))


def check_supported(cfg: dict) -> None:
    """Refuse a configuration this forward does not compute."""
    if cfg.get("hidden_act") != "silu":
        raise NotImplementedError(f"hidden_act {cfg.get('hidden_act')!r}")
    if cfg.get("norm", "rmsnorm") != "rmsnorm":
        raise NotImplementedError(f"norm {cfg['norm']!r}")
    if float(cfg.get("partial_rotary_factor", 1.0)) != 1.0:
        raise NotImplementedError("rotary on part of the head")
    if cfg.get("bias") or cfg.get("use_qkv_bias"):
        raise NotImplementedError("biases")
    if cfg.get("tie_word_embeddings"):
        raise NotImplementedError("tied embeddings")


def logits(cfg: dict, seed: int, tokens: np.ndarray, first: int, *,
           fp8: bool = False, rows: int = 1) -> jax.Array:
    """Logits (N, T - first, V) at positions ``first``..T-1 of each row of
    ``tokens`` (N, T), each row its own sequence from position 0."""
    check_supported(cfg)
    n = W.dims(cfg)
    eps, theta = norm_eps(cfg), float(cfg["rope_theta"])
    tokens = np.asarray(tokens, np.int32)
    N = tokens.shape[0]
    layer_fn = jax.jit(functools.partial(_layer, eps=eps, theta=theta,
                                         fp8=fp8))
    make_layer = jax.jit(lambda l: W.layer(cfg, seed, l))
    g = W.globals_(cfg, seed)
    table = _fp8(g["embed"], None) if fp8 else g["embed"]
    blocks = [table[jnp.asarray(tokens[i:i + rows])]
              for i in range(0, N, rows)]
    del table
    for l in range(n["L"]):
        w = make_layer(l)
        blocks = [layer_fn(x, w) for x in blocks]
        del w
    head = jax.jit(functools.partial(_head, eps=eps, fp8=fp8))
    return jnp.concatenate(
        [head(x[:, first:], g["final_norm"], g["head"]) for x in blocks])
