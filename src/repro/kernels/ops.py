"""Jit'd wrappers + tunable config spaces for the Pallas kernels.

On non-TPU backends the kernels run in interpret mode (the kernel body
executes in Python on CPU) — the TPU is the TARGET, interpret is the
validation path. Each kernel exposes a SearchSpace whose invalid region is
the TPU resource model (VMEM capacity, MXU alignment): the exact structure
the paper tunes on GPUs, re-parameterized for TPU (DESIGN.md §2).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.searchspace import Param, SearchSpace
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import gemm as _gemm
from repro.kernels import matern_gp as _mgp
from repro.launch.roofline import VMEM_BYTES


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# -- GEMM ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def gemm(a, b, block_m=256, block_n=256, block_k=256, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _gemm.gemm(a, b, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=interpret)


def gemm_config_space(M: int = 1024, N: int = 1024, K: int = 1024) -> SearchSpace:
    """BO target: MXU tile shapes. Invalid = VMEM overflow / misalignment
    (checked by the objective, not the constraints — runtime invalids)."""
    vals = (64, 128, 256, 512, 1024)
    params = [Param("block_m", vals), Param("block_n", vals),
              Param("block_k", vals)]
    cons = [lambda c: M % c["block_m"] == 0,
            lambda c: N % c["block_n"] == 0,
            lambda c: K % c["block_k"] == 0]
    return SearchSpace(params, cons, name="pallas_gemm")


def gemm_valid(cfg: Dict, dtype_bytes: int = 2,
               vmem_bytes: int = VMEM_BYTES) -> bool:
    return _gemm.gemm_vmem_bytes(cfg["block_m"], cfg["block_n"],
                                 cfg["block_k"], dtype_bytes) <= vmem_bytes


# -- flash attention -----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "causal",
                                             "interpret"))
def flash_attention(q, k, v, block_q=512, block_kv=512, causal=True,
                    interpret=None):
    """q, k, v (B, S, H, hd) -> (B, S, H, hd). The kernel streams a
    head-major view, so each operand is transposed to (B, H, S, hd) and the
    output back: one extra HBM round trip per tensor."""
    if interpret is None:
        interpret = _interpret_default()
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _fa.flash_attention(q, k, v, block_q=block_q, block_kv=block_kv,
                              causal=causal, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def flash_config_space(S: int = 4096) -> SearchSpace:
    vals = (128, 256, 512, 1024, 2048)
    params = [Param("block_q", vals), Param("block_kv", vals)]
    cons = [lambda c: S % c["block_q"] == 0, lambda c: S % c["block_kv"] == 0]
    return SearchSpace(params, cons, name="pallas_flash")


def flash_valid(cfg: Dict, hd: int = 128, dtype_bytes: int = 2,
                vmem_bytes: int = VMEM_BYTES) -> bool:
    return _fa.flash_vmem_bytes(cfg["block_q"], cfg["block_kv"], hd,
                                dtype_bytes) <= vmem_bytes


# -- flash decode (single-token cache attention) --------------------------


@functools.partial(jax.jit, static_argnames=("window", "block_kv",
                                             "num_splits", "combine",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, cache_pos, cur_pos, layer=None,
                     window=None, block_kv=512, num_splits=1, combine="jax",
                     interpret=None):
    """Split-KV flash decode over the cache, semantics-matched to
    ``models.layers._decode_attention``: q (B, 1, H, hd), caches
    (B, S, KV, hd), ``cache_pos`` (B, S) absolute positions (-1 = empty
    slot), ``cur_pos`` (B,) the position being decoded. Returns
    (B, 1, H, hd).

    With ``layer`` (an int32 scalar, traced or not) the caches are the
    stacked caches of every layer, (L, B, S, KV, hd), and the kernel reads
    layer ``layer`` out of them in place; ``cache_pos`` is still that
    layer's (B, S).

    Slot validity — empty, future, or evicted by a rolling ``window`` —
    becomes an additive f32 bias row (0 / -inf). A capacity that tiles into
    ``num_splits × block_kv`` is read as it is; any other is padded with
    masked slots, which copies the layer's cache on every call (a serving
    cache is allocated at ``models.layers.decode_capacity`` slots, which
    the tile divides, so it is never padded).
    """
    if interpret is None:
        interpret = _interpret_default()
    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    S = k_cache.shape[2]
    valid = (cache_pos >= 0) & (cache_pos <= cur_pos[:, None])
    if window is not None:
        valid &= cache_pos > cur_pos[:, None] - window
    bias = jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)
    pad = (-S) % (num_splits * block_kv)
    if pad:
        k_cache, v_cache = (jnp.pad(
            jax.lax.dynamic_index_in_dim(c, layer, keepdims=True),
            ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            for c in (k_cache, v_cache))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        layer = 0
    out = _fd.flash_decode(q[:, 0], k_cache, v_cache, bias,
                           jnp.asarray(layer, jnp.int32),
                           block_kv=block_kv, num_splits=num_splits,
                           combine=combine, interpret=interpret)
    return out[:, None]


def decode_config_space(S: int = 2048) -> SearchSpace:
    """BO target for the decode cell: KV tile length, split count, and the
    cross-split combine strategy. ``S`` is the cache capacity; splits whose
    leading tiles already cover the whole cache are pure overhead and
    constrained out (padding makes any remaining combination runnable)."""
    params = [Param("block_kv", (128, 256, 512, 1024)),
              Param("num_splits", (1, 2, 4, 8)),
              Param("combine", _fd.COMBINE_STRATEGIES)]
    cons = [lambda c: c["block_kv"] * (c["num_splits"] - 1) < S]
    return SearchSpace(params, cons, name="pallas_flash_decode")


def decode_valid(cfg: Dict, KV: int = 1, G: int = 1, hd: int = 128,
                 dtype_bytes: int = 2, vmem_bytes: int = VMEM_BYTES) -> bool:
    return _fd.decode_vmem_bytes(cfg["block_kv"], KV, G, hd,
                                 dtype_bytes) <= vmem_bytes


# -- Matérn GP posterior ---------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ell", "nu", "block_n", "interpret"))
def gp_posterior(x_cand, x_obs, vinv_rows, w, mask, ell=2.0, nu="matern32",
                 block_n=512, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _mgp.gp_posterior(x_cand, x_obs, vinv_rows, w, mask, ell=ell,
                             nu=nu, block_n=block_n, interpret=interpret)


def gp_inputs_from_incremental(gp, pad_T: Optional[int] = None):
    """Package an IncrementalGP state as padded kernel inputs."""
    from repro.core.gp_fast import forward_substitute

    t = gp.t
    T = pad_T or max(128, 1 << (t - 1).bit_length())
    d = gp.dim
    x_obs = np.zeros((T, d), np.float32)
    x_obs[:t] = gp.X[:t]
    # invert the Cholesky factor in float64 — GP kernel matrices are
    # ill-conditioned and an fp32 inverse loses ~1% of the posterior mean.
    # Triangular solve against identity (O(t²) per rhs column), NOT
    # np.linalg.inv of the full padded factor: the generic inverse is O(T³)
    # on every packaging call and ignores the triangular structure.
    vinv = np.zeros((T, T), np.float32)
    vinv[:t, :t] = forward_substitute(
        gp.L[:t, :t], np.eye(t, dtype=np.float64)).astype(np.float32)
    yv = gp.y[:t]
    y_mean, y_std = float(yv.mean()), max(float(yv.std()), 1e-12)
    w = np.zeros(T, np.float32)
    w[:t] = forward_substitute(gp.L[:t, :t], (yv - y_mean) / y_std)
    mask = np.zeros(T, np.float32)
    mask[:t] = 1.0
    return x_obs, vinv, w, mask, y_mean, y_std


def gp_config_space(N: int = 16384) -> SearchSpace:
    vals = (128, 256, 512, 1024, 2048, 4096)
    params = [Param("block_n", vals)]
    return SearchSpace(params, [lambda c: N % c["block_n"] == 0],
                       name="pallas_matern_gp")


def gp_valid(cfg: Dict, T: int = 256, d: int = 16,
             vmem_bytes: int = VMEM_BYTES) -> bool:
    """VMEM check for the GP-posterior cell (gemm/flash had theirs from the
    start; ``gp_vmem_bytes`` existed but nothing consumed it)."""
    return _mgp.gp_vmem_bytes(cfg["block_n"], T, d) <= vmem_bytes
