"""Fused Matérn GP posterior Pallas TPU kernel.

The paper's §III-G hot loop: "we exhaustively predict every discrete point in
the model" — posterior mean/variance over ALL candidate configs, every
iteration. This kernel fuses, per candidate tile resident in VMEM:

    pairwise distance (obs × cand)  →  Matérn ν covariance  →
    V = L⁻¹K (triangular matmul against preloaded L⁻¹ rows)  →
    mean = Vᵀw  and  var = 1 − Σ V²

Observations (t ≤ 256 padded, masked) stay resident; candidates stream in
`block_n` tiles. Both matmuls are MXU-shaped (T×d @ d×bn and T×T @ T×bn).
Tunable: block_n (VMEM capacity trade-off). Oracle: repro.kernels.ref.gp_posterior.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SQRT3 = math.sqrt(3.0)
SQRT5 = math.sqrt(5.0)
HIGHEST = jax.lax.Precision.HIGHEST


def _matern(r, ell: float, nu: str):
    s = r / ell
    if nu == "matern12":
        return jnp.exp(-s)
    if nu == "matern32":
        t = SQRT3 * s
        return (1.0 + t) * jnp.exp(-t)
    if nu == "matern52":
        t = SQRT5 * s
        return (1.0 + t + (5.0 / 3.0) * jnp.square(s)) * jnp.exp(-t)
    if nu == "rbf":
        return jnp.exp(-0.5 * jnp.square(s))
    raise ValueError(nu)


def _gp_kernel(xc_ref, xo_ref, vinv_ref, w_ref, mask_ref,
               mean_ref, var_ref, *, ell: float, nu: str):
    xc = xc_ref[...]                                  # (bn, d)
    xo = xo_ref[...]                                  # (T, d)
    mask = mask_ref[...]                              # (T, 1) 1.0/0.0
    # full f32 matmuls: the MXU's default f32 precision (bf16 passes)
    # costs ~1e-2 of the posterior variance, which 1 - sum(V^2) amplifies
    d2 = (jnp.sum(xo * xo, axis=1, keepdims=True)
          + jnp.sum(xc * xc, axis=1)[None, :]
          - 2.0 * jnp.dot(xo, xc.T, precision=HIGHEST,
                          preferred_element_type=jnp.float32))
    r = jnp.sqrt(jnp.maximum(d2, 0.0))
    K = _matern(r, ell, nu) * mask                    # (T, bn), padded rows 0
    V = jnp.dot(vinv_ref[...], K, precision=HIGHEST,
                preferred_element_type=jnp.float32)
    mean_ref[...] = (w_ref[...] * V).sum(axis=0, keepdims=True)   # (1, bn)
    var_ref[...] = jnp.maximum(1.0 - jnp.sum(V * V, axis=0, keepdims=True),
                               1e-12)


def gp_posterior(x_cand: jax.Array, x_obs: jax.Array, vinv_rows: jax.Array,
                 w: jax.Array, mask: jax.Array, *, ell: float = 2.0,
                 nu: str = "matern32", block_n: int = 512,
                 interpret: bool = False):
    """x_cand (N,d); x_obs (T,d) padded; vinv_rows = L⁻¹ (T,T) with identity
    on padded rows; w (T,) = L⁻¹ỹ zero-padded; mask (T,) 1 for real obs.
    Returns (mean (N,), var (N,))."""
    N, d = x_cand.shape
    T = x_obs.shape[0]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    mean, var = pl.pallas_call(
        functools.partial(_gp_kernel, ell=ell, nu=nu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((T, d), lambda i: (0, 0)),
            pl.BlockSpec((T, T), lambda i: (0, 0)),
            pl.BlockSpec((T, 1), lambda i: (0, 0)),
            pl.BlockSpec((T, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(x_cand, x_obs, vinv_rows, w[:, None], mask[:, None])
    return mean[0], var[0]


def gp_vmem_bytes(block_n: int, T: int, d: int) -> int:
    return 4 * (block_n * d + T * d + T * T + 2 * T + block_n * (T + 2))
