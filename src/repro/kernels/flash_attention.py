"""Causal flash attention Pallas TPU kernel (tunable block_q / block_kv).

Online-softmax over KV blocks with the running (m, l, acc) state in VMEM —
the accumulator NEVER touches HBM, which is precisely what the pure-JAX
blockwise attention in repro.models.attention cannot express (its fp32
accumulator is an HLO tensor; see EXPERIMENTS.md §Perf hillclimb #3).
Grid: (batch, heads, q_blocks, kv_blocks), kv innermost/arbitrary.

The kernel streams a head-major (B, H, S, hd) layout: each block is a
(block, hd) tile of one head, so its last two dims are what the TPU
compiler tiles (sublane x lane). A (B, S, H, hd) block holding one head
leaves a size-1 second-minor dim the compiler refuses whenever H > 1; the
``repro.kernels.ops`` wrapper transposes into and out of this layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, block_q: int, block_kv: int, scale: float,
                  causal: bool):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                              # (bq, hd)
    k = k_ref[...]                              # (bkv, hd)
    v = v_ref[...]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        i = pl.program_id(2)
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new

    @pl.when(j == kv_steps - 1)
    def _done():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    block_q: int = 512, block_kv: int = 512,
                    causal: bool = True, interpret: bool = False) -> jax.Array:
    """q,k,v (B, H, S, hd) head-major — MHA core (GQA: expand kv before
    the call). Returns (B, H, S, hd)."""
    B, H, S, hd = q.shape
    assert k.shape == v.shape == q.shape
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    kv_steps = S // block_kv
    grid = (B, H, S // block_q, kv_steps)
    scale = 1.0 / math.sqrt(hd)

    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    spec_q = pl.BlockSpec((None, None, block_q, hd),
                          lambda b, h, i, j: (b, h, i, 0))
    spec_kv = pl.BlockSpec((None, None, block_kv, hd),
                           lambda b, h, i, j: (b, h, j, 0))
    return pl.pallas_call(
        functools.partial(_flash_kernel, kv_steps=kv_steps, block_q=block_q,
                          block_kv=block_kv, scale=scale, causal=causal),
        grid=grid,
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=spec_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # m
            pltpu.VMEM((block_q, 1), jnp.float32),     # l
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
        **kw,
    )(q, k, v)


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block: the TPU lays out the last two
    dims in (8, 128) tiles, so a short dim still costs a whole tile."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * itemsize


def flash_vmem_bytes(block_q: int, block_kv: int, hd: int,
                     dtype_bytes: int = 2) -> int:
    """Working set: double-buffered q/k/v/out blocks, the (m, l, acc)
    scratch, and one (block_q, block_kv) score tile in f32 plus its
    probabilities cast to the input dtype."""
    io = 2 * (2 * vmem_tile_bytes(block_q, hd, dtype_bytes)
              + 2 * vmem_tile_bytes(block_kv, hd, dtype_bytes))
    state = (2 * vmem_tile_bytes(block_q, 1, 4)
             + vmem_tile_bytes(block_q, hd, 4))
    scores = block_q * block_kv * (4 + dtype_bytes)
    return io + state + scores
