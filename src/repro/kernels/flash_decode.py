"""Single-token flash-decode Pallas TPU kernel (split-KV, tunable).

Decode attention runs once per generated token over the whole KV cache, so
at serving scale it dominates cost; unlike prefill there is no q-sequence
to tile, which makes the natural parallel axis the CACHE LENGTH. The kernel
partitions the cache into ``num_splits`` independent ranges, each scanned
in ``block_kv`` tiles with the online-softmax (m, l, acc) state held in
VMEM, then a cross-split combine merges the per-split partials — the
"flash-decode" decomposition. GQA is native: each program holds the cache
tile of every KV head and, per head, that head's G = H/KV grouped query
rows, so KV tiles are loaded ONCE per group instead of per query head (the
GQA-expansion the prefill kernel needs would multiply decode HBM traffic by
G).

Validity (cache slots never written, slots beyond the current position,
rolling-window eviction) enters as a precomputed additive f32 bias row
(0 or -inf) built by the wrapper in ``repro.kernels.ops`` — the kernel
itself stays a pure softmax-accumulate, and a fully-masked split resolves
to zero weight in the combine rather than NaN.

Tunables (the BO cell's space, DESIGN.md §16): ``block_kv`` (tile length),
``num_splits`` (cache partitions — parallelism vs combine overhead), and
the combine strategy (``"jax"``: merge partials with jnp ops; ``"kernel"``:
a second small Pallas kernel so partials never leave the device path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import vmem_tile_bytes

COMBINE_STRATEGIES = ("jax", "kernel")


def _decode_split_kernel(layer_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                         m_out_ref, l_out_ref, m_ref, l_ref, acc_ref, *,
                         steps: int, kv_heads: int, scale: float):
    """One (batch, split) program: scan this split's KV tiles with online
    softmax for every KV head, emit unnormalized (acc, m, l) partials for
    the combine. ``layer_ref`` (scalar prefetch) only steers the K/V
    index maps to the layer's slab of the stacked cache.

    Masked positions carry a -inf bias, so ``exp(s - m_safe)`` is exactly 0
    for them; an all-masked split keeps m = -inf / l = 0 and contributes
    nothing downstream.
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[...]                           # (1, bkv) 0 / -inf
    for h in range(kv_heads):                      # static: one head group
        q = q_ref[h]                               # (G, hd)
        if len(k_ref.shape) == 2:                  # MQA: no head axis
            k, v = k_ref[...], v_ref[...]          # (bkv, hd)
        else:
            k = k_ref[:, h, :]                     # (bkv, hd)
            v = v_ref[:, h, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = s + bias
        m_prev = m_ref[h]                          # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)                    # exp(-inf - 0) == 0
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]


def _combine_partials_jnp(o_part, m_part, l_part):
    """Merge per-split (acc, m, l) into normalized attention output.

    o_part (B,S,KV,G,hd) f32, m/l (B,S,KV,G,1) — the flash cross-block
    correction applied once across splits: weight each split by
    exp(m_i - max_i m_i), then normalize by the merged l.
    """
    m_tot = m_part.max(axis=1)                                 # (B,KV,G,1)
    m_safe = jnp.where(jnp.isfinite(m_tot), m_tot, 0.0)
    w = jnp.where(jnp.isfinite(m_part),
                  jnp.exp(m_part - m_safe[:, None]), 0.0)
    l_tot = jnp.sum(w * l_part, axis=1)                        # (B,KV,G,1)
    o = jnp.sum(w * o_part, axis=1)                            # (B,KV,G,hd)
    return o / jnp.maximum(l_tot, 1e-30)


def _decode_combine_kernel(o_ref, m_ref, l_ref, out_ref, *, num_splits: int):
    """One batch row's program folding all splits of every head group."""
    m_tot = m_ref[0]                               # (KV, G, 1)
    for s in range(1, num_splits):
        m_tot = jnp.maximum(m_tot, m_ref[s])
    m_safe = jnp.where(jnp.isfinite(m_tot), m_tot, 0.0)
    l_tot = jnp.zeros_like(m_tot)
    merged = jnp.zeros(out_ref.shape, jnp.float32)  # (KV, G, hd)
    for s in range(num_splits):
        m = m_ref[s]
        w = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_tot = l_tot + w * l_ref[s]
        merged = merged + w * o_ref[s]
    out_ref[...] = (merged / jnp.maximum(l_tot, 1e-30)).astype(out_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 bias: jax.Array, layer: jax.Array, *, block_kv: int = 512,
                 num_splits: int = 1, combine: str = "jax",
                 interpret: bool = False) -> jax.Array:
    """Single-token cache attention over one layer of a stacked cache.
    q (B, H, hd); k/v caches (L, B, S, KV, hd), the caches of L layers in
    one array, with S % (num_splits * block_kv) == 0 (the ops wrapper pads
    other capacities); ``layer`` an int32 scalar, the layer to read; bias
    (B, S) f32 additive validity mask (0 valid / -inf masked). Returns
    (B, H, hd) in q's dtype.

    ``layer`` is prefetched into SMEM and read by the K/V index maps, so
    each grid step DMAs its ``block_kv`` slots straight out of the layer's
    slab: the stack is never sliced or copied, and a decode step that
    updates it in place hands it to the kernel as it is.

    Every block spans whole trailing dims or a tiled slice of the cache
    axis, the only layout the TPU compiler accepts here: a K/V block holds
    all KV heads of ``block_kv`` slots (the kernel loops over heads), q is
    viewed as (B, KV, G, hd) and the bias as (B, 1, S). With one KV head
    the caches are read as (L, B, S, hd): XLA lays a unit head axis out
    beside the (slot, hd) tiles, not inside them, so only that view reaches
    the kernel as it lies in memory (with the head axis, XLA would relayout
    the whole stack for every call).
    """
    B, H, hd = q.shape
    S, KV = k_cache.shape[2], k_cache.shape[3]
    assert v_cache.shape == k_cache.shape
    assert H % KV == 0, (H, KV)
    assert S % (num_splits * block_kv) == 0, (S, num_splits, block_kv)
    assert combine in COMBINE_STRATEGIES, combine
    G = H // KV
    steps = S // (num_splits * block_kv)
    scale = 1.0 / (hd ** 0.5)
    grid = (B, num_splits, steps)

    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    spec_q = pl.BlockSpec((None, KV, G, hd), lambda b, s, j, l: (b, 0, 0, 0))
    if KV == 1:
        k_cache, v_cache = k_cache[:, :, :, 0], v_cache[:, :, :, 0]
        spec_kv = pl.BlockSpec((None, None, block_kv, hd),
                               lambda b, s, j, l: (l[0], b, s * steps + j, 0))
    else:
        spec_kv = pl.BlockSpec((None, None, block_kv, KV, hd),
                               lambda b, s, j, l: (l[0], b, s * steps + j,
                                                   0, 0))
    spec_bias = pl.BlockSpec((None, 1, block_kv),
                             lambda b, s, j, l: (b, 0, s * steps + j))
    spec_o = pl.BlockSpec((None, None, KV, G, hd),
                          lambda b, s, j, l: (b, s, 0, 0, 0))
    spec_ml = pl.BlockSpec((None, None, KV, G, 1),
                           lambda b, s, j, l: (b, s, 0, 0, 0))
    ml_shape = jax.ShapeDtypeStruct((B, num_splits, KV, G, 1), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[spec_q, spec_kv, spec_kv, spec_bias],
        out_specs=[spec_o, spec_ml, spec_ml],
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),       # m
            pltpu.VMEM((KV, G, 1), jnp.float32),       # l
            pltpu.VMEM((KV, G, hd), jnp.float32),      # acc
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_decode_split_kernel, steps=steps, kv_heads=KV,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, num_splits, KV, G, hd), jnp.float32),
            ml_shape, ml_shape,
        ],
        interpret=interpret,
        **kw,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q.reshape(B, KV, G, hd),
      k_cache, v_cache, bias[:, None, :])

    if combine == "kernel":
        out = pl.pallas_call(
            functools.partial(_decode_combine_kernel, num_splits=num_splits),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, num_splits, KV, G, hd),
                             lambda b: (b, 0, 0, 0, 0)),
                pl.BlockSpec((None, num_splits, KV, G, 1),
                             lambda b: (b, 0, 0, 0, 0)),
                pl.BlockSpec((None, num_splits, KV, G, 1),
                             lambda b: (b, 0, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, KV, G, hd), lambda b: (b, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
            interpret=interpret,
        )(o_part, m_part, l_part)
        return out.reshape(B, H, hd)
    merged = _combine_partials_jnp(o_part, m_part, l_part)     # (B,KV,G,hd)
    return merged.reshape(B, H, hd).astype(q.dtype)


def decode_vmem_bytes(block_kv: int, KV: int, G: int, hd: int,
                      dtype_bytes: int = 2) -> int:
    """Split-kernel VMEM working set: double-buffered K/V blocks (all KV
    heads of ``block_kv`` slots), q rows, bias row and partial outputs, the
    (m, l, acc) scratch, and one head's f32 scores plus probabilities."""
    kv = 2 * 2 * block_kv * vmem_tile_bytes(KV, hd, dtype_bytes)
    qrows = 2 * KV * vmem_tile_bytes(G, hd, dtype_bytes)
    bias = 2 * vmem_tile_bytes(1, block_kv, 4)
    state = KV * (vmem_tile_bytes(G, hd, 4) + 2 * vmem_tile_bytes(G, 1, 4))
    partials = 2 * state
    scores = 2 * vmem_tile_bytes(G, block_kv, 4)
    return kv + qrows + bias + state + partials + scores
