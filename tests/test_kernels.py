"""Per-kernel allclose vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gp_fast import IncrementalGP
from repro.kernels import ops, ref


# -- GEMM --------------------------------------------------------------------

@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (256, 384, 512),
                                   (512, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_shapes_dtypes(M, N, K, dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(M, K)), dtype)
    b = jnp.asarray(rng.normal(size=(K, N)), dtype)
    out = ops.gemm(a, b, block_m=128, block_n=128, block_k=128)
    want = ref.gemm(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 64, 256)])
def test_gemm_block_configs(bm, bn, bk):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    out = ops.gemm(a, b, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gemm(a, b)),
                               rtol=1e-4, atol=1e-3)


def test_gemm_rejects_indivisible():
    a = jnp.zeros((100, 128), jnp.float32)
    b = jnp.zeros((128, 128), jnp.float32)
    with pytest.raises(AssertionError):
        ops.gemm(a, b, block_m=64, block_n=64, block_k=64)


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 64), (2, 256, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_shapes_dtypes(B, S, H, hd, dtype):
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, block_q=64, block_kv=64)
    want = ref.attention(q, k, v, causal=True)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bkv", [(32, 128), (128, 32), (64, 64)])
def test_flash_block_configs(bq, bkv):
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, block_q=bq, block_kv=bkv)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_flash_noncausal():
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, block_q=64, block_kv=64, causal=False)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.attention(q, k, v, causal=False)),
                               rtol=2e-4, atol=2e-4)


# -- Matérn GP posterior ---------------------------------------------------------

@pytest.mark.parametrize("t,N,d", [(13, 512, 6), (37, 1024, 15)])
@pytest.mark.parametrize("nu", ["matern32", "matern52"])
def test_gp_kernel_vs_oracle_and_engine(t, N, d, nu):
    rng = np.random.default_rng(5)
    Xc = rng.random((N, d)).astype(np.float32)
    g = IncrementalGP(Xc, max_obs=64, kernel=nu, ell=2.0)
    for _ in range(t):
        g.add(Xc[rng.integers(N)], float(rng.normal(10, 3)))
    x_obs, vinv, w, mask, y_mean, y_std = ops.gp_inputs_from_incremental(g)
    mean_k, var_k = ops.gp_posterior(
        jnp.asarray(Xc), jnp.asarray(x_obs), jnp.asarray(vinv),
        jnp.asarray(w), jnp.asarray(mask), ell=2.0, nu=nu, block_n=256)
    # kernel vs same-precision jnp oracle. The VARIANCE path is well
    # conditioned -> tight. The MEAN is amplified by ||L^-1||*||w|| (GP
    # kernel matrices are ill-conditioned), so even two fp32 codings differ
    # by ~kappa*eps: bound by a fraction of the mean's range instead.
    m_r, v_r = ref.gp_posterior(jnp.asarray(Xc), jnp.asarray(x_obs),
                                jnp.asarray(vinv), jnp.asarray(w), 2.0, nu)
    np.testing.assert_allclose(np.asarray(var_k), np.asarray(v_r),
                               rtol=3e-3, atol=1e-4)
    m_r = np.asarray(m_r)
    rng_m = m_r.max() - m_r.min() + 1e-9
    assert np.abs(np.asarray(mean_k) - m_r).max() < 0.03 * rng_m
    # behavioral: fp32 kernel vs float64 incremental engine. GP systems are
    # ill-conditioned, so pointwise fp32 error can reach ~2% of the y-range —
    # what matters for acquisition is the RANKING, which must agree.
    mu_k = y_mean + y_std * np.asarray(mean_k)
    mu_i, _ = g.predict()
    y_range = mu_i.max() - mu_i.min()
    assert np.abs(mu_k - mu_i).max() < 0.05 * y_range
    top_k = set(np.argsort(mu_k)[:20])
    top_i = set(np.argsort(mu_i)[:20])
    assert len(top_k & top_i) >= 18


def test_vmem_models_monotone():
    from repro.kernels.flash_attention import flash_vmem_bytes
    from repro.kernels.gemm import gemm_vmem_bytes
    assert gemm_vmem_bytes(256, 256, 256) < gemm_vmem_bytes(512, 512, 512)
    assert flash_vmem_bytes(256, 256, 128) < flash_vmem_bytes(1024, 1024, 128)


@pytest.mark.parametrize("KV,G,block_kv,admitted", [
    (8, 2, 1024, True),      # InternLM2-1.8B GQA: compiles on a v5e
    (16, 1, 512, True),      # MHA at 16 heads: compiles
    (16, 1, 1024, False),    # the v5e compiler runs out of scoped VMEM
    (1, 16, 1024, True),     # MQA
])
def test_decode_vmem_model_counts_every_kv_head(KV, G, block_kv, admitted):
    """A decode block holds all KV heads of ``block_kv`` cache slots, double
    buffered: the model refuses the config the compiler refuses (16 heads
    × 1024 slots × hd128 bf16 at a 16 MiB scoped-VMEM limit)."""
    assert ops.decode_valid({"block_kv": block_kv}, KV, G, 128, 2) == admitted


# -- GQA-expanded flash dispatch vs the models/attention reference ----------

@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (4, 1)])   # MHA, GQA, MQA
@pytest.mark.parametrize("bq,bkv", [(64, 64), (128, 64), (64, 128)])
def test_flash_gqa_expanded_vs_layers_reference(H, KV, bq, bkv):
    """The serve dispatch path (_pallas_flash_attention) expands KV heads
    and calls the MHA-core Pallas kernel; it must match the grouped-head
    pure-JAX attention in models/attention.py on causal prefill shapes."""
    from repro.models.attention import _direct_attention
    rng = np.random.default_rng(11)
    B, S, hd = 1, 256, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    G = H // KV
    ke = jnp.repeat(k, G, axis=2) if G > 1 else k
    ve = jnp.repeat(v, G, axis=2) if G > 1 else v
    out = ops.flash_attention(q, ke, ve, block_q=bq, block_kv=bkv,
                              causal=True)
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    want = _direct_attention(q, k, v, q_pos=pos, k_pos=pos, window=None,
                             scale=1.0 / np.sqrt(hd))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_kernel_config_dispatch_matches_pure_jax():
    """End-to-end: a prefill step with KernelConfig set must match the
    pure-JAX step within kernel tolerance (and fall back silently when the
    blocks don't tile the sequence)."""
    from repro.configs.registry import smoke_config
    from repro.models.params import init_params
    from repro.models.stepfn import make_prefill_step
    from repro.parallel.sharding import (KernelConfig, ParallelConfig,
                                         ShardCtx)
    cfg = smoke_config("qwen3-moe-30b-a3b")       # GQA arch
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, S = 2, 256
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    pcfg0 = ParallelConfig(flash_threshold=1 << 30, logits_chunk=0)
    pcfg1 = pcfg0.replace(kernel=KernelConfig(
        use_flash=True, flash_block_q=128, flash_block_kv=128))
    step0 = jax.jit(make_prefill_step(cfg, ShardCtx(None, pcfg0),
                                      cache_cap=S + 4))
    step1 = jax.jit(make_prefill_step(cfg, ShardCtx(None, pcfg1),
                                      cache_cap=S + 4))
    out0, _ = step0(params, batch)
    out1, _ = step1(params, batch)
    denom = float(jnp.abs(out0).max())
    assert float(jnp.abs(out0 - out1).max()) < 5e-3 * max(denom, 1.0)
    # blocks that don't tile S: dispatch precondition fails -> pure-JAX path
    pcfg2 = pcfg0.replace(kernel=KernelConfig(
        use_flash=True, flash_block_q=512, flash_block_kv=512))
    out2, _ = jax.jit(make_prefill_step(cfg, ShardCtx(None, pcfg2),
                                        cache_cap=S + 4))(params, batch)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out2))


# -- gp_inputs_from_incremental packaging ------------------------------------

def test_gp_inputs_triangular_solve_parity():
    """The O(t²)-per-column triangular-solve packaging must match the old
    O(T³) dense-inverse formulation exactly (same math, fp64 then cast)."""
    rng = np.random.default_rng(12)
    Xc = rng.random((64, 5)).astype(np.float32)
    g = IncrementalGP(Xc, max_obs=32, kernel="matern32", ell=2.0)
    for _ in range(17):
        g.add(Xc[rng.integers(64)], float(rng.normal(3, 1)))
    x_obs, vinv, w, mask, y_mean, y_std = ops.gp_inputs_from_incremental(g)
    T, t = len(mask), g.t
    # oracle: dense inverse of the padded factor (identity on pad rows),
    # zeroed outside the live t x t block — the pre-fix formulation
    Lp = np.eye(T)
    Lp[:t, :t] = g.L[:t, :t]
    vinv_ref = np.linalg.inv(Lp)
    vinv_ref[t:, :] = 0.0
    vinv_ref[:, t:] = 0.0
    np.testing.assert_allclose(vinv, vinv_ref.astype(np.float32),
                               rtol=1e-5, atol=1e-6)
    yv = g.y[:t]
    w_ref = np.zeros(T)
    w_ref[:t] = np.linalg.solve(g.L[:t, :t], (yv - yv.mean()) / yv.std())
    np.testing.assert_allclose(w, w_ref.astype(np.float32),
                               rtol=1e-5, atol=1e-6)
    assert mask[:t].all() and not mask[t:].any()


# -- self-hosted GP backend (DESIGN.md §14) ----------------------------------

@pytest.mark.parametrize("block_n", [128, 256])
def test_incremental_gp_pallas_backend_vs_numpy(block_n):
    """backend="pallas" routes predict/predict_at through the fused
    matern_gp kernel; it must track the numpy oracle within the kernel's
    established fp32 tolerance (fraction of the posterior-mean range) and
    agree on acquisition RANKING."""
    rng = np.random.default_rng(13)
    N, d = 300, 6                     # non-multiple of block_n: pads
    Xc = rng.random((N, d)).astype(np.float64)
    g_np = IncrementalGP(Xc, max_obs=32)
    g_pl = IncrementalGP(Xc, max_obs=32, backend="pallas", block_n=block_n)
    for _ in range(14):
        i = rng.integers(N)
        y = float(rng.normal(5, 2))
        g_np.add(Xc[i], y)
        g_pl.add(Xc[i], y)
    mu0, sd0 = g_np.predict()
    mu1, sd1 = g_pl.predict()
    assert mu1.shape == (N,) and sd1.shape == (N,)
    y_range = mu0.max() - mu0.min()
    assert np.abs(mu0 - mu1).max() < 0.05 * y_range
    assert np.abs(sd0 - sd1).max() < 5e-3 * max(sd0.max(), 1e-9) + 1e-4
    top0 = set(np.argsort(mu0)[:20])
    top1 = set(np.argsort(mu1)[:20])
    assert len(top0 & top1) >= 18
    # pool-mode scoring at arbitrary points goes through the same kernel
    Xq = rng.random((75, d))
    mu0a, _ = g_np.predict_at(Xq)
    mu1a, _ = g_pl.predict_at(Xq)
    assert np.abs(mu0a - mu1a).max() < 0.05 * y_range


def test_bo_strategy_runs_on_pallas_gp_backend():
    """Full BO loop with the self-hosted posterior: same engine, kernel
    scoring — must converge on a smooth synthetic surface."""
    from repro.core.runner import run_strategy
    from repro.core.searchspace import Param, SearchSpace
    from repro.core.strategies.bo import BOConfig, BOStrategy
    from repro.core.objectives import SimulatedObjective
    vals = tuple(range(8))
    space = SearchSpace([Param("a", vals), Param("b", vals)], name="syn")
    rng = np.random.default_rng(14)
    times = np.array([(c["a"] - 5) ** 2 + (c["b"] - 2) ** 2 + 1.0
                      for c in (space.config(i) for i in range(space.size))])
    obj = SimulatedObjective(space, times, name="syn")
    strat = BOStrategy(BOConfig(initial_samples=6, gp_backend="pallas",
                                gp_block_n=128))
    res = run_strategy(strat, obj, budget=20, seed=0)
    assert res.best_value <= times.min() + 4.0   # found the basin


# -- flash decode (single-token cache attention, ISSUE 8) --------------------

def _decode_case(B, S, H, KV, hd, cur, *, window=None, rolling=False, seed=0,
                 layers=None):
    """A cache state the way a live server produces it: contiguous fill to
    ``cur`` (later slots empty, ``cache_pos == -1``), or a rolling window's
    wrapped layout (slot s holds the latest position congruent to s).
    K/V are head-major, (B, KV, S, hd); with ``layers``, the stacked
    caches of that many layers, each drawn on its own."""
    rng = np.random.default_rng(seed)
    stack = (B, KV, S, hd) if layers is None else (layers, B, KV, S, hd)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=stack), jnp.float32)
    v = jnp.asarray(rng.normal(size=stack), jnp.float32)
    if rolling:
        slots = np.arange(S)
        pos = cur - ((cur - slots) % S)
        pos = np.where(pos >= 0, pos, -1)
    else:
        pos = np.where(np.arange(S) <= cur, np.arange(S), -1)
    cache_pos = jnp.asarray(np.broadcast_to(pos, (B, S)).copy(), jnp.int32)
    cur_pos = jnp.full((B,), cur, jnp.int32)
    return q, k, v, cache_pos, cur_pos


def _check_decode(q, k, v, cp, cu, *, window, layers, **blocks):
    """The kernel against ``attention._decode_attention``: on the one layer's
    cache, or on each of ``layers`` layers read out of the stack by a
    traced layer index."""
    from repro.models.attention import _decode_attention
    scale = 1.0 / np.sqrt(q.shape[-1])
    for i in ([None] if layers is None else range(layers)):
        kc, vc = (k, v) if i is None else (k[i], v[i])
        ref_out = _decode_attention(q, kc, vc, cache_pos=cp, cur_pos=cu,
                                    window=window, scale=scale)
        layer = None if i is None else jnp.asarray(i, jnp.int32)
        out = ops.decode_attention(q, k, v, cp, cu, layer, window=window,
                                   interpret=True, **blocks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layers", [None, 3])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("num_splits,block_kv,combine",
                         [(1, 64, "jax"), (2, 32, "jax"), (4, 16, "kernel")])
def test_decode_parity_gqa_and_splits(H, KV, num_splits, block_kv, combine,
                                      layers):
    """Kernel output must match the attention.py pure-JAX decode reference
    across GQA head ratios and split/combine configurations, on one
    layer's cache and on each layer of a stacked one."""
    q, k, v, cp, cu = _decode_case(2, 128, H, KV, 16, cur=97, layers=layers)
    _check_decode(q, k, v, cp, cu, window=None, layers=layers,
                  block_kv=block_kv, num_splits=num_splits, combine=combine)


@pytest.mark.parametrize("case", [
    dict(B=2, S=128, H=4, KV=2, hd=16, cur=5),              # mostly empty
    dict(B=1, S=100, H=4, KV=2, hd=16, cur=99),             # S % block != 0
    dict(B=2, S=64, H=4, KV=2, hd=16, cur=150, window=24,
         rolling=True),                                     # rolling window
    dict(B=2, S=96, H=4, KV=1, hd=16, cur=40, window=16),   # window, no wrap
    dict(B=2, S=128, H=4, KV=2, hd=16, cur=5, layers=2),    # stacked: empty
    dict(B=1, S=100, H=4, KV=2, hd=16, cur=99, layers=3),   # stacked, ragged
    dict(B=2, S=64, H=4, KV=2, hd=16, cur=150, window=24,
         rolling=True, layers=2),                           # stacked, rolling
    dict(B=2, S=128, H=4, KV=1, hd=16, cur=40, window=16,
         layers=2),                                         # stacked MQA
])
def test_decode_parity_occupancy_window_capacity(case):
    """Validity-mask edges: partially-empty caches, capacities that don't
    tile into block_kv (padded with masked slots), and windowed/rolling
    caches — including splits that land entirely in masked territory — on
    one layer's cache and on each layer of a stacked one."""
    case = dict(case)
    window = case.pop("window", None)
    rolling = case.pop("rolling", False)
    q, k, v, cp, cu = _decode_case(**case, window=window, rolling=rolling)
    for num_splits, block_kv, combine in [(1, 64, "jax"), (4, 16, "jax"),
                                          (2, 32, "kernel"),
                                          (8, 32, "kernel")]:
        _check_decode(q, k, v, cp, cu, window=window,
                      layers=case.get("layers"), block_kv=block_kv,
                      num_splits=num_splits, combine=combine)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("KV", [1, 2, 8])
def test_head_major_cache_write_then_read(KV, window):
    """A prefill's K/V written into a fresh cache (``_prefill_cache``, the
    rolling window's wrap included) and one decoded token inserted into a
    stack of layers (``_insert_slot``) put position p of head h at
    ``[layer, b, h, slot(p)]``, and nothing into the other layers; the
    Pallas kernel and ``_decode_attention`` read that stack as attention
    over the positions the token may see."""
    from repro.models.attention import (_cache_slot, _decode_attention,
                                        _insert_slot, _prefill_cache)
    rng = np.random.default_rng(KV)
    B, H, hd, S, L, layer = 2, 8, 16, 40, 3, 1
    cap = 64 if window is None else window
    k_seq, v_seq = (jnp.asarray(rng.normal(size=(B, S + 1, KV, hd)),
                                jnp.float32) for _ in range(2))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = _prefill_cache(k_seq[:, :S], v_seq[:, :S], positions, cap,
                           window)
    stacks = {n: jnp.zeros((L,) + a.shape, a.dtype).at[layer].set(a)
              for n, a in cache.items()}
    tok = jnp.full((B, 1), S, jnp.int32)
    slot = _cache_slot(tok[:, 0], cap, window)
    k_st = _insert_slot(stacks["k"], k_seq[:, S:], slot, layer, axis=3)
    v_st = _insert_slot(stacks["v"], v_seq[:, S:], slot, layer, axis=3)
    pos_st = _insert_slot(stacks["pos"], tok, slot, layer)
    assert k_st.shape == (L, B, KV, cap, hd)

    first = 0 if window is None else S + 1 - window
    want_k = np.zeros((L, B, KV, cap, hd), np.float32)
    want_pos = np.full((L, B, cap), -1, np.int32)
    for p in range(first, S + 1):
        sl = p % cap if window is not None else p
        want_k[layer, :, :, sl] = np.asarray(k_seq[:, p])
        want_pos[layer, :, sl] = p
    np.testing.assert_array_equal(np.asarray(k_st), want_k)
    np.testing.assert_array_equal(np.asarray(pos_st)[layer],
                                  want_pos[layer])
    for other in set(range(L)) - {layer}:
        assert not np.asarray(k_st[other]).any()

    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    scale = 1.0 / np.sqrt(hd)
    kk = np.repeat(np.asarray(k_seq[:, first:]), H // KV, axis=2)
    vv = np.repeat(np.asarray(v_seq[:, first:]), H // KV, axis=2)
    sc = np.einsum("bhd,bshd->bhs", np.asarray(q[:, 0]), kk) * scale
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("bhs,bshd->bhd", pr / pr.sum(-1, keepdims=True), vv)
    cur = tok[:, 0]
    ref = _decode_attention(q, k_st[layer], v_st[layer],
                            cache_pos=pos_st[layer], cur_pos=cur,
                            window=window, scale=scale)
    got = ops.decode_attention(q, k_st, v_st, pos_st[layer], cur,
                               jnp.asarray(layer, jnp.int32), window=window,
                               block_kv=8, num_splits=2, interpret=True)
    for out in (ref, got):
        np.testing.assert_allclose(np.asarray(out)[:, 0], want, rtol=2e-4,
                                   atol=2e-4)


def _golden_decode_run(arch, kernel=None):
    """The exact run tests/golden/decode_logits.json was captured with
    (pre-PR code, ParallelConfig(kernel=None)); returns final-step logits.
    The weights and prompts are drawn with the PRNG that capture used:
    threefry not partitionable, the default before JAX 0.5."""
    from repro.configs.registry import smoke_config
    from repro.models.params import init_params
    from repro.models.stepfn import make_decode_step, make_prefill_step
    from repro.parallel.sharding import ParallelConfig, ShardCtx
    cfg = smoke_config(arch)
    px = ShardCtx(None, ParallelConfig(flash_threshold=1 << 30,
                                       logits_chunk=0, kernel=kernel))
    B, S, STEPS = 2, 8, 12
    with jax.threefry_partitionable(False):
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab_size)
    prefill = jax.jit(make_prefill_step(cfg, px, cache_cap=S + STEPS))
    decode = jax.jit(make_decode_step(cfg, px))
    logits, cache = prefill(params, {"tokens": tokens})
    toks = jnp.argmax(logits, -1)
    for i in range(STEPS):
        logits, cache = decode(params, cache, {"tokens": toks[:, None]},
                               jnp.asarray(S + i, jnp.int32))
        toks = jnp.argmax(logits, -1)
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "gemma-2b",
                                  "recurrentgemma-9b"])
def test_decode_kernel_none_byte_identical_to_golden(arch):
    """Acceptance pin (ISSUE 8): with ``ParallelConfig.kernel=None`` the
    decode path is BYTE-identical to the pre-PR capture — adding the Pallas
    dispatch changed nothing for servers that don't opt in."""
    import json, os
    path = os.path.join(os.path.dirname(__file__), "golden",
                        "decode_logits.json")
    with open(path) as f:
        golden = json.load(f)
    got = _golden_decode_run(arch, kernel=None)
    np.testing.assert_array_equal(got,
                                  np.asarray(golden[arch], np.float32))


def test_decode_kernel_dispatch_matches_pure_jax_end_to_end():
    """The golden run re-executed WITH flash-decode dispatch must track the
    pure-JAX decode within kernel tolerance (bf16 model dtype — same band
    as the prefill dispatch test), across a GQA arch and the windowed
    rolling-cache arch; and a config whose gate is closed (use_decode=False)
    stays bitwise on the pure-JAX path."""
    from repro.parallel.sharding import KernelConfig
    for arch in ("qwen3-moe-30b-a3b", "recurrentgemma-9b"):
        base = _golden_decode_run(arch, kernel=None)
        kc = KernelConfig(use_decode=True, decode_block_kv=8,
                          decode_num_splits=2, decode_combine="kernel")
        got = _golden_decode_run(arch, kernel=kc)
        denom = max(float(np.abs(base).max()), 1.0)
        assert float(np.abs(got - base).max()) < 5e-3 * denom
    closed = _golden_decode_run("gemma-2b",
                               kernel=KernelConfig(use_decode=False))
    np.testing.assert_array_equal(closed,
                                  _golden_decode_run("gemma-2b", kernel=None))
