"""DeepSeek-V3's mechanisms at smoke widths, on the CPU: YaRN and the
``noaux_tc`` router against numpy transcriptions of the published formulas
(``modeling_deepseek.py``), the dropless held-expert layer, the latent
flash-decode kernel in interpret mode against the pure-JAX absorbed
decode, and the blockwise prefill's scores bounded by query chunks."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.arch import YaRNConfig
from repro.configs.registry import get_arch, smoke_config
from repro.kernels import ops
from repro.models import attention as A
from repro.models import layers as L
from repro.models.params import init_params
from repro.models.stepfn import make_prefill_step
from repro.parallel.sharding import KernelConfig, ParallelConfig, ShardCtx

PX = ShardCtx(mesh=None, pcfg=ParallelConfig(flash_threshold=1 << 30,
                                             logits_chunk=0))
YARN = YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


# -- published formulas, transcribed -------------------------------------

def hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
            mscale_all_dim, positions):
    """``DeepseekV3YarnRotaryEmbedding``: cos and sin of each position over
    the rotary half, and the attention's mscale."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0
    hi = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (hi - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    freqs = np.outer(positions, inv_freq)
    return (np.cos(freqs) * m, np.sin(freqs) * m, inv_freq,
            get_mscale(factor, mscale_all_dim))


def hf_gate(logits, bias, n_group, topk_group, top_k, scaling):
    """``MoEGate`` (``noaux_tc``, sigmoid), with DeepSeek's inference/model.py
    masking of the groups left out (-inf)."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    choice = scores + bias
    T, E = choice.shape
    group_scores = np.sort(choice.reshape(T, n_group, -1), -1)[..., -2:] \
        .sum(-1)
    group_idx = np.argsort(-group_scores, -1, kind="stable")[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, -1)
    score_mask = np.repeat(group_mask, E // n_group, -1)
    tmp = np.where(score_mask > 0, choice, -np.inf)
    idx = np.argsort(-tmp, -1, kind="stable")[:, :top_k]
    w = np.take_along_axis(scores, idx, -1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scaling


# -- rope -----------------------------------------------------------------

def test_plain_rope_tables_keep_their_bits():
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    cos, sin = L.rope_tables(pos, 64, 1e6)
    half = 32
    freqs = 1e6 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(jnp.cos(ang)))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(jnp.sin(ang)))


@pytest.mark.parametrize("dim,factor", [(64, 40.0), (8, 40.0), (64, 4.0)])
def test_yarn_tables_follow_the_published_formulas(dim, factor):
    y = dataclasses.replace(YARN, factor=factor)
    pos = np.array([0, 1, 17, 4095, 30000])
    cos, sin, inv_freq, _ = hf_yarn(dim, 1e4, factor, 4096, 32, 1, 1.0,
                                    1.0, pos)
    got_c, got_s = L.rope_tables(jnp.asarray(pos)[None], dim, 1e4, y)
    np.testing.assert_allclose(np.asarray(got_c[0]), cos, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_s[0]), sin, atol=2e-3)
    # the frequencies themselves, where rounding does not compound
    np.testing.assert_allclose(L.yarn_inv_freq(dim, 1e4, y), inv_freq,
                               rtol=1e-6)


def test_mla_softmax_scale_carries_yarns_mscale_squared():
    cfg = get_arch("deepseek-v3-671b")
    m = 0.1 * math.log(40) + 1.0
    assert A.mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    assert A.mla_softmax_scale(cfg.replace(rope_scaling=None)) == \
        pytest.approx(1 / math.sqrt(192))


# -- routing --------------------------------------------------------------

@pytest.mark.parametrize("E,n_group,topk_group,K", [(256, 8, 4, 8),
                                                     (16, 4, 2, 2)])
def test_router_follows_the_published_gate(E, n_group, topk_group, K):
    mo = dataclasses.replace(get_arch("deepseek-v3-671b").moe, num_experts=E,
                             n_group=n_group, topk_group=topk_group, top_k=K)
    rng = np.random.default_rng(0)
    d, T = 32, 64
    p = {"router": rng.normal(0, 0.3, (d, E)).astype(np.float32),
         "router_bias": rng.normal(0, 0.05, (E,)).astype(np.float32)}
    x = rng.normal(size=(T, d)).astype(np.float32)
    _, idx, w = L.route(p, jnp.asarray(x)[None], mo)
    want_idx, want_w = hf_gate(x @ p["router"], p["router_bias"], n_group,
                               topk_group, K, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(idx[0]), -1),
                                  np.sort(want_idx, -1))
    order = lambda i, v: np.take_along_axis(v, np.argsort(i, -1), -1)  # noqa
    np.testing.assert_allclose(order(np.asarray(idx[0]), np.asarray(w[0])),
                               order(want_idx, want_w), rtol=1e-5)
    # the bias only picks: the weights are the unbiased scores, normalized
    assert np.allclose(np.asarray(w[0]).sum(-1), 2.5)


# -- the held-expert layer -------------------------------------------------

def _moe_cfg(held, first=0):
    cfg = smoke_config("deepseek-v3-671b").replace(dtype="float32")
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts_held=held, first_expert_held=first))


def _moe_params(cfg, key=0):
    p = init_params(cfg, jax.random.PRNGKey(key))["segments"][-1]
    return jax.tree.map(lambda a: a[0], p["0:attn"]["moe"])


def test_held_layer_computes_every_routed_copy():
    """Every expert held: the layer is the per-token sum over each token's
    experts, nothing dropped, with each expert's copies over more than one
    tile (about 512 copies an expert, tiles of 256 rows)."""
    cfg = _moe_cfg(16)
    p = _moe_params(cfg)
    p["router"] = p["router"] * 40.0       # spread the scores
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512, cfg.d_model))
    _, idx, w = L.route(p, x.reshape(1, -1, cfg.d_model), cfg.moe)
    assert np.bincount(np.asarray(idx).ravel()).max() > L.HELD_TILE
    xt = x.reshape(-1, cfg.d_model)
    want = L.mlp(p["shared"], xt[None], cfg, PX)[0]
    for e in range(16):
        h = jax.nn.silu(xt @ p["wg"][e]) * (xt @ p["wu"][e])
        gate = jnp.sum(jnp.where(idx[0] == e, w[0], 0.0), -1)
        want = want + gate[:, None] * (h @ p["wd"][e])
    got, copies = L.moe_held(p, x, cfg=cfg, px=PX)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, cfg.d_model),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.asarray(copies).tolist() == [512 * 2] * 8


# -- the latent decode kernel ----------------------------------------------

@pytest.mark.parametrize("S,block_kv,num_splits,combine", [
    (64, 16, 2, "jax"), (64, 32, 1, "kernel"),
    (50, 16, 2, "kernel")])                     # ragged: padded per call
def test_latent_kernel_matches_the_absorbed_decode(S, block_kv, num_splits,
                                                   combine):
    B, H, W, r, Lyr = 2, 4, 128, 16, 3
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(k[0], (B, 1, H, W)).at[..., 24:].set(0)
    lat = jax.random.normal(k[1], (Lyr, B, S, W)).at[..., 24:].set(0)
    pos = jnp.where(jnp.arange(S)[None] < jnp.array([[37], [S - 3]]),
                    jnp.arange(S)[None], -1)
    cur = jnp.array([36, S - 4])
    for layer in (0, 2):
        want = A._mla_latent_decode(q, lat, pos, cur, r=r, scale=0.3,
                                    layer=layer)
        got = ops.mla_decode_attention(
            q, lat, pos, cur, layer, v_width=r, scale=0.3, block_kv=block_kv,
            num_splits=num_splits, combine=combine, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    alone = ops.mla_decode_attention(q, lat[1], pos, cur, v_width=r,
                                     scale=0.3, block_kv=block_kv,
                                     num_splits=num_splits, combine=combine,
                                     interpret=True)
    np.testing.assert_allclose(
        np.asarray(alone), np.asarray(A._mla_latent_decode(
            q, lat, pos, cur, r=r, scale=0.3, layer=1)),
        rtol=2e-5, atol=2e-5)


def test_decode_gate_and_capacity_take_the_latent_tile():
    cfg = get_arch("deepseek-v3-671b")
    kc = KernelConfig(use_decode=True, decode_block_kv=512,
                      decode_num_splits=1)
    assert A.decode_path(cfg, ParallelConfig(kernel=kc)) == "pallas"
    assert A.decode_path(cfg, ParallelConfig(kernel=kc.replace(
        use_decode=False))) == "jax"
    assert A.decode_path(cfg, ParallelConfig(kernel=None)) == "jax"
    assert A.decode_capacity(cfg, 2560, kc) == 2560
    assert A.decode_capacity(cfg, 2561, kc) == 3072
    assert A.decode_capacity(cfg, 2561, None) == 2561
    assert A.mla_latent_width(cfg.mla) == 640


# -- the blockwise scan's scores bounded by query chunks ---------------------

def test_prefill_past_the_score_bound_runs_query_chunks_to_the_same_result(
        monkeypatch):
    """A prefill whose scores for one KV block pass ``SCORE_BLOCK_BYTES``
    runs its queries in causal chunks: more scans, and the whole batch's
    logits and cache. The chunks skip only KV blocks wholly above their
    queries, which add nothing; float32, so the tolerance covers only
    XLA's order of operations at other shapes."""
    cfg = smoke_config("deepseek-v3-671b").replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                cfg.vocab_size)
    px = ShardCtx(mesh=None, pcfg=ParallelConfig(
        flash_threshold=8, attn_block_kv=4, logits_chunk=0))
    batch = {"tokens": tokens}

    def run():
        # a new step each time: a traced one keeps the bound it was traced at
        step = make_prefill_step(cfg, px, cache_cap=20)
        return (jax.jit(step)(params, batch),
                str(jax.make_jaxpr(step)(params, batch)).count("scan["))
    whole, scans = run()
    # 4 rows x 4 heads x 4 queries x 4 keys x 4 bytes: four chunks of 4
    monkeypatch.setattr(A, "SCORE_BLOCK_BYTES", 4 * 4 * 4 * 4 * 4)
    chunked, chunked_scans = run()
    assert chunked_scans > scans
    for x, y in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        assert x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-5)
