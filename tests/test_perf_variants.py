"""Equivalence tests for the beyond-paper performance variants (§Perf).

Every optimization keeps semantics: chunkwise mLSTM == sequential scan,
gather-based MoE dispatch == reference per-token routing, causal q-chunked
flash == direct attention.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import smoke_config
from repro.models.params import init_params
from repro.models.stepfn import loss_fn
from repro.parallel.sharding import ParallelConfig, ShardCtx

KEY = jax.random.PRNGKey(0)


def _loss(cfg, p, batch, **pc):
    base = dict(flash_threshold=1 << 30, logits_chunk=0)
    base.update(pc)
    px = ShardCtx(None, ParallelConfig(**base))
    return float(jax.jit(lambda p, b: loss_fn(p, b, cfg=cfg, px=px))(p, batch)[0])


def test_mlstm_chunkwise_equals_sequential():
    cfg = smoke_config("xlstm-1.3b")
    p = init_params(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size)}
    l_seq = _loss(cfg, p, batch, mlstm_chunk=0)
    l_chk = _loss(cfg, p, batch, mlstm_chunk=8)
    l_chk16 = _loss(cfg, p, batch, mlstm_chunk=16)
    assert abs(l_seq - l_chk) < 2e-3
    assert abs(l_seq - l_chk16) < 2e-3


def test_mlstm_chunkwise_bf16_streams_close():
    cfg = smoke_config("xlstm-1.3b")
    p = init_params(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size)}
    l_seq = _loss(cfg, p, batch, mlstm_chunk=0)
    l_b16 = _loss(cfg, p, batch, mlstm_chunk=8, mlstm_bf16_streams=True)
    assert abs(l_seq - l_b16) < 3e-2


def test_mlstm_chunkwise_grads_match():
    cfg = smoke_config("xlstm-1.3b")
    p = init_params(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)}
    px0 = ShardCtx(None, ParallelConfig(flash_threshold=1 << 30, logits_chunk=0))
    px1 = ShardCtx(None, ParallelConfig(flash_threshold=1 << 30, logits_chunk=0,
                                        mlstm_chunk=8))
    g0 = jax.grad(lambda p: loss_fn(p, batch, cfg=cfg, px=px0)[0])(p)
    g1 = jax.grad(lambda p: loss_fn(p, batch, cfg=cfg, px=px1)[0])(p)
    errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), g0, g1)
    assert max(jax.tree.leaves(errs)) < 5e-2


def _moe_reference(cfg, p, x):
    """Per-token dense routing oracle (no capacity, no dispatch tricks)."""
    from repro.models import layers as L
    mo = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if mo.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"][None, :]
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    if mo.n_group > 1:   # a token's experts lie in its best groups only
        per = mo.num_experts // mo.n_group
        group = jax.lax.top_k(sel.reshape(-1, mo.n_group, per), 2)[0].sum(-1)
        best = jax.lax.top_k(group, mo.topk_group)[1]
        keep = (best[:, :, None] == jnp.arange(mo.n_group)).any(1)
        sel = jnp.where(jnp.repeat(keep, per, -1), sel, -jnp.inf)
    top_vals, top_idx = jax.lax.top_k(sel, mo.top_k)
    gate = jnp.take_along_axis(scores, top_idx, axis=-1)
    w = gate / (gate.sum(-1, keepdims=True) + 1e-9) * mo.routed_scaling
    out = jnp.zeros_like(xt)
    act = jax.nn.gelu if cfg.mlp_act == "geglu" else jax.nn.silu
    for e in range(mo.num_experts):
        h = act(xt @ p["wg"][e]) * (xt @ p["wu"][e])
        ye = h @ p["wd"][e]
        m = (top_idx == e).astype(xt.dtype) * w.astype(xt.dtype)
        out = out + ye * m.sum(-1, keepdims=True)
    if mo.num_shared_experts > 0:
        from repro.parallel.sharding import ShardCtx, ParallelConfig
        px = ShardCtx(None, ParallelConfig())
        out = out + L.mlp(p["shared"], xt[None], cfg, px)[0]
    return out.reshape(B, S, d)


@pytest.mark.parametrize("name", ["qwen3-moe-30b-a3b", "deepseek-v3-671b"])
def test_moe_gather_dispatch_matches_reference(name):
    """Capacity-based gather dispatch == per-token routing when nothing
    overflows capacity (cf >= E/topk covers every token)."""
    from repro.models import layers as L
    cfg = smoke_config(name).replace(name="t")
    import dataclasses
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=float(
        cfg.moe.num_experts)))  # capacity = Tg: nothing dropped
    p = init_params(cfg, KEY)
    seg = p["segments"][-1]
    moe_key = [k for k in seg if k.endswith(":attn")][0]
    moe_p = jax.tree.map(lambda a: a[-1], seg[moe_key]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    px = ShardCtx(None, ParallelConfig())
    got, _aux = L.moe_block(moe_p, x, cfg=cfg, px=px)
    want = _moe_reference(cfg, moe_p, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_q_chunking_equals_direct():
    cfg = smoke_config("internlm2-1.8b")
    p = init_params(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size)}
    l_direct = _loss(cfg, p, batch, flash_threshold=1 << 30)
    l_flash = _loss(cfg, p, batch, flash_threshold=32, attn_block_kv=16,
                    attn_block_q=16)
    l_qc = _loss(cfg, p, batch, flash_threshold=32, attn_block_kv=16,
                 attn_block_q=16, attn_q_chunks=4)
    assert abs(l_direct - l_flash) < 2e-3
    assert abs(l_direct - l_qc) < 2e-3
