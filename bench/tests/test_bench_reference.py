"""The plain reference against the repo's pure-JAX step functions, in
float32 on the CPU: prefill, then decode through the cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense

SMOKE = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 256, "hidden_act": "silu",
         "rms_norm_eps": 1e-5, "rope_theta": 1e6,
         "tie_word_embeddings": False, "torch_dtype": "float32"}


@pytest.mark.parametrize("kv", [2, 4])
def test_reference_matches_prefill_then_decode(kv):
    from repro.models.stepfn import make_decode_step, make_prefill_step
    from repro.parallel.sharding import ParallelConfig, ShardCtx
    cfg = dict(SMOKE, num_key_value_heads=kv)
    serve = harness.Files().driver("serve")
    arch = serve.arch_config("smoke", cfg)
    seed, B, P, steps = 2**33 + 5, 2, 12, 5
    params = serve.program_params(cfg, seed, arch)
    px = ShardCtx(mesh=None, pcfg=ParallelConfig())
    prefill = jax.jit(make_prefill_step(arch, px, cache_cap=P + steps))
    decode = jax.jit(make_decode_step(arch, px))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, (B, P + steps), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(params, {"tokens": jnp.asarray(tokens[:, :P])})
        got = [logits]
        for i in range(steps):
            logits, cache = decode(
                params, cache,
                {"tokens": jnp.asarray(tokens[:, P + i:P + i + 1])},
                jnp.asarray(P + i, jnp.int32))
            got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)       # (B, steps+1, V)
    want = np.asarray(dense.logits(cfg, seed, tokens, P - 1))
    assert want.shape == got.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert np.abs(want).max() > 0.05   # the logits are not all near 0


def test_reference_refuses_what_it_does_not_compute():
    for bad in ({"hidden_act": "gelu"}, {"partial_rotary_factor": 0.25},
                {"norm": "layernorm"}, {"use_qkv_bias": True},
                {"tie_word_embeddings": True}):
        with pytest.raises(NotImplementedError):
            dense.check_supported(dict(SMOKE, **bad))


def test_control_rounds_to_float8():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 64)),
                    jnp.float32)
    q = dense._fp8(x, -1)
    rel = np.abs(np.asarray(q - x)) / np.abs(np.asarray(x)).max(-1,
                                                               keepdims=True)
    assert 0 < rel.max() <= 2.0 ** -4
