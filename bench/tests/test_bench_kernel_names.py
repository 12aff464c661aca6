"""The roofline readers find the program's kernels by the names XLA gives
them: the prefill and decode steps, compiled for a described v5e with the
Pallas kernels on, hold custom calls that ``tracefile`` matches to each
reader's ``KERNEL``, once per layer (inside the loop over layers)."""
import dataclasses
import re
import tempfile

import pytest

from bench import harness

CFG = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 128, "vocab_size": 1024, "hidden_act": "silu",
       "rms_norm_eps": 1e-5, "rope_theta": 10000,
       "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
KERNELS = {"flash": {"block_q": 128, "block_kv": 128},
           "decode": {"block_kv": 128, "num_splits": 1, "combine": "jax"}}
B, P, C = 2, 256, 64


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def steps_hlo(one_chip):
    import jax
    import jax.numpy as jnp
    from repro.models.params import abstract_params
    from repro.models.stepfn import make_decode_step, make_prefill_step
    from repro.parallel.sharding import ShardCtx
    serve = harness.Files().driver("serve")
    arch = serve.arch_config("small", CFG)
    with tempfile.TemporaryDirectory() as store:
        serve.seed_store(store, arch, KERNELS, B, P, P + C)
        pcfg = serve.resolve_pcfg(store, arch, P, P + C)
    pcfg = pcfg.replace(kernel=dataclasses.replace(pcfg.kernel,
                                                   interpret=False))
    px = ShardCtx(mesh=None, pcfg=pcfg)
    prefill = make_prefill_step(arch, px, cache_cap=P + C)
    decode = make_decode_step(arch, px)

    def put(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = abstract_params(arch)
    tokens = jax.ShapeDtypeStruct((B, P), jnp.int32)
    _, cache = jax.eval_shape(prefill, params, {"tokens": tokens})
    step = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return {
        "prefill": jax.jit(prefill).lower(
            put(params), put({"tokens": tokens})).compile().as_text(),
        "decode": jax.jit(decode).lower(
            put(params), put(cache), put(step), pos).compile().as_text()}


def kernel_calls(hlo: str, kernel: str) -> list:
    """Custom calls that ``tracefile.kernel_match`` counts as ``kernel``:
    by instruction name, or by op name."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*custom_call_target="
                     r"\"tpu_custom_call\"", line)
        if m and (re.fullmatch(rf"{kernel}(\.\d+)?", m.group(1))
                  or f"jit({kernel})/pallas_call" in line):
            out.append(m.group(1))
    return out


@pytest.mark.parametrize("step, metric", [
    ("prefill", "flash_prefill_roofline"),
    ("decode", "flash_decode_roofline")])
def test_each_step_calls_its_readers_kernel_once_in_the_layer_loop(
        steps_hlo, step, metric):
    kernel = harness.Files().metric(metric).KERNEL
    hlo = steps_hlo[step]
    calls = kernel_calls(hlo, kernel)
    assert len(calls) == 1, (kernel, calls)
    assert hlo.count("tpu_custom_call") == 1
    assert " while(" in hlo                 # the layers run in one loop
