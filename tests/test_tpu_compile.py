"""The serve path's Pallas kernels compile for a TPU v5e, at InternLM2-1.8B's
widths, with no chip attached.

Interpret mode runs slices and tiles the chip's compiler refuses, so the
parity tests in ``test_kernels.py`` cannot show that a kernel compiles. Here
each kernel is lowered with ``interpret=False`` against a described v5e and
compiled; the compiled program must hold the kernel (``tpu_custom_call``).
The largest flash and flash-decode configs the VMEM models in
``repro.kernels.ops`` admit must compile too, so those models never admit
a config the compiler refuses.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler's library, and under a
multi-worker run only the worker given this file should load it.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import ops

BF16 = jnp.bfloat16
#: InternLM2-1.8B attention widths and the smoke run's serving shape
H, KV, HD, S, B, CACHE = 16, 8, 128, 2048, 8, 2080


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    return compiled


def _flash(one_chip, block_q, block_kv):
    qkv = jax.ShapeDtypeStruct((1, S, H, HD), BF16, sharding=one_chip)
    _compile(lambda q, k, v: ops.flash_attention(
        q, k, v, block_q=block_q, block_kv=block_kv, interpret=False),
        qkv, qkv, qkv)


def _decode(one_chip, kv, block_kv, num_splits, combine):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    _compile(lambda q, k, v, p, c: ops.decode_attention(
        q, k, v, p, c, block_kv=block_kv, num_splits=num_splits,
        combine=combine, interpret=False),
        sds((B, 1, H, HD), BF16), sds((B, CACHE, kv, HD), BF16),
        sds((B, CACHE, kv, HD), BF16), sds((B, CACHE), jnp.int32),
        sds((B,), jnp.int32))


def test_flash_prefill_compiles(one_chip):
    _flash(one_chip, 512, 512)


def test_flash_largest_valid_config_compiles(one_chip):
    space = ops.flash_config_space(S)
    admitted = [c for c in map(space.config, range(space.size))
                if ops.flash_valid(c, HD, 2)]
    cfg = max(admitted, key=lambda c: _fa.flash_vmem_bytes(
        c["block_q"], c["block_kv"], HD, 2))
    _flash(one_chip, cfg["block_q"], cfg["block_kv"])


@pytest.mark.parametrize("kv,num_splits,combine", [
    (KV, 1, "jax"), (KV, 2, "kernel"),   # the serving GQA shape
    (1, 2, "jax"),                       # MQA
    (H, 1, "kernel"),                    # MHA
])
def test_flash_decode_compiles(one_chip, kv, num_splits, combine):
    _decode(one_chip, kv, 512, num_splits, combine)


def test_flash_decode_largest_valid_config_compiles(one_chip):
    space = ops.decode_config_space(CACHE)
    admitted = [c for c in map(space.config, range(space.size))
                if ops.decode_valid(c, KV, H // KV, HD, 2)]
    cfg = max(admitted, key=lambda c: (
        _fd.decode_vmem_bytes(c["block_kv"], KV, H // KV, HD, 2),
        c["num_splits"]))
    _decode(one_chip, KV, cfg["block_kv"], cfg["num_splits"], cfg["combine"])


def test_gemm_compiles(one_chip):
    ab = jax.ShapeDtypeStruct((2048, 2048), BF16, sharding=one_chip)
    _compile(lambda a, b: ops.gemm(a, b, interpret=False), ab, ab)


def test_matern_gp_compiles(one_chip):
    N, T, d = 4096, 128, 15
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                              sharding=one_chip)
    _compile(lambda *a: ops.gp_posterior(*a, interpret=False),
             sds(N, d), sds(T, d), sds(T, T), sds(T), sds(T))
