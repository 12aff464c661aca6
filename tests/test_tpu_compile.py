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
import collections
import re

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


# -- the decode step updates its cache in place --------------------------

#: decode blocks of the serve cells; layers and batch cut to compile fast
DECODE_BLOCKS, STEP_L, STEP_B = dict(block_kv=512, num_splits=1), 2, 2
#: a capacity the decode tile divides, and one it does not (rounded up at
#: allocation). Large enough that each K/V stack outgrows VMEM, as a
#: serving cache does: XLA may stage a smaller one through VMEM, a copy a
#: cache of serving size never gets.
TILED, RAGGED = 16384, 16384 + 32
PALLAS = "pallas"


def _shape_op(line: str):
    """(name, shapes of the result, opcode) of one HLO instruction."""
    m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):                   # a tuple: balance the parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 2:]
    else:
        result, _, rest = rest.partition(" ")
    shapes = {tuple(int(d) for d in s.split(",") if d)
              for s in re.findall(r"\w+\[([\d,]*)\]", result)}
    return m.group(1), shapes, rest.partition("(")[0]


def _step_arch(attention: str, kv: int):
    """InternLM2-1.8B (GQA) or DeepSeek-V3 (MLA, its dense layers) at
    published widths, cut to ``STEP_L`` layers."""
    import dataclasses
    from repro.configs.registry import get_arch
    if attention == "mla":
        return dataclasses.replace(get_arch("deepseek-v3-671b"),
                                   num_layers=STEP_L, moe=None)
    return dataclasses.replace(get_arch("internlm2-1.8b"), num_layers=STEP_L,
                               num_kv_heads=kv)


def _decode_step_hlo(one_chip, arch, kernel, cap, donate=True):
    from repro.models.params import abstract_params
    from repro.models.stepfn import make_decode_step, make_prefill_step
    from repro.parallel.sharding import KernelConfig, ParallelConfig, ShardCtx
    kc = None if kernel is None else KernelConfig(
        use_decode=True, decode_block_kv=DECODE_BLOCKS["block_kv"],
        decode_num_splits=DECODE_BLOCKS["num_splits"], interpret=False)
    px = ShardCtx(mesh=None, pcfg=ParallelConfig(
        flash_threshold=1 << 30, logits_chunk=0, kernel=kc))
    put = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(  # noqa
        a.shape, a.dtype, sharding=one_chip), tree)
    params = abstract_params(arch)
    _, cache = jax.eval_shape(
        make_prefill_step(arch, px, cache_cap=cap), params,
        {"tokens": jax.ShapeDtypeStruct((STEP_B, 8), jnp.int32)})
    step = {"tokens": jax.ShapeDtypeStruct((STEP_B, 1), jnp.int32)}
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = jax.jit(make_decode_step(arch, px),
                  donate_argnums=(1,) if donate else ()).lower(
        put(params), put(cache), put(step), pos).compile().as_text()
    return hlo, cache


def _cache_ops(hlo: str, shapes):
    """(opcode, shapes) of each instruction outside fused computations whose
    result has one of ``shapes``, except plumbing and the token's in-place
    scatters; and the names of those scatters."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    plumbing = {"bitcast", "get-tuple-element", "parameter", "tuple",
                "while"}
    comp, ops, writes = None, [], []
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head:
            comp = head.group(1)
            continue
        parsed = _shape_op(line)
        if comp in fused or parsed is None or not parsed[1] & shapes:
            continue
        name, result, op = parsed
        if op in plumbing:
            continue
        if op == "fusion" and re.search(r'op_name="[^"]*/scatter"', line):
            writes.append(name)
        else:
            ops.append((op, tuple(sorted(result & shapes))))
    return ops, writes


@pytest.mark.parametrize("attention,kv,cap,kernel", [
    ("gqa", KV, TILED, PALLAS), ("gqa", KV, RAGGED, PALLAS),
    ("gqa", 1, TILED, PALLAS), ("gqa", 1, RAGGED, PALLAS),
    ("gqa", 4, TILED, PALLAS), ("gqa", KV, TILED, None),
    ("gqa", 1, TILED, None),
    # a latent slot is 1/7 of InternLM2's K/V: four times the slots
    ("mla", None, 4 * TILED, None)])
def test_donated_decode_step_writes_its_cache_in_place(one_chip, attention,
                                                       kv, cap, kernel):
    """Compiled for a v5e with the cache donated, at InternLM2-1.8B's widths
    (GQA, the Pallas decode on or the pure-JAX one) and DeepSeek-V3's (MLA,
    pure JAX): every cache leaf is aliased from input to output; outside
    fused computations no copy, slice, pad or new buffer of a layer's or
    the stack's K/V shape is made, the token's scatter into the carried
    stack of each K/V leaf being the only op of that shape. With Pallas the
    kernel is called once, inside the loop over layers; MQA (KV 1) takes
    the same path, its caches read without the unit head axis."""
    arch = _step_arch(attention, kv)
    hlo, cache = _decode_step_hlo(one_chip, arch, kernel, cap)
    leaves = jax.tree.leaves(cache)
    if attention == "gqa":
        tile = (DECODE_BLOCKS["block_kv"] * DECODE_BLOCKS["num_splits"]
                if kernel else 1)
        stack = (STEP_L, STEP_B, -(-cap // tile) * tile, kv, HD)
        assert {a.shape for a in leaves} == {stack, stack[:3]}

    params = {int(m.group(1)) for m in re.finditer(
        r"parameter\((\d+)\).*op_name=\"cache\[", hlo)}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    aliased = {int(p) for p in re.findall(r"\}: \((\d+),",
                                          alias.group(1) if alias else "")}
    assert len(params) == len(leaves) and params <= aliased, (params, alias)

    kv_leaves = [a.shape for a in leaves if a.dtype != jnp.int32]
    shapes = {s for shape in kv_leaves for s in (shape, shape[1:])}
    shapes |= {tuple(d for d in s if d != 1) for s in shapes}   # MQA views
    ops, writes = _cache_ops(hlo, shapes)
    assert len(writes) == len(kv_leaves), writes
    if attention == "mla":
        # XLA keeps the 64-wide rope keys with the slot axis minor between
        # programs and transposes them for the loop: a copy of the k_rope
        # stack in and out, which the step makes undonated as well (with
        # a copy of the whole c_kv stack besides)
        rope = {a.shape for a in leaves
                if a.shape[-1] == arch.mla.qk_rope_head_dim}
        assert {s for op, ss in ops for s in ss} <= rope, ops
        assert all(op == "copy" for op, _ in ops), ops
        kept, _ = _cache_ops(_decode_step_hlo(one_chip, arch, kernel, cap,
                                              donate=False)[0], shapes)
        assert not collections.Counter(ops) - collections.Counter(kept), (
            ops, kept)
    else:
        assert not ops, ops

    kernels = [ln for ln in hlo.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(kernels) == (1 if kernel else 0), kernels
    if kernel:
        name = re.match(r"\s*(?:ROOT )?%(\S+) = ", kernels[0]).group(1)
        body, = re.findall(r" while\(.*?body=%([\w.\-]+)", hlo)
        comp = re.search(rf"^%{re.escape(body)} .*?^\}}", hlo, re.M | re.S)
        assert comp and f"%{name} = " in comp.group(0), (name, body)
