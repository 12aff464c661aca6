"""``DecodeServer`` donates the KV cache to its decode step.

On the CPU at smoke size, over the pure-JAX decode and the Pallas decode
(interpret mode): the donated step gives the logits and the cache of a
step that keeps its input, the cache it was given is deleted after the
step, and JAX never reports the donated buffers as unusable (it would then
copy the cache instead of updating it).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import smoke_config
from repro.launch import serve
from repro.parallel.sharding import KernelConfig, ParallelConfig

PROMPT, STEPS = 8, 5
PALLAS = KernelConfig(use_decode=True, decode_block_kv=8, decode_num_splits=2)
CASES = [("gemma-2b", None), ("qwen3-moe-30b-a3b", None),
         ("deepseek-v3-671b", None), ("xlstm-1.3b", None),
         ("musicgen-large", None), ("gemma-2b", PALLAS),
         ("qwen3-moe-30b-a3b", PALLAS), ("recurrentgemma-9b", PALLAS)]
IDS = [f"{arch}-{'pallas' if kc else 'jax'}" for arch, kc in CASES]


def make_server(arch, kernel):
    return serve.DecodeServer(
        smoke_config(arch),
        ParallelConfig(flash_threshold=1 << 30, logits_chunk=0,
                       kernel=kernel),
        batch=2, prompt_len=PROMPT, decode_steps=STEPS)


def deleted(tree) -> bool:
    return all(a.is_deleted() for a in jax.tree.leaves(tree))


@pytest.fixture
def no_unusable_donation():
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*[Dd]onated buffers")
        yield


@pytest.mark.parametrize("arch,kernel", CASES, ids=IDS)
def test_donated_step_matches_a_step_that_keeps_its_cache(
        arch, kernel, no_unusable_donation):
    server = make_server(arch, kernel)
    logits, cache = server.prefill(server.params, server.input_batch())
    kept = jax.tree.map(jnp.copy, cache)
    toks = jnp.argmax(logits, -1)
    for i in range(STEPS):
        inputs = server.step_batch(toks)
        pos = jnp.asarray(PROMPT + i, jnp.int32)
        want, kept = server.decode(server.params, kept, inputs, pos)
        given = cache
        logits, cache = server.decode_in_place(server.params, given, inputs,
                                               pos)
        assert deleted(given)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(kept)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        toks = jnp.argmax(logits, -1)


@pytest.mark.parametrize("arch,kernel", [("gemma-2b", None),
                                         ("gemma-2b", PALLAS)],
                         ids=["gemma-2b-jax", "gemma-2b-pallas"])
def test_server_steps_consume_each_cache_once(arch, kernel,
                                              no_unusable_donation):
    server = make_server(arch, kernel)
    batch = server.input_batch()
    server.warmup(batch)
    logits, kept = server.prefill(server.params, batch)
    server.prefill_batch(batch)
    want = [jnp.argmax(logits, -1)]
    for i in range(STEPS):
        logits, kept = server.decode(server.params, kept,
                                     server.step_batch(want[-1]),
                                     jnp.asarray(PROMPT + i, jnp.int32))
        want.append(jnp.argmax(logits, -1))
        given = server.cache
        server.decode_step()
        assert deleted(given) and not deleted(server.cache)
    np.testing.assert_array_equal(np.stack(server.out), np.stack(want))


def test_a_replaced_decode_step_is_run_without_donation():
    """A wrapper put in place of ``decode`` may hand back the cache it was
    given: the server runs it as given, and that cache stays readable."""
    server = make_server("gemma-2b", None)
    step = server.decode
    server.decode = lambda p, c, b, pos: (step(p, c, b, pos)[0], c)
    server.prefill_batch(server.input_batch())
    given = server.cache
    for _ in range(2):
        server.decode_step()
    assert server.cache is given and not deleted(given)


def test_unrolled_layers_write_the_stacks_as_the_layer_scan_does(
        no_unusable_donation):
    """With ``scan_layers=False`` the decode step indexes the stacked
    caches layer by layer and writes them in place, to the layer scan's
    result: positions exactly, logits within the bf16 band of the kernel
    tests, K/V within a bf16 rounding carried through the layers (XLA
    fuses the two programs differently)."""
    got = []
    for scan in (True, False):
        server = serve.DecodeServer(
            smoke_config("qwen3-moe-30b-a3b"),
            ParallelConfig(flash_threshold=1 << 30, logits_chunk=0,
                           kernel=PALLAS, scan_layers=scan),
            batch=2, prompt_len=PROMPT, decode_steps=STEPS)
        logits, cache = server.prefill(server.params, server.input_batch())
        for i in range(STEPS):
            given = cache
            logits, cache = server.decode_in_place(
                server.params, given,
                server.step_batch(jnp.argmax(logits, -1)),
                jnp.asarray(PROMPT + i, jnp.int32))
            assert deleted(given)
        got.append((np.asarray(logits), jax.tree.map(np.asarray, cache)))
    (logits, cache), (want, want_cache) = got
    assert float(np.abs(logits - want).max()) < 5e-3 * max(
        float(np.abs(want).max()), 1.0)
    for path, a in jax.tree_util.tree_leaves_with_path(cache):
        b = dict(jax.tree_util.tree_leaves_with_path(want_cache))[path]
        if a.dtype == np.int32:                 # positions: exact
            np.testing.assert_array_equal(a, b)
        else:             # K/V: a bf16 rounding apart where they differ
            a, b = np.float32(a), np.float32(b)
            assert float(np.abs(a - b).max()) < 1e-2 * max(
                float(np.abs(b).max()), 1.0), path
