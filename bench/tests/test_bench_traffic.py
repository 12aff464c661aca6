"""Prompts and weights are a function of the seed alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.traffic import ClosedLoop

MIX = {"loop": "closed", "clients": 3, "prompt_tokens": 16,
       "output_tokens": 4}
CFG = {"hidden_size": 16, "intermediate_size": 32, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "vocab_size": 64}
BIG = 2**31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 1])
def test_prompts_repeat_per_seed(seed):
    a, b = ClosedLoop(MIX, 64, seed), ClosedLoop(MIX, 64, seed)
    for i in range(3):
        pa, pb = np.asarray(a.prompts(i)), np.asarray(b.prompts(i))
        assert pa.shape == (3, 16) and pa.dtype == np.int32
        np.testing.assert_array_equal(pa, pb)
        assert 0 <= pa.min() and pa.max() < 64
    assert not np.array_equal(np.asarray(a.prompts(0)),
                              np.asarray(a.prompts(1)))


def test_seeds_change_ids_not_sizes():
    a, b = ClosedLoop(MIX, 64, 1), ClosedLoop(MIX, 64, BIG)
    pa, pb = np.asarray(a.prompts(0)), np.asarray(b.prompts(0))
    assert pa.shape == pb.shape and not np.array_equal(pa, pb)


def test_open_loop_is_refused():
    with pytest.raises(ValueError):
        ClosedLoop(dict(MIX, loop="open"), 64, 0)


def test_seed_out_of_range_is_refused():
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_stacked_weights_equal_layer_by_layer():
    """The program's one-call stacked weights and the reference's
    per-layer weights are the same numbers, exactly."""
    seed = BIG
    st = weights.stacked(CFG, seed, jnp.bfloat16)
    for l in range(3):
        lay = weights.layer(CFG, seed, l, jnp.float32)
        for name, w in lay.items():
            got = np.asarray(st[name][l].astype(jnp.float32))
            np.testing.assert_array_equal(got, np.asarray(w), err_msg=name)
    g = weights.globals_(CFG, seed)
    for name, w in g.items():
        np.testing.assert_array_equal(
            np.asarray(st[name].astype(jnp.float32)), np.asarray(w))
    other = weights.stacked(CFG, seed + 1, jnp.bfloat16)
    assert not np.array_equal(np.asarray(st["wq"], np.float32),
                              np.asarray(other["wq"], np.float32))


def test_weights_are_exact_in_bfloat16():
    w = weights.layer(CFG, 3, 0, jnp.float32)
    for name, x in w.items():
        x = np.asarray(x)
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(
                jnp.float32)), x, err_msg=name)
