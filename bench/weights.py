"""Weights of a dense decoder, made from the seed.

Every tensor is a whole number in [-128, 127] times a power of two, so its
value is exact in bfloat16 and float32 alike, and the same bits come out
whether a tensor is made alone (the reference, layer by layer) or with all
others in one jitted call (the program, layers stacked). Norm scales are
1 + j/128 for j in [-16, 15], exact in bfloat16 too.

Names follow ``layer_shapes``; each tensor has its own key, folded from the
seed, the tensor's name and its layer.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: power-of-two exponent of each tensor's step: std of the values is about
#: 74 steps, so 2^-12 gives 0.018 and 2^-15 (the projections back into the
#: residual stream) 0.0023
SCALE_EXP = {"embed": -12, "head": -12, "wq": -12, "wk": -12, "wv": -12,
             "wo": -15, "wg": -12, "wu": -12, "wd": -15}
NORMS = ("ln1", "ln2", "final_norm")


def dims(cfg: dict) -> dict:
    """The sizes a dense decoder's shapes are made of, from a configuration
    file's keys."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"d": d, "H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def layer_shapes(cfg: dict) -> dict:
    n = dims(cfg)
    d, H, KV, hd, F = n["d"], n["H"], n["KV"], n["hd"], n["F"]
    return {"ln1": (d,), "wq": (d, H, hd), "wk": (d, KV, hd),
            "wv": (d, KV, hd), "wo": (H, hd, d), "ln2": (d,),
            "wg": (d, F), "wu": (d, F), "wd": (F, d)}


def global_shapes(cfg: dict) -> dict:
    n = dims(cfg)
    return {"embed": (n["V"], n["d"]), "final_norm": (n["d"],),
            "head": (n["d"], n["V"])}


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any seed below 2**64 (the two 32-bit words)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32),
        impl="threefry2x32")


def tensor_key(key: jax.Array, name: str, layer: int) -> jax.Array:
    return jax.random.fold_in(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        layer)


def make(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    """One tensor from its own key."""
    ints = jax.random.bits(key, shape, jnp.uint8).astype(jnp.int32) - 128
    if name in NORMS:
        steps = (ints // 8).astype(jnp.float32)
        return (1.0 + steps * 2.0 ** -7).astype(dtype)
    return (ints.astype(jnp.float32) * 2.0 ** SCALE_EXP[name]).astype(dtype)


def layer(cfg: dict, seed: int, l: int, dtype=jnp.float32) -> dict:
    """Layer ``l``'s tensors, each in ``dtype``."""
    key = seed_key(seed)
    return {n: make(tensor_key(key, n, l), n, s, dtype)
            for n, s in layer_shapes(cfg).items()}


def globals_(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    key = seed_key(seed)
    return {n: make(tensor_key(key, n, 0), n, s, dtype)
            for n, s in global_shapes(cfg).items()}


def stacked(cfg: dict, seed: int, dtype, norm_dtype=jnp.float32) -> dict:
    """Every tensor at once, in one jitted call on the device: the layers'
    tensors stacked along a leading axis of ``num_hidden_layers``."""
    L = dims(cfg)["L"]
    lshapes, gshapes = layer_shapes(cfg), global_shapes(cfg)

    def dt(n):
        return norm_dtype if n in NORMS else dtype

    @jax.jit
    def build(key):
        out = {n: make(tensor_key(key, n, 0), n, s, dt(n))
               for n, s in gshapes.items()}
        for n, s in lshapes.items():
            keys = jax.vmap(lambda l, n=n: tensor_key(key, n, l))(
                jnp.arange(L))
            out[n] = jax.vmap(lambda k, n=n, s=s: make(k, n, s, dt(n)))(keys)
        return out

    return build(seed_key(seed))
