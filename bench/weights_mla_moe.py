"""Weights of a DeepSeek-V3-style decoder (MLA, leading dense layers, then
layers of routed and shared experts), made from the seed.

As in ``bench.weights``: every tensor is a whole number in [-128, 127]
times a power of two, exact in bfloat16 and float32 alike, with its own key
folded from the seed, its name and its layer, so that the reference (layer
by layer) and the program (layers stacked) get the same bits. Norm scales
are 1 + j/128 for j in [-16, 15]. The router's weights are float32 in the
program too; its selection bias is not made here but levelled on the
seed's weights (``bench.reference.mla_moe.router_bias``).

Each layer has the MLA projections (``MLA``) and, where it is dense, a gated
MLP (``DENSE``), else the router, the held experts stacked on a leading
axis and the shared expert (``MOE``). Layer ``l`` is numbered over the
whole cut depth: the dense layers first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import seed_key, tensor_key

#: power-of-two exponent of each tensor's step (the values' std is about 74
#: steps): 2^-13 for fan-in 7168, 2^-11 for 512, and small steps for the
#: projections back into the residual stream. The query's step, 2^-11 for
#: fan-in 1536, is twice the fan-in's: attention scores then spread about 2
#: rather than 1, so a head attends to tens of positions. With scores of
#: spread 1 it averaged hundreds, a request's hidden states all leaned on
#: that one average, and greedy decoding settled on a repeated token: a
#: request routed to the same experts for all its output tokens
SCALE_EXP = {"embed": -12, "head": -13, "wq_a": -13, "wq_b": -11,
             "wkv_a": -13, "wk_rope": -13, "wk_nope": -11, "wv": -11,
             "wo": -15, "wg": -13, "wu": -13, "wd": -15, "router": -13,
             "ewg": -13, "ewu": -13, "ewd": -15,
             "swg": -13, "swu": -13, "swd": -15}
NORMS = ("ln1", "ln2", "q_a_norm", "kv_a_norm", "final_norm")
#: float32 in the program as well
FLOAT32 = NORMS + ("router",)


def dims(cfg: dict) -> dict:
    """The sizes the shapes are made of, from a configuration file."""
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "ql": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "ns": cfg["n_shared_experts"],
            "E": cfg["routed_experts_published"],
            "El": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"]}


def mla_shapes(n: dict) -> dict:
    d, H, ql, r = n["d"], n["H"], n["ql"], n["r"]
    dn, dr, dv = n["dn"], n["dr"], n["dv"]
    return {"ln1": (d,), "wq_a": (d, ql), "q_a_norm": (ql,),
            "wq_b": (ql, H, dn + dr), "wkv_a": (d, r), "kv_a_norm": (r,),
            "wk_rope": (d, dr), "wk_nope": (r, H, dn), "wv": (r, H, dv),
            "wo": (H, dv, d), "ln2": (d,)}


def dense_shapes(n: dict) -> dict:
    d, F = n["d"], n["F"]
    return {"wg": (d, F), "wu": (d, F), "wd": (F, d)}


def moe_shapes(n: dict) -> dict:
    d, f, El, fs = n["d"], n["f"], n["El"], n["f"] * n["ns"]
    return {"router": (d, n["E"]),
            "ewg": (El, d, f), "ewu": (El, d, f), "ewd": (El, f, d),
            "swg": (d, fs), "swu": (d, fs), "swd": (fs, d)}


def layer_shapes(cfg: dict, l: int) -> dict:
    n = dims(cfg)
    return dict(mla_shapes(n), **(dense_shapes(n) if l < n["Ld"]
                                  else moe_shapes(n)))


def global_shapes(cfg: dict) -> dict:
    n = dims(cfg)
    return {"embed": (n["V"], n["d"]), "final_norm": (n["d"],),
            "head": (n["d"], n["V"])}


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
            for n, s in layer_shapes(cfg, l).items()}


def globals_(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    key = seed_key(seed)
    return {n: make(tensor_key(key, n, 0), n, s, dtype)
            for n, s in global_shapes(cfg).items()}


def stacked(cfg: dict, seed: int, dtype) -> dict:
    """Every tensor at once, in one jitted call on the device: ``dense``
    and ``moe`` hold their layers' tensors stacked on a leading axis, the
    norms, the router and its bias in float32, the rest in ``dtype``."""
    n = dims(cfg)
    Ld, L = n["Ld"], n["L"]

    def dt(name):
        return jnp.float32 if name in FLOAT32 else dtype

    def stack(key, shapes, first, count):
        out = {}
        for name, s in shapes.items():
            keys = jax.vmap(lambda l, name=name: tensor_key(key, name, l))(
                jnp.arange(first, first + count))
            out[name] = jax.vmap(
                lambda k, name=name, s=s: make(k, name, s, dt(name)))(keys)
        return out

    @jax.jit
    def build(key):
        out = {name: make(tensor_key(key, name, 0), name, s, dt(name))
               for name, s in global_shapes(cfg).items()}
        out["dense"] = stack(key, dict(mla_shapes(n), **dense_shapes(n)),
                             0, Ld)
        out["moe"] = stack(key, dict(mla_shapes(n), **moe_shapes(n)),
                           Ld, L - Ld)
        return out

    return build(seed_key(seed))
