"""Operations and bytes that a dense decoder's steps and attention kernels
need, computed from shapes alone.

These are the yardstick: the work the algorithm needs, whatever implements
it. Masked causal blocks, K/V repeated for grouped heads, padding and
copies are not counted.
"""
from __future__ import annotations


def layer_matmul_params(n: dict) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and the
    gated MLP), from ``weights.dims``."""
    d, H, KV, hd, F = n["d"], n["H"], n["KV"], n["hd"], n["F"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def causal_attention_flops(B: int, S: int, H: int, hd: int) -> int:
    """q·k and p·v over the causal half: query i sees keys 0..i."""
    return 4 * B * H * hd * (S * (S + 1) // 2)


def prefill_step_flops(n: dict, B: int, S: int) -> int:
    """One prefill step: every layer on B×S tokens, causal attention, and the
    head on the last position only (the step returns that row's logits)."""
    return (2 * n["L"] * layer_matmul_params(n) * B * S
            + n["L"] * causal_attention_flops(B, S, n["H"], n["hd"])
            + 2 * n["d"] * n["V"] * B)


def decode_step_flops(n: dict, B: int, live: int) -> int:
    """One decode step: one token per row through every layer, attention
    over ``live`` cached positions (the new one included), and the head."""
    return (2 * n["L"] * layer_matmul_params(n) * B
            + n["L"] * 4 * B * n["H"] * n["hd"] * live
            + 2 * n["d"] * n["V"] * B)


def flash_prefill_cost(B: int, S: int, H: int, KV: int, hd: int,
                       itemsize: int = 2):
    """(flops, bytes) of one causal attention call: q and o at H heads, K and
    V at KV heads, each read or written once."""
    flops = causal_attention_flops(B, S, H, hd)
    nbytes = (2 * B * S * H * hd + 2 * B * S * KV * hd) * itemsize
    return flops, nbytes


def flash_decode_cost(B: int, live: int, H: int, KV: int, hd: int,
                      itemsize: int = 2):
    """(flops, bytes) of one decode attention call: K and V at the ``live``
    positions, q read and o written once."""
    flops = 4 * B * H * hd * live
    nbytes = (2 * B * live * KV * hd + 2 * B * H * hd) * itemsize
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    its peak rate and bytes over its memory bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
