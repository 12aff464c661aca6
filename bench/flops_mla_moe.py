"""Operations and bytes that a DeepSeek-V3-style decoder's decode steps and
latent decode kernel need, computed from shapes alone (``bench.flops``'s
yardstick for the dense decoder): the work the algorithm needs, whatever
implements it. Decode attention is counted absorbed, in the latent space,
at live positions; the latent rows at their published width, not the
lanes they are padded to; routed experts only for the copies computed.
"""
from __future__ import annotations


def mla_decode_params(n: dict) -> int:
    """Weights one decoded token multiplies through in one MLA layer: the q
    and kv down-projections, q's up-projection, the rope keys, the two
    absorbed projections (q_nope into the latent space, the context out of
    it) and the output projection; from ``weights_mla_moe.dims``."""
    d, H, ql, r = n["d"], n["H"], n["ql"], n["r"]
    dn, dr, dv = n["dn"], n["dr"], n["dv"]
    return (d * ql + ql * H * (dn + dr) + d * r + d * dr + H * dn * r
            + H * r * dv + H * dv * d)


def latent_attention_flops(B: int, live: int, H: int, r: int,
                           dr: int) -> int:
    """One layer's absorbed decode attention: scores over c_kv and k_rope,
    then the probabilities times c_kv, for every head."""
    return 2 * B * H * live * (2 * r + dr)


def expert_flops(n: dict) -> int:
    """One token through one routed expert (a gated MLP of width f)."""
    return 2 * 3 * n["d"] * n["f"]


def decode_step_flops(n: dict, B: int, live: int, copies: int) -> int:
    """One decode step of B rows: MLA in every layer, attention over
    ``live`` cached positions (the new one included), the dense MLP in the
    leading layers, the router and the shared expert in the others,
    ``copies`` routed copies computed by held experts, and the head."""
    L, Ld = n["L"], n["Ld"]
    per_row = (L * mla_decode_params(n) + Ld * 3 * n["d"] * n["F"]
               + (L - Ld) * (3 * n["d"] * n["f"] * n["ns"]
                             + n["d"] * n["E"])
               + n["d"] * n["V"])
    return (2 * B * per_row + copies * expert_flops(n)
            + L * latent_attention_flops(B, live, n["H"], n["r"], n["dr"]))


def mla_decode_cost(B: int, live: int, H: int, r: int, dr: int,
                    itemsize: int = 2):
    """(flops, bytes) of one latent decode attention call: each live latent
    row (c_kv and k_rope) read once, each row's queries read and its
    latent context written once."""
    flops = latent_attention_flops(B, live, H, r, dr)
    nbytes = (B * live * (r + dr) + B * H * (r + dr) + B * H * r) * itemsize
    return flops, nbytes
