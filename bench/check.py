"""Whether served tokens are the ones the plain reference would serve.

A served token is judged by how far its reference logit lies below the
reference's best logit at that position: 0 where the program chose the
reference's own argmax, small where two logits lie within rounding of each
other, and large where the program chose a token the reference does not
rank near the top. The widest such gap over a sample of requests is the
number compared with the cell's limit.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.reference import dense


def _sequences(prompts: np.ndarray, served: np.ndarray):
    """The tokens the reference runs over: each prompt and its served
    tokens but the last; the logits that chose served token j sit at
    position P - 1 + j."""
    return np.concatenate([prompts, served[:, :-1]], 1), prompts.shape[1] - 1


def reference(cfg: dict, seed: int, prompts: np.ndarray, served: np.ndarray,
              rows: int = 1, fp8: bool = False):
    """The reference's logits at every served position (the float8
    control's with ``fp8``)."""
    tokens, first = _sequences(prompts, served)
    return dense.logits(cfg, seed, tokens, first, fp8=fp8, rows=rows)


def gap(ref, picked) -> float:
    """Widest gap between the reference's best logit and that of the token
    picked at each position."""
    got = jnp.take_along_axis(ref, jnp.asarray(picked)[..., None], -1)[..., 0]
    return float(jnp.max(ref.max(-1) - got))


def served_gap(cfg: dict, seed: int, prompts: np.ndarray,
               served: np.ndarray, rows: int = 1) -> float:
    """Widest gap, over every served token of every row, between the
    reference's best logit and the served token's."""
    return gap(reference(cfg, seed, prompts, served, rows), served)


def control_gap(cfg: dict, seed: int, prompts: np.ndarray,
                served: np.ndarray, rows: int = 1, ref=None) -> float:
    """The same gap for the tokens the float8 control ranks first at the
    same positions, over the same prompts and served tokens (``ref``: the
    reference's logits there, where they are at hand)."""
    pick = jnp.argmax(reference(cfg, seed, prompts, served, rows, fp8=True),
                      -1)
    if ref is None:
        ref = reference(cfg, seed, prompts, served, rows)
    return gap(ref, pick)
