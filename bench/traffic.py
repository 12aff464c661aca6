"""The one traffic generator: reads a mix's parameters, makes its requests
from the seed.

A closed-loop mix (``"loop": "closed"``) has ``clients`` callers that each
send their next request when the last one is answered; the serve path runs
them as one batch in lock-step. Every request has ``prompt_tokens`` token
ids, drawn uniformly from the vocabulary, and asks for ``output_tokens``
tokens. Every seed gets the same sizes; only the ids differ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights

#: fold-in tag that keeps prompt keys apart from weight keys
PROMPT_TAG = 0x70726F6D


class ClosedLoop:
    def __init__(self, params: dict, vocab: int, seed: int):
        if params.get("loop") != "closed":
            raise ValueError(f"unsupported loop {params.get('loop')!r}")
        self.clients = int(params["clients"])
        self.prompt_tokens = int(params["prompt_tokens"])
        self.output_tokens = int(params["output_tokens"])
        if self.output_tokens < 1 or self.prompt_tokens < 1:
            raise ValueError("a request has at least one prompt and one "
                             "output token")
        self.vocab = int(vocab)
        self._key = jax.random.fold_in(weights.seed_key(seed), PROMPT_TAG)
        shape = (self.clients, self.prompt_tokens)
        self._make = jax.jit(lambda key, i: jax.random.randint(
            jax.random.fold_in(key, i), shape, 0, self.vocab, jnp.int32))

    def prompts(self, batch: int) -> jax.Array:
        """The prompts of the ``batch``-th round, (clients, prompt_tokens)
        on the device."""
        return self._make(self._key, jnp.int32(batch))
