"""Train / prefill / decode step functions.

These are the functions the launcher jits and the dry-run lowers. They are
pure; distribution comes from input shardings + internal constraints.

Kernel dispatch (DESIGN.md §14): when ``px.pcfg.kernel`` is set, the
attention layers these steps trace route train/prefill attention through
the tuned Pallas flash kernel (``models/layers.py::_pallas_flash_ok``
gates it statically, so the choice is baked into the jitted step — a
kernel hot-swap means re-deriving the step fns, which
``launch/serve.py::DecodeServer`` memoizes in its compiled-kernel cache).
With ``kernel=None`` (the default) every path is pure-JAX and
byte-identical to pre-§14 traces.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.arch import ArchConfig
from repro.models import model as M
from repro.models.layers import rms_norm
from repro.parallel.sharding import ShardCtx, constrain

Tree = Dict[str, Any]


# ---------------------------------------------------------------------------
# loss


def chunked_xent(x: jax.Array, head_w: jax.Array, labels: jax.Array,
                 px: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy with the (B,S,V) logits never fully materialized.

    Scans over sequence chunks; each chunk's logits live only inside one scan
    step. Returns (sum_loss, n_valid). labels == -1 are masked.
    """
    B, S, d = x.shape
    chunk = px.pcfg.logits_chunk
    V = head_w.shape[-1]

    def chunk_loss(xc, lc):
        logits = jnp.einsum("btd,dv->btv", xc, head_w.astype(xc.dtype),
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"), px)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                 axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        return jnp.sum((logz - ll) * mask), jnp.sum(mask)

    if chunk and S > chunk and S % chunk == 0:
        n = S // chunk
        xs = x.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)

        def body(carry, inp):
            tot, cnt = carry
            s, c = chunk_loss(*inp)
            return (tot + s, cnt + c), None

        (tot, cnt), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (xs, ls))
        return tot, cnt
    return chunk_loss(x, labels)


def loss_fn(params: Tree, batch: Tree, *, cfg: ArchConfig, px: ShardCtx) -> Tuple[jax.Array, Tree]:
    if cfg.frontend == "embeddings":
        embeds = batch["frame_embeddings"]
        labels = batch["labels"]
        tokens = None
        B, S = labels.shape
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.full((B, 1), -1, tokens.dtype)], axis=1)
        embeds = None
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    cond = batch.get("cond")
    x, _, aux = M.forward(params, cfg=cfg, px=px, mode="train", tokens=tokens,
                          embeds=embeds, cond=cond, positions=positions, cache=None)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = params["lm_head"]["w"] if "lm_head" in params else params["embed"]["table"].T
    tot, cnt = chunked_xent(x, head, labels, px)
    xent = tot / jnp.maximum(cnt, 1.0)
    loss = xent + aux
    return loss, {"xent": xent, "aux": aux, "n_tokens": cnt}


# ---------------------------------------------------------------------------
# steps


def make_train_step(cfg: ArchConfig, px: ShardCtx, optimizer):
    """Returns train_step(params, opt_state, batch, step) -> (params, opt_state, metrics)."""
    mb = px.pcfg.microbatches

    def grads_of(params, batch):
        (loss, met), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg=cfg, px=px), has_aux=True)(params)
        return loss, met, grads

    def train_step(params, opt_state, batch, step):
        if mb > 1:
            def split(x):
                return x.reshape(mb, x.shape[0] // mb, *x.shape[1:])
            mbatch = jax.tree.map(split, batch)

            def body(carry, b):
                acc, loss_acc = carry
                loss, met, grads = grads_of(params, b)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / mb, acc, grads)
                return (acc, loss_acc + loss / mb), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = lax.scan(body, (zeros, jnp.zeros(())), mbatch)
            met = {}
        else:
            loss, met, grads = grads_of(params, batch)
        new_params, new_opt, opt_met = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss, **met, **opt_met, "step": step}
        return new_params, new_opt, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, px: ShardCtx, cache_cap: int):
    """prefill_step(params, batch) -> (last_token_logits, cache).

    The cache holds ``cache_cap`` positions, its K/V at the capacity the
    decode kernel reads with no padding (``layers.decode_capacity``).
    """

    def prefill_step(params, batch):
        if cfg.frontend == "embeddings":
            embeds = batch["frame_embeddings"]
            tokens = None
            B, S = embeds.shape[:2]
        else:
            tokens = batch["tokens"]
            embeds = None
            B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        cache = M.init_cache(cfg, B, cache_cap, px.pcfg.kernel)
        x, new_cache, _ = M.forward(params, cfg=cfg, px=px, mode="prefill",
                                    tokens=tokens, embeds=embeds,
                                    cond=batch.get("cond"), positions=positions,
                                    cache=cache)
        logits = M.output_head(params, cfg, x[:, -1:, :])[:, 0]
        return logits, new_cache

    return prefill_step


def make_decode_step(cfg: ArchConfig, px: ShardCtx):
    """decode_step(params, cache, batch, pos) -> (logits (B,V), cache).

    ``pos`` is the (scalar int32) position of the incoming token; the KV cache
    holds positions < pos. The step writes the token into the cache it is
    given and returns it: jit it with the cache donated
    (``donate_argnums=(1,)``) and the update runs in place, with no copy of
    the cache.
    """

    def decode_step(params, cache, batch, pos):
        if cfg.frontend == "embeddings":
            embeds = batch["frame_embeddings"]
            tokens = None
            B = embeds.shape[0]
        else:
            tokens = batch["tokens"]
            embeds = None
            B = tokens.shape[0]
        positions = jnp.full((B, 1), pos, jnp.int32)
        x, new_cache, _ = M.forward(params, cfg=cfg, px=px, mode="decode",
                                    tokens=tokens, embeds=embeds, cond=None,
                                    positions=positions, cache=cache)
        logits = M.output_head(params, cfg, x)[:, 0]
        return logits, new_cache

    return decode_step
