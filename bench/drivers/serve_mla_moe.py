"""Serve cells of a DeepSeek-V3-style decoder (MLA, dense then MoE layers),
as cut to one chip's share of an expert-parallel deployment.

The rounds, the window, the sample and the host report are
``bench/drivers/serve.py``'s. What differs is set-up and the check: the
server is built from the configuration's MLA, routing, held experts and
YaRN, with the latent flash-decode kernel at the cell's blocks and the
prefill run on the pure-JAX blockwise scan; the check runs the sample
through ``bench.reference.mla_moe``.

After every round the routed copies the held experts computed in its
decode steps (a counter in the cache, summed on the device) are added to a
running total on the device, with no sync; the total is read once, after
the window, for ``mla_moe.decode_mfu``.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla_moe as W
from bench.drivers import serve
from bench.harness import Run
from bench.reference import mla_moe
from bench.traffic import ClosedLoop


def arch_config(name: str, cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.arch import ArchConfig, MLAConfig, MoEConfig, YaRNConfig
    mla_moe.check_supported(cfg)
    n = W.dims(cfg)
    y = cfg["rope_scaling"]
    return ArchConfig(
        name=name, family="moe", num_layers=n["L"], d_model=n["d"],
        num_heads=n["H"], num_kv_heads=n["H"], d_ff=n["f"],
        dense_d_ff=n["F"], vocab_size=n["V"], attention="mla",
        mla=MLAConfig(q_lora_rank=n["ql"], kv_lora_rank=n["r"],
                      qk_nope_head_dim=n["dn"], qk_rope_head_dim=n["dr"],
                      v_head_dim=n["dv"]),
        moe=MoEConfig(num_experts=n["E"], top_k=n["K"], d_expert=n["f"],
                      num_shared_experts=n["ns"], router_score="sigmoid",
                      n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                      routed_scaling=float(cfg["routed_scaling_factor"]),
                      num_experts_held=n["El"],
                      first_expert_held=cfg["first_expert_held"]),
        moe_dense_first=n["Ld"], mlp_act="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YaRNConfig(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        tie_embeddings=False)


def _attn(t: dict) -> dict:
    return {"wq_a": t["wq_a"], "q_a_norm": {"scale": t["q_a_norm"]},
            "wq_b": t["wq_b"], "wkv_a": t["wkv_a"],
            "kv_a_norm": {"scale": t["kv_a_norm"]}, "wk_rope": t["wk_rope"],
            "wk_nope": t["wk_nope"], "wv": t["wv"], "wo": t["wo"]}


def program_params(cfg: dict, seed: int, arch) -> dict:
    """The seed's weights in the program's parameter tree, made in one
    jitted call, and the selection bias the reference levels on them;
    checked against the program's own description of the tree."""
    from repro.models.params import abstract_params
    bias = jnp.asarray(mla_moe.router_bias(cfg, seed))
    w = W.stacked(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    dense, moe = w["dense"], w["moe"]
    params = {
        "embed": {"table": w["embed"]},
        "segments": [
            {"0:attn_dense": {
                "ln1": {"scale": dense["ln1"]}, "ln2": {"scale": dense["ln2"]},
                "attn": _attn(dense),
                "mlp": {k: dense[k] for k in ("wg", "wu", "wd")}}},
            {"0:attn": {
                "ln1": {"scale": moe["ln1"]}, "ln2": {"scale": moe["ln2"]},
                "attn": _attn(moe),
                "moe": {"router": moe["router"],
                        "router_bias": bias,
                        "wg": moe["ewg"], "wu": moe["ewu"], "wd": moe["ewd"],
                        "shared": {"wg": moe["swg"], "wu": moe["swu"],
                                   "wd": moe["swd"]}}}}],
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["head"]},
    }
    want = abstract_params(arch)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree is not the MLA + "
                           f"MoE decoder this driver fills: {want}")
    return params


def build_server(ctx, seed: int):
    """Set-up: the server with the seed's weights, the latent decode kernel
    at the cell's blocks and the blockwise prefill's KV block."""
    from repro.launch.serve import DecodeServer
    from repro.parallel.sharding import KernelConfig, ParallelConfig
    t = ClosedLoop(ctx.traffic, ctx.config["vocab_size"], seed)
    arch = arch_config(ctx.config_name, ctx.config)
    dec, pre = ctx.workload["kernels"]["decode"], ctx.workload["prefill"]
    kc = KernelConfig(use_decode=True, decode_block_kv=dec["block_kv"],
                      decode_num_splits=dec["num_splits"],
                      decode_combine=dec["combine"])
    pcfg = ParallelConfig(flash_threshold=pre["flash_threshold"],
                          attn_block_kv=pre["block_kv"], logits_chunk=0,
                          kernel=kc)
    server = DecodeServer(arch, pcfg, batch=t.clients,
                          prompt_len=t.prompt_tokens,
                          decode_steps=t.output_tokens, seed=0)
    server.params = None                    # the program's own init
    server.params = program_params(ctx.config, seed, arch)
    if (server.prefill_dispatch, server.decode_dispatch) != ("jax",
                                                             "pallas"):
        raise RuntimeError(
            f"attention dispatches to prefill={server.prefill_dispatch} "
            f"decode={server.decode_dispatch}; the cell runs the blockwise "
            "scan and the latent decode kernel")
    return server, t


# the functions of a round, as the dense serve cells run them
serve_round, sample = serve.serve_round, serve.sample


def routed(cache) -> list:
    """The cache's counters of routed copies, one leaf a MoE segment."""
    return [layer["routed"] for seg in cache["segments"]
            for layer in seg.values() if "routed" in layer]


@jax.jit
def _add_copies(total, counters):
    return total + sum(jnp.sum(c) for c in counters)


def reference(cfg: dict, seed: int, prompts: np.ndarray, served: np.ndarray,
              rows: int = 1, fp8: bool = False):
    """The reference's logits at every served position (the float8
    control's with ``fp8``)."""
    tokens = np.concatenate([prompts, served[:, :-1]], 1)
    return mla_moe.logits(cfg, seed, tokens, prompts.shape[1] - 1, fp8=fp8,
                          rows=rows)


class Gaps(float):
    """``check.gap``'s number, the widest gap between the reference's best
    logit and the picked token's, carrying the mean gap over every
    position. The widest alone parts the program from the float8 control
    by less than three times: five layers gather little float8 rounding,
    and the program's widest gaps come from an expert choice that rounding
    flipped at one position. Over every position the control strays far
    more often."""

    mean: float

    @classmethod
    def of(cls, ref, picked) -> "Gaps":
        got = jnp.take_along_axis(ref, jnp.asarray(picked)[..., None],
                                  -1)[..., 0]
        g = ref.max(-1) - got
        out = cls(float(jnp.max(g)))
        out.mean = float(jnp.mean(g))
        return out

    def __repr__(self) -> str:
        return f"{float(self)!r} (mean {self.mean!r})"


def served_gap(cfg: dict, seed: int, prompts: np.ndarray,
               served: np.ndarray, rows: int = 1) -> Gaps:
    """``check.served_gap`` against this reference, with the mean gap."""
    return Gaps.of(reference(cfg, seed, prompts, served, rows), served)


def run(ctx) -> Run:
    server, traffic = build_server(ctx, ctx.seed)
    steps: list = []
    # set-up: every call of a round once, and the counters' sum
    serve_round(ctx, server, traffic, 0, steps, max_tokens=3)
    copies = _add_copies(jnp.zeros((), jnp.int32), routed(server.cache))
    copies = jnp.zeros_like(copies).block_until_ready()
    steps.clear()
    gc.collect()
    gc.freeze()

    t0 = ctx.window_start()
    deadline = t0 + ctx.seconds
    rounds = []
    while not rounds or time.perf_counter() < deadline:
        rounds.append(serve_round(ctx, server, traffic, len(rounds), steps))
        copies = _add_copies(copies, routed(server.cache))
    t1 = rounds[-1]["times"][-1]
    ctx.window_end()
    ctx.read_memory_peak()
    gc.unfreeze()
    copies = int(copies)
    print(serve.host_report(rounds, steps) + f"; {ctx.compiles} programs "
          f"built in the window; {copies} routed copies on held experts",
          file=sys.stderr)

    server.params = server.cache = server.toks = server.out = None
    del server
    gc.collect()
    chk = ctx.workload["check"]
    prompts, served = sample(rounds, traffic, ctx.seed, chk["requests"])
    gap = served_gap(ctx.config, ctx.seed, prompts, served,
                     rows=chk.get("rows", 1))
    return Run(
        e2e=serve.window_metrics(rounds, steps, t0, t1, traffic.clients),
        attempted=traffic.clients * len(rounds), failed=0,
        checks={"served_logit_gap": {"value": float(gap),
                                     "limit": chk["served_logit_gap"]},
                "served_logit_gap_mean": {
                    "value": gap.mean,
                    "limit": chk["served_logit_gap_mean"]}},
        steps=steps, info={"dims": W.dims(ctx.config), "copies": copies})
