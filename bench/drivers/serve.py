"""Serve cells: closed-loop rounds of requests through ``DecodeServer``.

Set-up builds the server as ``repro.launch.serve.main`` does, resolves its
Pallas blocks through the tuning-record store (seeded with the cell's
blocks), swaps in weights made from the seed, and runs one round's calls
once so that every program the window uses is built. The window then sends
round after round: each round's prompts go to ``prefill_batch``, and
``decode_step`` runs until every request has its output tokens. Each token
is pulled to the host, where a user would read it. No round starts after
``--seconds``, and the round in progress then runs to its end, so that the
window holds whole rounds only: where the window ends inside a round does
not move its rate.

Once the window has closed, a sample of the finished requests, drawn from
the seed, is run through the plain reference (``bench.check``).
"""
from __future__ import annotations

import gc
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, weights
from bench.harness import Run
from bench.reference import dense
from bench.traffic import ClosedLoop

#: a host-clock time spans at least this long (the clock and the sync
#: around it are good to about half a millisecond)
MIN_SPAN_S = 0.25


def arch_config(name: str, cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.arch import ArchConfig
    dense.check_supported(cfg)
    n = weights.dims(cfg)
    return ArchConfig(name=name, family="dense", num_layers=n["L"],
                      d_model=n["d"], num_heads=n["H"], num_kv_heads=n["KV"],
                      d_ff=n["F"], vocab_size=n["V"], head_dim=n["hd"],
                      mlp_act="swiglu", rope_theta=float(cfg["rope_theta"]),
                      norm_eps=dense.norm_eps(cfg), dtype=cfg["torch_dtype"],
                      tie_embeddings=False)


def program_params(cfg: dict, seed: int, arch) -> dict:
    """The seed's weights in the program's parameter tree, made in one
    jitted call in the served type; checked against the program's own
    description of its tree."""
    from repro.models.params import abstract_params
    w = weights.stacked(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    params = {
        "embed": {"table": w["embed"]},
        "segments": [{"0:attn": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: w[k] for k in ("wg", "wu", "wd")}}}],
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["head"]},
    }
    want = abstract_params(arch)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree is not the dense "
                           f"decoder this driver fills: {want}")
    return params


def seed_store(path: str, arch, kernels: dict, B: int, S: int,
               cache_cap: int) -> None:
    """One tuning record per kernel at the cell's blocks, under the
    objective ids the serve path resolves from."""
    from repro.kernels import ops
    from repro.kernels.tuning import device_kind, kernel_cell_objective
    from repro.store import SpaceFingerprint, TuningRecord, TuningRecordStore
    H, KV, hd = arch.num_heads, arch.num_kv_heads, arch.resolved_head_dim
    cells = (("flash", f"B{B}_S{S}_H{H}_hd{hd}", ops.flash_config_space(S),
              kernels["flash"]),
             ("decode", f"B{B}_S{cache_cap}_H{H}_KV{KV}_hd{hd}",
              ops.decode_config_space(cache_cap), kernels["decode"]))
    store = TuningRecordStore(path)
    try:
        for kernel, sig, space, cfg in cells:
            idx = space.index_of(cfg)
            if idx is None:
                raise ValueError(f"{kernel} blocks {cfg} are not in the "
                                 f"kernel's space at this shape")
            fp = SpaceFingerprint.of(space, objective=kernel_cell_objective(
                kernel, sig, device_kind()))
            store.append(TuningRecord(fp="", run="bench", seq=0,
                                      key=str(idx), idx=idx, value=1e-3,
                                      config=dict(cfg)), fingerprint=fp)
    finally:
        store.close()


def resolve_pcfg(store: str, arch, S: int, cache_cap: int):
    """The ``ParallelConfig`` ``serve.main --store --kernels`` builds."""
    from repro.kernels import tuning as ktuning
    from repro.parallel.sharding import ParallelConfig
    pcfg = ParallelConfig(flash_threshold=1 << 30, logits_chunk=0)
    hd = arch.resolved_head_dim
    kcfg = ktuning.kernel_config_from_store(store, S=S, hd=hd)
    if kcfg is None:
        raise RuntimeError("the store gave no flash blocks for this cell")
    pcfg = pcfg.replace(kernel=kcfg)
    dcfg = ktuning.decode_kernel_config_from_store(
        store, cache_cap=cache_cap, H=arch.num_heads, KV=arch.num_kv_heads,
        hd=hd, base=pcfg.kernel)
    if dcfg is None:
        raise RuntimeError("the store gave no decode blocks for this cell")
    return pcfg.replace(kernel=dcfg)


def build_server(ctx, seed: int):
    """Set-up: the server with the seed's weights and Pallas dispatch."""
    from repro.launch.serve import DecodeServer
    t = ClosedLoop(ctx.traffic, ctx.config["vocab_size"], seed)
    arch = arch_config(ctx.config_name, ctx.config)
    cap = t.prompt_tokens + t.output_tokens
    with tempfile.TemporaryDirectory(prefix="bench_store_") as store:
        seed_store(store, arch, ctx.workload["kernels"], t.clients,
                   t.prompt_tokens, cap)
        pcfg = resolve_pcfg(store, arch, t.prompt_tokens, cap)
    server = DecodeServer(arch, pcfg, batch=t.clients,
                          prompt_len=t.prompt_tokens,
                          decode_steps=t.output_tokens, seed=0)
    server.params = None                    # the program's own init
    server.params = program_params(ctx.config, seed, arch)
    if server.prefill_dispatch != "pallas" or server.decode_dispatch != \
            "pallas":
        raise RuntimeError(
            f"attention dispatches to prefill={server.prefill_dispatch} "
            f"decode={server.decode_dispatch}; the cell's blocks name Pallas")
    return server, t


def serve_round(ctx, server, traffic: ClosedLoop, i: int, steps: list,
                max_tokens: int = 0) -> dict:
    """One round: prompts, prefill, then decode until every request has
    its tokens, or ``max_tokens`` are served."""
    with ctx.span("bench.prompts"):
        prompts = traffic.prompts(i).block_until_ready()
    B, P = traffic.clients, traffic.prompt_tokens
    t_sub = time.perf_counter()
    with ctx.span("bench.prefill"):
        server.prefill_batch({"tokens": prompts})
        toks = [np.asarray(server.toks)]
    t = time.perf_counter()
    steps.append({"kind": "prefill", "t0": t_sub, "t1": t, "B": B, "S": P})
    times = [t]
    limit = max_tokens or traffic.output_tokens
    while len(toks) < limit:
        live = server.pos + 1
        t0 = t
        with ctx.span("bench.decode"):
            server.decode_step()
            toks.append(np.asarray(server.toks))
        t = time.perf_counter()
        steps.append({"kind": "decode", "t0": t0, "t1": t, "B": B,
                      "live": live})
        times.append(t)
    return {"round": i, "t_sub": t_sub, "times": times,
            "tokens": np.stack(toks, 1),
            "done": len(toks) == traffic.output_tokens}


def window_metrics(rounds: list, steps: list, t0: float, t1: float,
                   clients: int) -> dict:
    """tokens/s over the whole window; the 95th percentile, over every
    request, of its time to first token; and the time per output token:
    all the window's decode time over its decode steps (each step gives
    every request one token), where that time spans ``MIN_SPAN_S`` or
    more."""
    tokens = sum(r["tokens"].size for r in rounds)
    ttft = np.repeat([r["times"][0] - r["t_sub"] for r in rounds], clients)
    decode = [s["t1"] - s["t0"] for s in steps if s["kind"] == "decode"]
    return {"tokens_per_s": tokens / (t1 - t0),
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "tpot_ms": (sum(decode) / len(decode) * 1e3
                        if sum(decode) >= MIN_SPAN_S else None)}


def host_report(rounds: list, steps: list) -> str:
    """How steady the host kept the window: each decode step's time and
    each round's time to first token, with the stalls among them."""
    dec = np.array([s["t1"] - s["t0"] for s in steps
                    if s["kind"] == "decode"])
    ttft = np.array([r["times"][0] - r["t_sub"] for r in rounds])
    out = f"window: {len(rounds)} rounds"
    if dec.size:
        med = float(np.median(dec))
        slow = dec[dec > 2 * med]
        out += (f"; {dec.size} decode steps, median {med * 1e3:.3f} ms, max "
                f"{dec.max() * 1e3:.3f} ms, {slow.size} over twice the "
                f"median ({float((slow - med).sum()):.3f} s beyond it)")
    return out + (f"; time to first token median "
                  f"{float(np.median(ttft)) * 1e3:.3f} ms, max "
                  f"{ttft.max() * 1e3:.3f} ms")


def sample(rounds: list, traffic: ClosedLoop, seed: int, k: int):
    """(prompts, served tokens) of ``k`` finished requests drawn from the
    seed; where no round finished, the requests of the longest round."""
    done = [r for r in rounds if r["done"]] or sorted(
        rounds, key=lambda r: -r["tokens"].shape[1])[:1]
    pool = [(r, row) for r in done for row in range(traffic.clients)]
    rng = np.random.default_rng(seed)
    pick = sorted(rng.choice(len(pool), size=min(k, len(pool)),
                             replace=False))
    prompts = np.stack([np.asarray(traffic.prompts(pool[j][0]["round"]))
                        [pool[j][1]] for j in pick])
    served = np.stack([pool[j][0]["tokens"][pool[j][1]] for j in pick])
    return prompts, served


def run(ctx) -> Run:
    server, traffic = build_server(ctx, ctx.seed)
    steps: list = []
    # set-up: every call of a round once (prefill, two decode steps, the
    # host pulls), so that nothing is built inside the window
    serve_round(ctx, server, traffic, 0, steps, max_tokens=3)
    steps.clear()
    # what set-up made lives on: the collector need not walk it in the window
    gc.collect()
    gc.freeze()

    t0 = ctx.window_start()
    deadline = t0 + ctx.seconds
    rounds = []
    while not rounds or time.perf_counter() < deadline:
        rounds.append(serve_round(ctx, server, traffic, len(rounds), steps))
    t1 = rounds[-1]["times"][-1]
    ctx.window_end()
    ctx.read_memory_peak()
    gc.unfreeze()
    print(host_report(rounds, steps), file=sys.stderr)

    server.params = server.cache = server.toks = server.out = None
    del server
    gc.collect()
    chk = ctx.workload["check"]
    prompts, served = sample(rounds, traffic, ctx.seed, chk["requests"])
    gap = check.served_gap(ctx.config, ctx.seed, prompts, served,
                           rows=chk.get("rows", 1))
    return Run(
        e2e=window_metrics(rounds, steps, t0, t1, traffic.clients),
        attempted=traffic.clients * len(rounds), failed=0,
        checks={"served_logit_gap": {"value": gap,
                                     "limit": chk["served_logit_gap"]}},
        steps=steps, info={"dims": weights.dims(ctx.config)})
