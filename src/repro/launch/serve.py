"""Serving launcher: batched prefill + decode over a smoke config.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --batch 4 --prompt-len 32 --decode-steps 16

Distribution/performance knobs come from the tuning-record store when one is
given (``--store``): the best prior tuning result for this (arch, shape,
mesh) cell overrides the built-in defaults, so serving inherits every past
tuning run's work. No record -> defaults, loudly.

``--online`` closes the loop (DESIGN.md §12): the server tail-follows the
store between decode steps and atomically swaps in a strictly better config
when one lands (no restart — params and KV cache survive, only the step
functions are re-derived), writes measured per-step latencies back as
``context="prod"`` records that warm-start future tuning runs, and submits
a durable re-tune request into the store when observed latency drifts off
the stored roofline prediction by ``--drift-factor`` (statistic selected by
``--drift-stat``) — serviced by a separate ``repro.launch.retune`` daemon
even after this server dies. ``--swap-margin`` adds hot-reload hysteresis:
improvements smaller than the re-jit cost are not worth a swap.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch, smoke_config
from repro.kernels.cache import CompiledKernelCache, config_key
from repro.launch.compile_cache import enable_compile_cache
from repro.models.params import init_params
from repro.models.stepfn import make_decode_step, make_prefill_step
from repro.parallel.sharding import ParallelConfig, ShardCtx
from repro.store import (DriftMonitor, HotConfigSource, OnlineServeLoop,
                         ProdRecorder, apply_kernel_config,
                         apply_sharding_config, best_sharding_config)


def resolve_pcfg(pcfg: ParallelConfig, store: str, arch: str, shape: str,
                 mesh: str = "single") -> ParallelConfig:
    """Best stored tuning config for this serving cell, else defaults."""
    hit = best_sharding_config(store, arch, shape, mesh=mesh)
    if hit is None:
        print(f"[serve] no tuning record for ({arch}, {shape}, {mesh}) in "
              f"{store} — using built-in defaults")
        return pcfg
    cfg, step_time = hit
    print(f"[serve] tuned config from store ({step_time:.3f}s roofline): "
          f"{cfg}")
    return apply_sharding_config(pcfg, cfg)


class DecodeServer:
    """Data plane of one serving process: params, KV cache, decode state,
    and jitted step functions derived from the current ParallelConfig.

    ``apply_config`` is the hot-reload point the online loop calls between
    decode batches: it overlays a stored tuning config and re-derives the
    step functions — params, cache, and generated tokens all survive, so a
    swap never costs a restart (only the first step's re-jit).
    ``apply_kernel_config`` is the same hot-reload point for tuned Pallas
    block configs (DESIGN.md §14); derived step-fn bundles are memoized in a
    ``CompiledKernelCache`` keyed by the tunable fields, so swapping BACK to
    a previously-deployed config is a cache hit — no re-jit at all.

    ``decode`` is the jitted decode step; it leaves the cache it is given
    intact. ``decode_step`` runs ``decode_in_place``, the same step with the
    cache donated: the new cache is the old one, updated where the step
    wrote its token, and the old arrays are deleted. Each cache is passed
    to it once. A step put in place of ``decode`` (a wrapper, a test
    double) runs as given, with no donation, since it may keep its input.

    Each step runs inside a profiler span, ``serve.prefill`` or
    ``serve.decode``, that carries ``batch_id`` and ``pos``; inside it one
    span per host call (``.inputs``, ``.dispatch``, ``.sample``, ``.sync``)
    names what the host did while the device waited. Outside a profiler
    trace each span costs one check.
    """

    def __init__(self, cfg, pcfg: ParallelConfig, *, batch: int,
                 prompt_len: int, decode_steps: int, seed: int = 0):
        self.cfg = cfg
        self.pcfg = pcfg
        self.prompt_len = prompt_len
        self.cache_cap = prompt_len + decode_steps
        self.key = jax.random.PRNGKey(seed)
        self.params = init_params(cfg, self.key)
        self.batch_size = batch
        self.cache = None
        self.toks = None
        self.out = []
        self.pos = 0
        #: ``prefill_batch`` calls so far: the id of the batch in flight
        self.batch_id = 0
        self.swaps = 0
        self.kernel_swaps = 0
        self.kernel_cache = CompiledKernelCache()
        self._derive()

    def _stepfn_key(self):
        """Hashable identity of the derived step functions: every tunable
        ParallelConfig field a store record can overlay, plus the kernel
        block config. Rule tables are excluded — serving never hot-swaps
        them (they change the mesh, which IS a restart)."""
        p = self.pcfg
        kc = p.kernel
        kernel = (() if kc is None else
                  ("flash", kc.use_flash, kc.flash_block_q,
                   kc.flash_block_kv, "decode", kc.use_decode,
                   kc.decode_block_kv, kc.decode_num_splits,
                   kc.decode_combine, kc.interpret))
        return (p.remat, p.microbatches, p.attn_block_q, p.attn_block_kv,
                p.attn_q_chunks, p.capacity_factor, p.logits_chunk,
                p.opt_moment_dtype, p.scan_layers, p.flash_threshold,
                p.mlstm_chunk, p.mlstm_bf16_streams, p.moe_combine, kernel)

    def _derive(self) -> None:
        def build():
            px = ShardCtx(mesh=None, pcfg=self.pcfg)
            prefill = jax.jit(make_prefill_step(self.cfg, px,
                                                cache_cap=self.cache_cap))
            step = make_decode_step(self.cfg, px)
            return prefill, jax.jit(step), jax.jit(step, donate_argnums=(1,))
        self.prefill, self.decode, self.decode_in_place = \
            self.kernel_cache.get(self._stepfn_key(), build)
        self._derived_decode = self.decode

    def apply_config(self, cfg_dict) -> None:
        self.pcfg = apply_sharding_config(self.pcfg, cfg_dict)
        self._derive()
        self.swaps += 1

    def apply_kernel_config(self, cfg_dict) -> None:
        """Hot-swap tuned Pallas kernel blocks between decode steps: params,
        KV cache, and generated tokens survive; only the step-fn bundle is
        re-derived (or re-used from the compiled-kernel cache)."""
        self.pcfg = apply_kernel_config(self.pcfg, cfg_dict)
        self._derive()
        self.kernel_swaps += 1

    @property
    def prefill_dispatch(self) -> str:
        """``"pallas"`` when prefill attention runs the Pallas flash kernel
        at this server's prompt length, ``"jax"`` otherwise."""
        from repro.models.layers import _pallas_flash_ok
        hd = self.cfg.resolved_head_dim
        return ("pallas" if _pallas_flash_ok(self.prompt_len, hd, hd, None,
                                             self.pcfg.kernel)
                else "jax")

    @property
    def decode_dispatch(self) -> str:
        """Which implementation the next decode step's attention runs on —
        ``"pallas"`` when the flash-decode dispatch gate is open, ``"jax"``
        otherwise. Surfaced per-step by ``ServeStats``."""
        from repro.models.layers import _pallas_decode_ok
        hd = self.cfg.resolved_head_dim
        return ("pallas" if _pallas_decode_ok(hd, hd, self.pcfg.kernel)
                else "jax")

    def _decode_fn(self):
        """The decode step the server runs: ``decode_in_place`` unless
        ``decode`` was replaced."""
        return (self.decode_in_place if self.decode is self._derived_decode
                else self.decode)

    def input_batch(self):
        cfg, B = self.cfg, self.batch_size
        if cfg.frontend == "embeddings":
            batch = {"frame_embeddings": jax.random.normal(
                self.key, (B, self.prompt_len, cfg.d_model),
                jnp.dtype(cfg.dtype))}
            if cfg.cross_attention:
                batch["cond"] = jax.random.normal(
                    self.key, (B, cfg.cross_seq, cfg.d_model),
                    jnp.dtype(cfg.dtype))
        else:
            batch = {"tokens": jax.random.randint(
                self.key, (B, self.prompt_len), 0, cfg.vocab_size)}
        return batch

    def step_batch(self, toks):
        """Decode-step input for the tokens ``toks`` (B,) just generated."""
        if self.cfg.frontend == "embeddings":
            emb = self.params["lm_head"]["w"][:, toks].T[:, None, :] \
                .astype(jnp.dtype(self.cfg.dtype))
            return {"frame_embeddings": emb}
        return {"tokens": toks[:, None]}

    def warmup(self, batch) -> float:
        """Compile the prefill and decode steps by running each once on
        throwaway outputs (held state is untouched), so the timed steps
        that follow run compiled code. Returns seconds."""
        t0 = time.perf_counter()
        logits, cache = self.prefill(self.params, batch)
        out = self._decode_fn()(self.params, cache,
                                self.step_batch(jnp.argmax(logits, -1)),
                                jnp.asarray(self.prompt_len, jnp.int32))
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    def prefill_batch(self, batch) -> float:
        """Prefill a new batch in place of the held state; returns its
        seconds on ``time.perf_counter``. Starts batch ``batch_id + 1``:
        the spans of this batch's steps carry that id."""
        self.batch_id += 1
        with jax.profiler.TraceAnnotation("serve.prefill",
                                          batch_id=self.batch_id, pos=0):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve.prefill.dispatch"):
                self.cache = None           # the held batch's, freed first
                logits, self.cache = self.prefill(self.params, batch)
            with jax.profiler.TraceAnnotation("serve.prefill.sync"):
                logits.block_until_ready()
            with jax.profiler.TraceAnnotation("serve.prefill.sample"):
                self.toks = jnp.argmax(logits, -1)
            self.logits_shape = logits.shape
            self.out = [self.toks]
            self.pos = self.prompt_len
            dt = time.perf_counter() - t0
        return dt

    def decode_step(self) -> float:
        """One decode step over the held state; returns its seconds on
        ``time.perf_counter``. The step's span carries the batch and the
        position it writes."""
        with jax.profiler.TraceAnnotation("serve.decode",
                                          batch_id=self.batch_id,
                                          pos=self.pos):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve.decode.inputs"):
                pos = jnp.asarray(self.pos, jnp.int32)
                inputs = self.step_batch(self.toks)
            with jax.profiler.TraceAnnotation("serve.decode.dispatch"):
                logits, self.cache = self._decode_fn()(self.params,
                                                       self.cache, inputs,
                                                       pos)
            with jax.profiler.TraceAnnotation("serve.decode.sample"):
                toks = jnp.argmax(logits, -1)
            with jax.profiler.TraceAnnotation("serve.decode.sync"):
                toks.block_until_ready()
            self.toks = toks
            self.out.append(toks)
            self.pos += 1
            dt = time.perf_counter() - t0
        return dt


def main(argv=None) -> DecodeServer:
    """Serve one batch from the command line (``argv``, default
    ``sys.argv``); returns the server, its state after the last step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store", default=None,
                    help="tuning-record store (dir or .jsonl) to resolve "
                         "the serving config from")
    ap.add_argument("--tuned-shape", default="decode_32k",
                    help="dry-run shape whose tuning records configure "
                         "this server")
    ap.add_argument("--online", action="store_true",
                    help="tail the store between decode steps (hot config "
                         "reload), write prod-latency records back, flag "
                         "drift re-tunes (requires --store)")
    ap.add_argument("--drift-factor", type=float, default=1.5,
                    help="re-tune when windowed prod latency is off the "
                         "stored roofline by this factor either way")
    ap.add_argument("--drift-stat", default="median",
                    choices=["median", "p50", "p99", "mean"],
                    help="window statistic the drift alarm keys off (p99 "
                         "tracks the tail users feel)")
    ap.add_argument("--kernels", action="store_true",
                    help="resolve tuned Pallas kernel block configs from "
                         "--store and dispatch through them (prefill flash "
                         "attention + per-token flash decode); in --online "
                         "mode also tail the store for kernel hot-swaps")
    ap.add_argument("--swap-margin", type=float, default=0.0,
                    help="hot-reload hysteresis: a same-tier better record "
                         "must improve the roofline step time by MORE than "
                         "this many seconds to be worth the re-jit")
    ap.add_argument("--poll-every", type=int, default=4,
                    help="decode steps between store polls in --online mode")
    args = ap.parse_args(argv)
    if args.online and not args.store:
        ap.error("--online requires --store")
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    pcfg = ParallelConfig(flash_threshold=1 << 30, logits_chunk=0)
    source = None
    if args.online:
        # one code path for startup resolution AND hot reload: the first
        # refresh replays the store; later refreshes see only new records
        source = HotConfigSource(args.store, args.arch, args.tuned_shape,
                                 swap_margin=args.swap_margin)
        hit = source.refresh()
        if hit is None:
            print(f"[serve] no tuning record for ({args.arch}, "
                  f"{args.tuned_shape}, single) in {args.store} — using "
                  "built-in defaults")
        else:
            print(f"[serve] tuned config from store ({hit[1]:.3f}s "
                  f"roofline): {hit[0]}")
            pcfg = apply_sharding_config(pcfg, hit[0])
    elif args.store:
        pcfg = resolve_pcfg(pcfg, args.store, args.arch, args.tuned_shape)

    kernel_sources = []
    if args.kernels and args.store:
        from repro.kernels import tuning as ktuning
        hd = cfg.resolved_head_dim
        cache_cap = args.prompt_len + args.decode_steps
        kv_heads = cfg.num_kv_heads or cfg.num_heads
        kcfg = ktuning.kernel_config_from_store(args.store,
                                                S=args.prompt_len, hd=hd)
        if kcfg is None:
            print("[serve] no usable flash (prefill) kernel record in "
                  "store — pure-JAX prefill attention")
        else:
            print(f"[serve] tuned flash (prefill) blocks from store: {kcfg}")
            pcfg = pcfg.replace(kernel=kcfg)
        dcfg = ktuning.decode_kernel_config_from_store(
            args.store, cache_cap=cache_cap, H=cfg.num_heads, KV=kv_heads,
            hd=hd, base=pcfg.kernel)
        if dcfg is None:
            print("[serve] no usable decode kernel record in store — "
                  "pure-JAX decode attention")
        else:
            print(f"[serve] tuned decode blocks from store: "
                  f"block_kv={dcfg.decode_block_kv} "
                  f"num_splits={dcfg.decode_num_splits} "
                  f"combine={dcfg.decode_combine}")
            pcfg = pcfg.replace(kernel=dcfg)
        if args.online:
            def _cell(mk, *a):
                # a shape the kernel's config space cannot tile at all
                # (e.g. prompt shorter than every flash block) has no cell
                # to watch — skip the source, keep serving
                try:
                    return mk(*a)
                except ValueError as e:
                    print(f"[serve] no tunable kernel cell for this shape "
                          f"({e}) — skipping hot-swap source")
                    return None

            fcell = _cell(ktuning.flash_cell, args.batch, args.prompt_len,
                          cfg.num_heads, hd)
            dcell = _cell(ktuning.decode_cell, args.batch, cache_cap,
                          cfg.num_heads, kv_heads, hd)
            for cell in (fcell, dcell):
                if cell is None:
                    continue
                src = HotConfigSource.for_kernel_cell(
                    args.store, cell, swap_margin=args.swap_margin)
                src.refresh()
                kernel_sources.append(src)

    t0 = time.perf_counter()
    server = DecodeServer(cfg, pcfg, batch=args.batch,
                          prompt_len=args.prompt_len,
                          decode_steps=args.decode_steps, seed=args.seed)
    jax.block_until_ready(server.params)
    dt_init = time.perf_counter() - t0
    batch = server.input_batch()
    dt_warm = server.warmup(batch)
    print(f"[serve] set-up: init {dt_init:.1f} s, compile + warm-up "
          f"{dt_warm:.1f} s")
    dt_prefill = server.prefill_batch(batch)
    print(f"[serve] prefill B={args.batch} S={args.prompt_len}: "
          f"{dt_prefill*1e3:.0f} ms, logits {server.logits_shape}, "
          f"attention {server.prefill_dispatch}")

    if args.online:
        from repro.store.queue import TuningJobQueue
        recorder = ProdRecorder(args.store, args.arch, args.tuned_shape)
        # prefill latency is telemetry, not a decode-step observation: it
        # is in different units than the tuned step time — journaled
        # configless so it never transfers
        recorder.record(None, dt_prefill, phase="prefill")
        monitor = DriftMonitor(source.current[1] if source.current else None,
                               factor=args.drift_factor,
                               stat=args.drift_stat)
        # durable: a drift request survives this server's death and is
        # claimed (exactly once, fleet-wide) by any number of separate
        # `python -m repro.launch.retune` daemons.
        # The queue appends through the recorder's store handle — one live
        # segment per pid, the shape compaction's "sealed" rule assumes
        queue = TuningJobQueue(args.store, appender=recorder.store)
        loop = OnlineServeLoop(server, source, recorder=recorder,
                               monitor=monitor, retune_queue=queue,
                               cell_key=source.objective_id,
                               poll_every=args.poll_every,
                               kernel_sources=kernel_sources)
        t0 = time.perf_counter()
        stats = loop.run(args.decode_steps)
        dt = time.perf_counter() - t0
        print(f"[serve] decoded {args.decode_steps} steps x B={args.batch}: "
              f"{dt*1e3:.0f} ms ({dt/args.decode_steps*1e3:.1f} ms/step)")
        for step, cfg_new, value in stats.swaps:
            print(f"[serve] hot-reload at step {step}: {value:.3f}s "
                  f"roofline {cfg_new}")
        for step, cfg_new, value in stats.kernel_swaps:
            print(f"[serve] kernel hot-swap at step {step}: "
                  f"{value*1e3:.2f} ms step {cfg_new} "
                  f"(cache {server.kernel_cache.stats()})")
        print(f"[serve] online: {recorder.count} prod records, "
              f"{len(stats.swaps)} hot reloads, "
              f"{stats.retunes_requested} re-tune requests submitted")
        print(f"[serve] decode dispatch: {stats.decode_steps_pallas} steps "
              f"Pallas flash-decode, {stats.decode_steps_jax} pure-JAX")
        for tk in queue.open_tickets():
            print(f"[serve] drift: observed {tk.observed*1e3:.1f} ms/step "
                  f"vs {tk.predicted*1e3:.1f} ms predicted — durable "
                  f"re-tune request {tk.id} open (service with "
                  f"`python -m repro.launch.retune --store {args.store}`)")
    else:
        t0 = time.perf_counter()
        n_pallas = 0
        for _ in range(args.decode_steps):
            server.decode_step()
            n_pallas += server.decode_dispatch == "pallas"
        dt = time.perf_counter() - t0
        print(f"[serve] decoded {args.decode_steps} steps x B={args.batch}: "
              f"{dt*1e3:.0f} ms ({dt/args.decode_steps*1e3:.1f} ms/step)")
        print(f"[serve] decode dispatch: {n_pallas} steps Pallas "
              f"flash-decode, {args.decode_steps - n_pallas} pure-JAX")
    print("[serve] sample tokens:", [int(t[0]) for t in server.out][:12])
    return server


if __name__ == "__main__":
    main()
