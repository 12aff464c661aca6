"""Bring-up smoke run on one TPU chip: the tuner and the InternLM2-1.8B
serve path, once, through the entry points a user calls.

    python3 chip_smoke.py

Phases, in order; any failure exits non-zero and prints no result:

1. device  - requires ``jax.devices()[0].platform == "tpu"``; nothing falls
             back to the CPU or to Pallas interpret mode.
2. kernels - compiles and runs flash prefill, flash-decode (``num_splits``
             1 and 2, both combines), gemm and the Matern-GP posterior with
             ``interpret=False`` at InternLM2-1.8B's widths; each compiled
             program must hold a ``tpu_custom_call``, and each output must
             match its pure-JAX reference within the tolerance of its dtype.
3. tune    - ``run_kernel_tuning`` on a flash and a decode cell at those
             shapes, the BO surrogate on the Pallas GP backend, journaled
             into a fresh store; each cell's default and best configs must
             measure finite.
4. serve   - ``repro.launch.serve`` at full width (B=8, prompt 2048, 32
             decode steps) with ``--store <that store> --kernels``; prefill
             and every decode step must dispatch to Pallas, and the logits
             of prefill and every decode step must match the pure-JAX step
             functions on the same parameters.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Timings printed on the way are smoke prints, not metrics.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "internlm2-1.8b"
BATCH, PROMPT, DECODE_STEPS = 8, 2048, 32
TUNE_BUDGET, TUNE_INIT = 6, 3

#: max |got - want| / max(1, max |want|) allowed per dtype. bf16 carries 8
#: mantissa bits (eps 2^-8 ~ 3.9e-3); the kernels and references round at
#: different points, so allow a few eps. f32 kernels on the MXU are
#: compared with references at "highest" matmul precision.
KERNEL_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
#: the GP posterior mean is amplified by ||L^-1||·||w|| (GP kernel
#: matrices are ill-conditioned): bounded by a fraction of its range
GP_MEAN_TOL = 3e-2
#: serve logits: the bf16 model's two attention paths are each compared
#: with a float32 run of the same weights. The Pallas path must be no
#: further from it than LOGITS_NOISE times the pure-JAX bf16 path's own
#: distance (bf16 rounding through 24 layers, measured in the same run),
#: and the two paths no further apart than the triangle inequality allows
LOGITS_NOISE = 1.5
LOGITS_FLOOR = 1e-3
REF_ROWS = 2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values in output")
    return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))


def require_custom_call(text: str, what: str) -> None:
    check("tpu_custom_call" in text,
          f"{what}: no tpu_custom_call in the program — the Pallas kernel "
          "did not compile for the chip")


# -- phase 1 -------------------------------------------------------------------


def phase_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {d.platform!r}; this smoke "
          "run needs the chip and has no CPU or interpret-mode fallback")
    print(f"[device] platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -- phase 2 -------------------------------------------------------------------


def run_compiled(what: str, fn, *args):
    """Compile ``fn`` for the chip, require a Pallas custom call in the
    compiled program, and run it."""
    compiled = jax.jit(fn).lower(*args).compile()
    require_custom_call(compiled.as_text(), what)
    return jax.block_until_ready(compiled(*args))


def report(what: str, err: float, tol: float) -> None:
    print(f"[kernels] {what}: max err {err:.3e} (tol {tol:g})", flush=True)
    check(err <= tol, f"{what}: max err {err:.3e} exceeds {tol:g}")


def phase_kernels(*, H: int, KV: int, hd: int, S: int, B: int,
                  cache: int) -> None:
    from repro.core.gp_fast import IncrementalGP
    from repro.kernels import ops, ref
    from repro.models.attention import _decode_attention
    bf16 = jnp.bfloat16
    tol = KERNEL_TOL["bfloat16"]
    rng = np.random.default_rng(0)
    highest = jax.default_matmul_precision("highest")

    # flash prefill, one sequence at the model's head count
    q, k, v = (jnp.asarray(rng.normal(size=(1, S, H, hd)), bf16)
               for _ in range(3))
    got = run_compiled("flash", lambda q, k, v: ops.flash_attention(
        q, k, v, interpret=False), q, k, v)
    with highest:
        want = ref.attention(q, k, v, causal=True)
    report(f"flash B1 S{S} H{H} hd{hd} bf16", rel_err(got, want), tol)

    # flash-decode over a cache filled to ``cur`` (later slots empty)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), bf16)
    kc, vc = (jnp.asarray(rng.normal(size=(B, KV, cache, hd)), bf16)
              for _ in range(2))
    cur = cache - 7
    pos = np.where(np.arange(cache) <= cur, np.arange(cache), -1)
    cache_pos = jnp.asarray(np.broadcast_to(pos, (B, cache)).copy(),
                            jnp.int32)
    cur_pos = jnp.full((B,), cur, jnp.int32)
    with highest:
        want = _decode_attention(q, kc, vc, cache_pos=cache_pos,
                                 cur_pos=cur_pos, window=None,
                                 scale=1.0 / np.sqrt(hd))
    for ns in (1, 2):
        for combine in ("jax", "kernel"):
            what = (f"decode B{B} cache{cache} H{H} KV{KV} hd{hd} bf16 "
                    f"splits={ns} combine={combine}")
            got = run_compiled(what, lambda q, k, v, p, c: ops.decode_attention(
                q, k, v, p, c, block_kv=512, num_splits=ns, combine=combine,
                interpret=False), q, kc, vc, cache_pos, cur_pos)
            report(what, rel_err(got, want), tol)

    # gemm at the model width
    a, b = (jnp.asarray(rng.normal(size=(2048, 2048)), bf16)
            for _ in range(2))
    got = run_compiled("gemm", lambda a, b: ops.gemm(a, b, interpret=False),
                       a, b)
    with highest:
        want = ref.gemm(a, b)
    report("gemm 2048 bf16", rel_err(got, want), tol)

    # the tuner's own posterior over a 4096-candidate panel
    N, T, d = 4096, 128, 15
    Xc = rng.random((N, d)).astype(np.float32)
    g = IncrementalGP(Xc, max_obs=64, kernel="matern32", ell=2.0)
    for _ in range(37):
        g.add(Xc[rng.integers(N)], float(rng.normal(10, 2)))
    x_obs, vinv, w, mask, _, _ = ops.gp_inputs_from_incremental(g, pad_T=T)
    gp_args = [jnp.asarray(x) for x in (Xc, x_obs, vinv, w, mask)]
    mean, var = run_compiled("matern_gp", lambda *a: ops.gp_posterior(
        *a, ell=2.0, interpret=False), *gp_args)
    with highest:
        m_ref, v_ref = ref.gp_posterior(*gp_args[:4], 2.0)
    report(f"matern_gp N{N} T{T} d{d} f32 variance", rel_err(var, v_ref),
           KERNEL_TOL["float32"])
    m_ref = np.asarray(m_ref)
    span = float(m_ref.max() - m_ref.min()) + 1e-9
    report(f"matern_gp N{N} T{T} d{d} f32 mean (of its range)",
           float(np.abs(np.asarray(mean) - m_ref).max()) / span, GP_MEAN_TOL)


# -- phase 3 -------------------------------------------------------------------


def tune_cell(cell, store) -> None:
    from repro.kernels.tuning import KernelObjective, run_kernel_tuning
    obj = KernelObjective(cell, reps=3)
    t0 = time.perf_counter()
    res = run_kernel_tuning(cell, store, budget=TUNE_BUDGET, init=TUNE_INIT,
                            gp_backend="pallas", objective=obj)
    default_s = obj.eval_config(cell.default)
    dt = time.perf_counter() - t0
    best_cfg = (cell.space.config(res.best_idx)
                if res.best_idx is not None else None)
    print(f"[tune] {obj.name}: {res.unique_evals} evals in {dt:.1f} s; "
          f"default {cell.default} {default_s * 1e3:.4f} ms, best {best_cfg} "
          f"{res.best_value * 1e3:.4f} ms", flush=True)
    print(f"[tune] {obj.name}: {len(obj.errors)} configs invalid at run "
          "time", flush=True)
    for idx, msg in sorted(obj.errors.items()):
        print(f"[tune]   {cell.space.config(idx)}: {msg}", flush=True)
    check(bool(np.isfinite(default_s)),
          f"{obj.name}: the default config {cell.default} is invalid: "
          f"{obj.errors.get(cell.space.index_of(cell.default), '?')}")
    check(bool(np.isfinite(res.best_value)),
          f"{obj.name}: no valid config measured")


def phase_tune(store_dir: str, *, H: int, KV: int, hd: int, S: int, B: int,
               cache: int) -> None:
    from repro.kernels.tuning import decode_cell, flash_cell
    from repro.store import TuningRecordStore
    store = TuningRecordStore(store_dir)
    try:
        tune_cell(flash_cell(1, S, H, hd, dtype=jnp.bfloat16,
                             interpret=False), store)
        tune_cell(decode_cell(B, cache, H, KV, hd, dtype=jnp.bfloat16,
                              interpret=False), store)
    finally:
        store.close()


# -- phase 4 -------------------------------------------------------------------


def run_steps(params, prefill, decode, batch, toks, pos0):
    """Prefill then one decode step per token of ``toks`` (teacher-forced
    from the served run), returning the logits of each step on the host."""
    logits, cache = prefill(params, batch)
    out = [np.asarray(logits, np.float32)]
    for i, t in enumerate(toks):
        logits, cache = decode(params, cache, {"tokens": t[:, None]},
                               jnp.asarray(pos0 + i, jnp.int32))
        out.append(np.asarray(logits, np.float32))
    return out


def max_err(runs, refs) -> float:
    """Largest ``rel_err`` over the steps of two runs."""
    return max(rel_err(a, b) for a, b in zip(runs, refs))


def phase_serve(store_dir: str, argv) -> None:
    from repro.launch import serve
    t0 = time.perf_counter()
    server = serve.main(["--store", store_dir, "--kernels", *argv])
    print(f"[serve] served in {time.perf_counter() - t0:.1f} s, set-up "
          "included", flush=True)
    steps = len(server.out) - 1
    check(server.prefill_dispatch == "pallas",
          "prefill attention did not dispatch to the Pallas flash kernel")
    check(server.decode_dispatch == "pallas" and steps > 0,
          "decode attention did not dispatch to the Pallas decode kernel")
    batch = server.input_batch()
    prefill_txt = server.prefill.lower(server.params, batch).as_text()
    require_custom_call(prefill_txt, "serve prefill step")
    decode_txt = server.decode.lower(
        server.params, server.cache, server.step_batch(server.toks),
        jnp.asarray(server.pos, jnp.int32)).as_text()
    require_custom_call(decode_txt, "serve decode step")
    print(f"[serve] dispatch: prefill Pallas flash, {steps}/{steps} decode "
          "steps Pallas flash-decode (tpu_custom_call in both programs)",
          flush=True)

    from repro.models.stepfn import make_decode_step, make_prefill_step
    from repro.parallel.sharding import ShardCtx
    toks = server.out[:-1]
    server.cache = None                    # free the served cache
    t0 = time.perf_counter()
    pallas = run_steps(server.params, server.prefill, server.decode, batch,
                       toks, server.prompt_len)
    server.pcfg = server.pcfg.replace(kernel=None)
    server.apply_config({})                # pure-JAX step functions
    pure = run_steps(server.params, server.prefill, server.decode, batch,
                     toks, server.prompt_len)
    # float32 reference: the same weights cast up, the pure-JAX path at
    # full matmul precision, on the first REF_ROWS rows (rows are
    # independent); the bf16 copy is dropped first to fit the chip
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), server.params)
    server.params = None
    px = ShardCtx(mesh=None, pcfg=server.pcfg)
    cfg32 = server.cfg.replace(dtype="float32")
    rows = slice(0, REF_ROWS)
    with jax.default_matmul_precision("highest"):
        ref = run_steps(
            params32,
            jax.jit(make_prefill_step(cfg32, px, cache_cap=server.cache_cap)),
            jax.jit(make_decode_step(cfg32, px)),
            {k: v[rows] for k, v in batch.items()}, [t[rows] for t in toks],
            server.prompt_len)
    print(f"[serve] parity runs (compiles included) "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    e_pallas = max_err([x[rows] for x in pallas], ref)
    e_pure = max_err([x[rows] for x in pure], ref)
    e_pair = max_err(pallas, pure)
    print(f"[serve] logits over prefill + {len(toks)} decode steps, max err "
          f"vs float32 (rows 0-{REF_ROWS - 1}): Pallas bf16 {e_pallas:.3e}, "
          f"pure-JAX bf16 {e_pure:.3e}; Pallas vs pure-JAX (all rows) "
          f"{e_pair:.3e}", flush=True)
    tol = LOGITS_NOISE * e_pure + LOGITS_FLOOR
    check(e_pallas <= tol,
          f"Pallas logits are {e_pallas:.3e} from float32, beyond the bf16 "
          f"pure-JAX path's {e_pure:.3e} (tol {tol:.3e})")
    tol = (1 + LOGITS_NOISE) * e_pure + LOGITS_FLOOR
    check(e_pair <= tol,
          f"Pallas and pure-JAX logits differ by {e_pair:.3e} (tol {tol:.3e})")


def main() -> int:
    from repro.configs.registry import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t_start = time.perf_counter()
    device = phase_device()
    cfg = get_arch(ARCH)
    shape = dict(H=cfg.num_heads, KV=cfg.num_kv_heads,
                 hd=cfg.resolved_head_dim, S=PROMPT, B=BATCH,
                 cache=PROMPT + DECODE_STEPS)
    t0 = time.perf_counter()
    phase_kernels(**shape)
    print(f"[kernels] done in {time.perf_counter() - t0:.1f} s", flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as store:
        t0 = time.perf_counter()
        phase_tune(store, **shape)
        print(f"[tune] done in {time.perf_counter() - t0:.1f} s", flush=True)
        phase_serve(store, ["--arch", ARCH, "--batch", str(BATCH),
                            "--prompt-len", str(PROMPT),
                            "--decode-steps", str(DECODE_STEPS)])
    print(f"[chip_smoke] all phases passed in "
          f"{time.perf_counter() - t_start:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
