"""In-process Pallas kernel autotuning cells (DESIGN.md §14).

This is the source paper's literal problem — tune GPU *kernel* parameters
(thread-block/tile shapes) with BO against measured runtimes — brought
in-process and re-parameterized for TPU: the tunable cells are the repo's
own Pallas kernels (flash_attention ``block_q``/``block_kv``, gemm
``block_m/n/k``, matern_gp ``block_n``), the objective is real kernel step
time (interpret-mode timing off-TPU — the validation path — real device
timing on TPU), and VMEM overflow / tile misalignment are the paper's
invalid configurations: journaled as NaN records, never fed to the
surrogate, never raised as exceptions.

Everything reuses the existing machinery unchanged: a ``KernelCell`` is an
``Objective`` over a ``SearchSpace``, runs journal into the
``TuningRecordStore`` under ``kernel[name×shape×device]`` fingerprints
(so warm-start, resume, and the durable retune queue all apply), and
serving resolves tuned block configs from the same store it resolves
sharding configs from (``best_kernel_config`` → ``KernelConfig`` →
``DecodeServer.apply_kernel_config``). The matern_gp cell closes the
self-hosting loop: its tuned ``block_n`` feeds the tuner's own §III-G
exhaustive-prediction hot loop (``IncrementalGP(backend="pallas")``).
"""
from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.objectives import Objective
from repro.core.searchspace import SearchSpace
from repro.kernels import ops
from repro.launch.roofline import VMEM_BYTES
from repro.parallel.sharding import ParallelConfig
from repro.store.resolve import apply_kernel_config

KERNEL_NAMES = ("gemm", "flash", "decode", "gp")


def device_kind() -> str:
    """Device context kernel timings are keyed under: the chip generation
    as JAX names it (``"TPU v5 lite"``; ``"cpu"`` off-TPU), so a record
    tuned on one generation — or in interpret mode — never resolves for
    another."""
    return jax.devices()[0].device_kind


def kernel_cell_objective(kernel: str, shape_sig: str,
                          device: Optional[str] = None) -> str:
    """Objective id of one kernel-tuning cell, mirroring the sharding cells'
    ``dryrun[arch×shape×mesh]`` convention: ``kernel[name×shape×device]``."""
    return f"kernel[{kernel}×{shape_sig}×{device or device_kind()}]"


@dataclass
class KernelCell:
    """One tunable kernel at one problem shape on one device.

    ``run(cfg)`` executes the kernel under a block config and returns the
    output (callers block on it); ``valid(cfg, vmem_bytes)`` is the static
    TPU resource model (VMEM capacity + alignment). ``default`` is the
    kernel's built-in block config — the thing tuning must beat.
    """

    kernel: str
    shape_sig: str
    space: SearchSpace
    run: Callable[[Dict[str, Any]], Any]
    valid: Callable[[Dict[str, Any], int], bool]
    default: Dict[str, Any]
    meta: Dict[str, Any] = field(default_factory=dict)

    def objective_id(self, device: Optional[str] = None) -> str:
        return kernel_cell_objective(self.kernel, self.shape_sig, device)


# -- cell factories ----------------------------------------------------------


def gemm_cell(M: int = 512, N: int = 512, K: int = 512,
              dtype=jnp.float32, interpret: Optional[bool] = None,
              seed: int = 0) -> KernelCell:
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(M, K)), dtype)
    b = jnp.asarray(rng.normal(size=(K, N)), dtype)
    dtype_bytes = jnp.dtype(dtype).itemsize

    def run(cfg):
        return ops.gemm(a, b, block_m=cfg["block_m"], block_n=cfg["block_n"],
                        block_k=cfg["block_k"], interpret=interpret)

    def valid(cfg, vmem_bytes):
        aligned = (M % cfg["block_m"] == 0 and N % cfg["block_n"] == 0
                   and K % cfg["block_k"] == 0)
        return aligned and ops.gemm_valid(cfg, dtype_bytes, vmem_bytes)

    return KernelCell(
        kernel="gemm", shape_sig=f"{M}x{N}x{K}",
        space=ops.gemm_config_space(M, N, K), run=run, valid=valid,
        default={"block_m": 256, "block_n": 256, "block_k": 256},
        meta={"M": M, "N": N, "K": K, "dtype_bytes": dtype_bytes})


def flash_cell(B: int = 1, S: int = 1024, H: int = 4, hd: int = 64,
               dtype=jnp.float32, causal: bool = True,
               interpret: Optional[bool] = None, seed: int = 0) -> KernelCell:
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
               for _ in range(3))
    dtype_bytes = jnp.dtype(dtype).itemsize

    def run(cfg):
        return ops.flash_attention(q, k, v, block_q=cfg["block_q"],
                                   block_kv=cfg["block_kv"], causal=causal,
                                   interpret=interpret)

    def valid(cfg, vmem_bytes):
        aligned = S % cfg["block_q"] == 0 and S % cfg["block_kv"] == 0
        return aligned and ops.flash_valid(cfg, hd, dtype_bytes, vmem_bytes)

    return KernelCell(
        kernel="flash", shape_sig=f"B{B}_S{S}_H{H}_hd{hd}",
        space=ops.flash_config_space(S), run=run, valid=valid,
        default={"block_q": 512, "block_kv": 512},
        meta={"B": B, "S": S, "H": H, "hd": hd, "dtype_bytes": dtype_bytes})


def decode_cell(B: int = 4, S: int = 2048, H: int = 8, KV: int = 2,
                hd: int = 64, fill: float = 0.95, window: Optional[int] = None,
                dtype=jnp.float32, interpret: Optional[bool] = None,
                seed: int = 0) -> KernelCell:
    """The per-token serve hot path: split-KV flash decode over a KV cache
    of capacity ``S`` at ``fill`` occupancy (empty slots carry
    ``cache_pos = -1`` exactly like a live server's cache). Shape key =
    cache capacity × heads × KV heads × head dim × batch."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, KV, S, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, KV, S, hd)), dtype)
    cur = max(int(S * fill) - 1, 0)
    pos = np.where(np.arange(S) <= cur, np.arange(S), -1)
    cache_pos = jnp.asarray(np.broadcast_to(pos, (B, S)).copy(), jnp.int32)
    cur_pos = jnp.full((B,), cur, jnp.int32)
    dtype_bytes = jnp.dtype(dtype).itemsize
    G = H // max(KV, 1)

    def run(cfg):
        return ops.decode_attention(q, k, v, cache_pos, cur_pos,
                                    window=window,
                                    block_kv=cfg["block_kv"],
                                    num_splits=cfg["num_splits"],
                                    combine=cfg["combine"],
                                    interpret=interpret)

    def valid(cfg, vmem_bytes):
        # padding tiles any capacity, but splits past the cache are pure
        # combine overhead — the alignment face of the resource model
        covered = cfg["block_kv"] * (cfg["num_splits"] - 1) < S
        return covered and ops.decode_valid(cfg, KV, G, hd, dtype_bytes,
                                            vmem_bytes)

    return KernelCell(
        kernel="decode", shape_sig=f"B{B}_S{S}_H{H}_KV{KV}_hd{hd}",
        space=ops.decode_config_space(S), run=run, valid=valid,
        default={"block_kv": 512, "num_splits": 1, "combine": "jax"},
        meta={"B": B, "S": S, "H": H, "KV": KV, "hd": hd, "fill": fill,
              "window": window, "dtype_bytes": dtype_bytes})


def gp_cell(N: int = 4096, T: int = 128, d: int = 15, t_obs: int = 37,
            nu: str = "matern32", ell: float = 2.0,
            interpret: Optional[bool] = None, seed: int = 0) -> KernelCell:
    """The self-hosting cell: the tuner's own §III-G exhaustive-prediction
    hot loop, as a tuning target. Inputs are a real packaged IncrementalGP
    state (t_obs observations over an N-candidate panel)."""
    from repro.core.gp_fast import IncrementalGP
    rng = np.random.default_rng(seed)
    Xc = rng.random((N, d)).astype(np.float32)
    g = IncrementalGP(Xc, max_obs=max(t_obs, 1), kernel=nu, ell=ell)
    for _ in range(t_obs):
        g.add(Xc[rng.integers(N)], float(rng.normal(10, 2)))
    x_obs, vinv, w, mask, _, _ = ops.gp_inputs_from_incremental(g, pad_T=T)
    args = (jnp.asarray(Xc), jnp.asarray(x_obs), jnp.asarray(vinv),
            jnp.asarray(w), jnp.asarray(mask))

    def run(cfg):
        return ops.gp_posterior(*args, ell=ell, nu=nu,
                                block_n=cfg["block_n"], interpret=interpret)

    def valid(cfg, vmem_bytes):
        return (N % cfg["block_n"] == 0
                and ops.gp_valid(cfg, T, d, vmem_bytes))

    return KernelCell(
        kernel="gp", shape_sig=f"N{N}_T{T}_d{d}",
        space=ops.gp_config_space(N), run=run, valid=valid,
        default={"block_n": 512},
        meta={"N": N, "T": T, "d": d, "t_obs": t_obs, "nu": nu})


def default_cells(smoke: bool = False) -> Tuple[KernelCell, ...]:
    """The standard four-cell matrix ``benchmarks/kernel_tuning.py`` runs.
    Smoke shapes keep interpret-mode timing tractable on CPU CI."""
    if smoke:
        return (gemm_cell(256, 256, 256), flash_cell(1, 512, 2, 64),
                decode_cell(1, 512, 4, 2, 64), gp_cell(2048, 128, 15))
    return (gemm_cell(512, 512, 512), flash_cell(1, 1024, 4, 64),
            decode_cell(4, 2048, 8, 2, 64), gp_cell(4096, 128, 15))


# -- the measured objective --------------------------------------------------


class KernelObjective(Objective):
    """Measured kernel step time (seconds, lower better).

    The TPU resource model is checked FIRST: a config that would overflow
    VMEM or mis-tile the problem returns NaN — the paper's invalid
    configuration, journaled by the runner, skipped by the surrogate —
    instead of crashing the run. A config that passes the model but fails
    at execution (compiler rejection, interpret-mode assert) is likewise
    caught and journaled invalid; its message stays in ``errors`` (space
    index -> first line) so a caller can tell a refused tile from a broken
    kernel. ``vmem_bytes`` is injectable so tests can shrink the budget and
    pin the invalid path without 16 MiB tiles.
    """

    def __init__(self, cell: KernelCell, *, reps: int = 3, warmup: int = 1,
                 vmem_bytes: int = VMEM_BYTES,
                 device: Optional[str] = None, verbose: bool = False):
        self.cell = cell
        self.space = cell.space
        self.name = cell.objective_id(device)
        self.reps = max(int(reps), 1)
        self.warmup = max(int(warmup), 1)
        self.vmem_bytes = int(vmem_bytes)
        self.verbose = verbose
        self.errors: Dict[int, str] = {}

    def __call__(self, idx: int) -> float:
        cfg = self.space.config(int(idx))
        if not self.cell.valid(cfg, self.vmem_bytes):
            if self.verbose:
                print(f"  [kernel-tune] {cfg} -> INVALID (resource model)")
            return math.nan
        try:
            for _ in range(self.warmup):          # compile + cache warm
                jax.block_until_ready(self.cell.run(cfg))
            best = math.inf
            for _ in range(self.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(self.cell.run(cfg))
                best = min(best, time.perf_counter() - t0)
        except Exception as e:                    # runtime-discovered invalid
            lines = str(e).strip().splitlines()
            self.errors[int(idx)] = (f"{type(e).__name__}: "
                                     f"{lines[0] if lines else ''}")
            if self.verbose:
                print(f"  [kernel-tune] {cfg} -> INVALID "
                      f"({self.errors[int(idx)]})")
            return math.nan
        if self.verbose:
            print(f"  [kernel-tune] {cfg} -> {best*1e3:.3f} ms")
        return best


# -- store integration -------------------------------------------------------


def run_kernel_tuning(cell: KernelCell, store=None, *, budget: int = 12,
                      init: int = 4, seed: int = 0, reps: int = 3,
                      vmem_bytes: int = VMEM_BYTES, warm_start: bool = True,
                      device: Optional[str] = None, verbose: bool = False,
                      gp_backend: str = "numpy",
                      objective: Optional[KernelObjective] = None):
    """Tune one kernel cell with the standard BO engine, journaling into the
    shared store under the cell's ``kernel[...]`` fingerprint. Returns the
    engine's TuneResult. ``gp_backend`` is the BO surrogate's posterior
    backend (``"pallas"`` scores candidates with the matern_gp kernel);
    pass a prebuilt ``objective`` to read its ``errors`` after the run."""
    from repro.core.runner import run_strategy
    from repro.core.strategies.bo import BOConfig, BOStrategy
    obj = objective or KernelObjective(cell, reps=reps, vmem_bytes=vmem_bytes,
                                       device=device, verbose=verbose)
    n_init = min(init, budget)
    strat = BOStrategy(BOConfig(initial_samples=n_init,
                                gp_backend=gp_backend))
    run_id = f"kernel_{cell.kernel}_{cell.shape_sig}-s{seed}"
    return run_strategy(strat, obj, budget=budget, seed=seed, store=store,
                        run_id=run_id, warm_start=warm_start)


def best_kernel_config(store, kernel: str, shape_sig: Optional[str] = None,
                       device: Optional[str] = None
                       ) -> Optional[Tuple[Dict[str, Any], float]]:
    """Best stored (block config, measured step time) for a kernel cell.

    ``shape_sig=None`` relaxes to any tuned shape of this kernel on this
    device (minimum over cells) — how a server picks blocks for a problem
    shape that was never tuned exactly. Returns None on a cold store."""
    from repro.store.records import TuningRecordStore
    if isinstance(store, str):
        if not os.path.exists(store):
            return None
        store = TuningRecordStore(store, lazy=True)
    device = device or device_kind()
    want = (kernel_cell_objective(kernel, shape_sig, device)
            if shape_sig is not None else None)
    prefix = f"kernel[{kernel}×"
    suffix = f"×{device}]"
    best: Optional[Tuple[Dict[str, Any], float]] = None
    for digest, desc in store.fingerprints().items():
        obj = desc.objective
        if want is not None:
            if obj != want:
                continue
        elif not (obj.startswith(prefix) and obj.endswith(suffix)):
            continue
        hit = store.best_config(digest)
        if hit is not None and (best is None or hit[1] < best[1]):
            best = hit
    return best


def tuned_gp_block_n(store, N: Optional[int] = None,
                     device: Optional[str] = None,
                     default: int = 512) -> int:
    """Tuned matern_gp ``block_n`` for the self-hosted GP backend; falls
    back to the kernel default on a cold store. ``N`` (candidate count)
    only filters to block sizes that could tile it."""
    hit = best_kernel_config(store, "gp", None, device)
    if hit is None:
        return default
    bn = int(hit[0]["block_n"])
    if N is not None and bn > N:
        return default
    return bn


def kernel_config_from_store(store, *, S: int, hd: int,
                             device: Optional[str] = None):
    """Resolve a ``KernelConfig`` for a serving cell's prefill problem
    (sequence length ``S``, head dim ``hd``) from stored flash-cell tunings.
    None when the store has no usable record (caller keeps pure-JAX)."""
    hit = best_kernel_config(store, "flash", None, device)
    if hit is None:
        return None
    blocks = {k: int(hit[0][k]) for k in ("block_q", "block_kv")}
    if any(S % b for b in blocks.values()):
        return None             # tuned blocks don't tile this server's S
    if not ops.flash_valid(blocks, hd):
        return None
    return apply_kernel_config(ParallelConfig(), blocks).kernel


def decode_kernel_config_from_store(store, *, cache_cap: int, H: int, KV: int,
                                    hd: int, device: Optional[str] = None,
                                    base=None):
    """Resolve tuned decode blocks for a serving cell's cache shape from
    stored decode-cell tunings, overlaid on ``base`` (so a server can carry
    both tuned flash AND tuned decode blocks in one ``KernelConfig``).
    None when no stored record is usable for this cache (caller keeps the
    pure-JAX decode path)."""
    hit = best_kernel_config(store, "decode", None, device)
    if hit is None:
        return None
    cfg = hit[0]
    bkv, ns = int(cfg["block_kv"]), int(cfg["num_splits"])
    if bkv * (ns - 1) >= cache_cap:
        return None             # tuned splits overhang this server's cache
    G = H // max(KV, 1)
    if not ops.decode_valid({"block_kv": bkv}, KV, G, hd):
        return None
    return apply_kernel_config(ParallelConfig(kernel=base), cfg).kernel
