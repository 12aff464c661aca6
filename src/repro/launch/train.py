"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 200 --ckpt-dir /tmp/ckpt

--smoke uses the reduced same-family config (CPU-runnable); the full configs
are for real accelerators (and are exercised via the dry-run here). The
~100M example model lives in examples/train_lm.py.
"""
from __future__ import annotations

import argparse

from repro.configs.registry import get_arch, smoke_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.parallel.sharding import ParallelConfig
from repro.runtime.train import LoopConfig, TrainLoop, run_with_restarts


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a failure (demonstrates restart)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed)
    if cfg.frontend == "embeddings":
        raise SystemExit(f"{cfg.name} takes frontend embeddings; use "
                         "examples/train_lm.py for token-LM training demos")

    def make_loop(attempt: int) -> TrainLoop:
        lc = LoopConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                        ckpt_dir=args.ckpt_dir, seed=args.seed,
                        fail_at_step=args.fail_at_step if attempt == 0 else None,
                        peak_lr=args.peak_lr)
        return TrainLoop(cfg, data_cfg, lc)

    metrics = run_with_restarts(make_loop, max_restarts=args.max_restarts)
    print(f"[train] done: {len(metrics.losses)} steps this process, "
          f"final loss {metrics.losses[-1]:.4f}, "
          f"stragglers {metrics.straggler_events}, "
          f"restored_from={metrics.restored_from}")


if __name__ == "__main__":
    main()
