"""End-to-end training driver.

CPU-scale example (the real thing, shrunk):
  python -m repro.launch.train --arch llama3.1-8b --reduced --steps 200 \
      --use-case gpu-red

Runs the full stack: synthetic data pipeline -> pjit'd FSDP train step ->
AdamW -> atomic checkpoints -> watchdog -> Lit Silicon power-management
co-sim hook (detect+mitigate per paper §V).
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.1-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU container)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--use-case", default="",
                    choices=["", "gpu-red", "gpu-realloc", "cpu-slosh"],
                    help="enable the Lit Silicon power-management hook")
    ap.add_argument("--preset", default="mi300x", choices=["mi300x", "v5e"])
    ap.add_argument("--metrics-out", default="")
    return ap


def build_trainer(args, model_cfg=None, mesh=None):
    """The `Trainer` (and its hooks) that ``main`` runs for ``args``.

    ``model_cfg`` defaults to the ``--arch`` config (reduced with
    ``--reduced``); the Lit Silicon hook always simulates the full arch.
    ``mesh`` defaults to every device on the 'data' axis.
    """
    from repro.configs import (ParallelConfig, TrainConfig, get_config,
                               get_reduced_config)
    from repro.core.manager import ManagerConfig
    from repro.train.data import DataConfig
    from repro.train.train_loop import LitSiliconHook, Trainer, TrainerConfig

    if model_cfg is None:
        model_cfg = (get_reduced_config(args.arch) if args.reduced
                     else get_config(args.arch))
    tc = TrainerConfig(
        model=model_cfg,
        train=TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps,
                          checkpoint_every=args.checkpoint_every,
                          checkpoint_dir=args.checkpoint_dir),
        parallel=ParallelConfig(),
        data=DataConfig(global_batch=args.global_batch,
                        seq_len=args.seq_len),
    )
    hooks = []
    if args.use_case:
        hooks.append(LitSiliconHook(
            get_config(args.arch),       # sim runs the FULL arch workload
            ManagerConfig(use_case=args.use_case, sampling_period=2,
                          warmup=3, window_size=2),
            preset=args.preset))
    return Trainer(tc, mesh=mesh, hooks=hooks)


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    trainer = build_trainer(args)
    log = trainer.run(args.steps)
    print(f"step {log[-1]['step']}: loss {log[-1]['loss']:.4f} "
          f"(start {log[0]['loss']:.4f})")
    if args.use_case:
        h = trainer.hooks[0]
        caps = h.backend.get_power_caps()
        print(f"lit-silicon[{args.use_case}]: converged caps = "
              f"{np.round(caps, 0).tolist()}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(log, f, indent=1, sort_keys=True, allow_nan=False)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
