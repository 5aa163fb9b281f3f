"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --protocol softsync --n 4 --engine fused --steps 100 --batch 8 \
        --seq 128 --ckpt /tmp/run1

Training runs on the default device (a TPU chip where one is attached,
else the CPU).  The production meshes of ``repro.launch.mesh`` and the
sharding policy of ``repro.launch.sharding`` are exercised by the dry run
(``repro.launch.dryrun``), which compiles them on emulated CPU devices.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.config import RunConfig
from repro.configs import get_config, get_smoke
from repro.checkpoint.io import load_checkpoint, save_checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--protocol", default="softsync",
                    choices=["hardsync", "softsync", "async"])
    ap.add_argument("--n", type=int, default=4, dest="n_softsync")
    ap.add_argument("--learners", type=int, default=8)
    ap.add_argument("--engine", default="sequential",
                    choices=["sequential", "fused"])
    ap.add_argument("--lr-policy", default="staleness_inverse",
                    choices=["const", "staleness_inverse", "sqrt_scale",
                             "per_gradient"])
    ap.add_argument("--optimizer", default="momentum",
                    choices=["sgd", "momentum", "adagrad", "adamw"])
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only and args.protocol == "async":
        pass  # protocols are model-agnostic; nothing to special-case
    run = RunConfig(
        protocol=args.protocol, n_softsync=args.n_softsync,
        n_learners=args.learners,
        minibatch=max(1, args.batch // args.learners),
        base_lr=args.lr, lr_policy=args.lr_policy,
        optimizer=args.optimizer, num_microbatches=args.microbatches,
        seed=args.seed, attn_q_chunk=min(1024, args.seq),
        attn_kv_chunk=min(1024, args.seq))

    # report expected staleness for the chosen protocol (clock machinery)
    if run.protocol != "hardsync":
        from repro.experiments import ExperimentSpec
        from repro.experiments import run as run_experiment
        meas = run_experiment(ExperimentSpec(run=run, steps=200))
        print(f"protocol={run.protocol} n={run.n_softsync} "
              f"c={run.gradients_per_update} "
              f"expected<sigma>={meas.staleness['mean']:.2f} "
              f"lr={run.learning_rate():.5f}")

    t0 = time.time()
    res = train(cfg, run, steps=args.steps, batch=args.batch, seq=args.seq,
                engine=args.engine, eval_every=args.eval_every, log=print)
    print(f"done: {args.steps} rounds in {res.wallclock:.1f}s "
          f"({res.wallclock / args.steps * 1e3:.0f} ms/round)")
    if args.ckpt:
        path = os.path.join(args.ckpt, "checkpoint.npz")
        save_checkpoint(path, res.params, step=args.steps)
        with open(os.path.join(args.ckpt, "history.json"), "w") as f:
            json.dump(res.history, f, indent=1)
        print(f"checkpoint -> {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
