"""Training loop: protocol-aware trainer over the distributed engines.

This is the single-process/jit path used by examples and tests (the
launcher in ``repro.launch.train`` adds the mesh/sharding).  One "round" of
softsync = n PS update events (DESIGN.md §2); metrics include the running
staleness bookkeeping so the (σ, μ, λ) tradeoff driver can read it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.config import ModelConfig, RunConfig
from repro.core.distributed import make_train_step
from repro.data.pipeline import PrefetchIterator, make_batch_fn
from repro.models import init_model, model_loss
from repro.models import moe
from repro.optim import init_state, spec_from_run


@dataclasses.dataclass
class TrainResult:
    params: object
    opt_state: object
    history: List[Dict]
    steps: int
    wallclock: float


def train(cfg: ModelConfig, run: RunConfig, *, steps: int,
          batch: int, seq: int, engine: str = "sequential",
          eval_every: int = 0,
          eval_fn: Optional[Callable] = None,
          params=None,
          warmstart_steps: int = 0,
          log: Optional[Callable[[str], None]] = None) -> TrainResult:
    """Train ``steps`` rounds of the configured protocol on synthetic data.

    ``warmstart_steps`` implements the paper's §5.5 strategy: initialize a
    softsync run from hardsync training (the paper warm-starts ImageNet
    1-softsync from 1 hardsync epoch to stabilize AdaGrad)."""
    import dataclasses as _dc
    key = jax.random.PRNGKey(run.seed)
    if params is None:
        params = init_model(cfg, key)
    opt = init_state(spec_from_run(run), params)

    def loss_fn(p, b, sample_weights=None):
        return model_loss(cfg, run, p, b, sample_weights=sample_weights)

    if warmstart_steps and run.protocol != "hardsync":
        warm_run = _dc.replace(run, protocol="hardsync",
                               lr_policy="sqrt_scale")
        warm = train(cfg, warm_run, steps=warmstart_steps, batch=batch,
                     seq=seq, eval_every=0, params=params, log=log)
        params = warm.params
        if log:
            log(f"warm-start: {warmstart_steps} hardsync rounds done")

    step_fn = jax.jit(make_train_step(
        run, loss_fn, engine=engine,
        post_event=moe.routing_bias_update(cfg)))
    batch_fn = make_batch_fn(cfg, batch, seq, seed=run.seed)
    it = iter(PrefetchIterator(batch_fn, steps))

    history: List[Dict] = []
    counts = None         # the rounds' counter metrics, summed on the device
    t0 = time.perf_counter()
    for step, b in enumerate(it):
        params, opt, metrics = step_fn(params, opt, b)
        counts = telemetry.add_step_counts(counts, metrics)
        if eval_every and (step + 1) % eval_every == 0:
            telemetry.record_step_counts(counts)
            counts = None
            entry = {"step": step + 1,
                     "loss": float(metrics["loss"]),
                     "ce": float(metrics["ce"])}
            if eval_fn is not None:
                entry.update(eval_fn(params))
            history.append(entry)
            if log:
                log(f"step {step+1}: " + " ".join(
                    f"{k}={v:.4f}" for k, v in entry.items() if k != "step"))
    # the steps are enqueued asynchronously: the clock stops when the
    # device has finished the last one
    params, opt = jax.block_until_ready((params, opt))
    wall = time.perf_counter() - t0
    telemetry.record_step_counts(counts)
    return TrainResult(params, opt, history, steps, wall)
