"""TPU-native Rudra protocols as SPMD programs (DESIGN.md §2).

Inside one SPMD program there is no true asynchrony, so the n-softsync
protocol is realised as **round-based softsync**: one training round = n
sequential PS update events.  All λ learners (data-axis shard groups)
compute gradients against the round-start weights θ(i); event j folds the
mean gradient of group j with staleness σ_j = j, so σ ∈ {0..n−1} and
⟨σ⟩ = (n−1)/2.  The LR policy sees the *measured* ⟨σ⟩.

Two engines:

* ``sequential`` — faithful semantics.  ``lax.scan`` over the n groups: each
  iteration computes that group's gradient (backward over B/n samples) and
  applies the update immediately.  Total FLOPs equal one pass over the global
  batch, but the collective pattern is n gradient all-reduces per round —
  exactly the PS-traffic penalty the paper measures for λ-softsync (§5.2).

* ``fused`` — beyond-paper optimization.  Because the optimizer update is
  linear in the gradients (SGD exactly; momentum after folding the geometric
  velocity coefficients), the n sequential events collapse into ONE
  staleness-weighted gradient combination, computable as a single backward
  pass over a per-sample-weighted loss ⇒ one all-reduce per round, the same
  collective cost as hardsync.  For momentum the round applies the exact
  affine fold (repro.optim.sequential_fold): θ carries the folded
  velocity-decay term v0_coef and v advances by (m^n, Σ m^{n−1−i}) — exact
  whenever the n group-mean gradients coincide, a documented round-level
  approximation otherwise (see EXPERIMENTS.md §Perf for the convergence
  check).

Every applyUpdate routes through ``repro.optim`` (DESIGN.md §3) — this
module owns only the round structure and per-event LR schedule.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.config import ModelConfig, RunConfig
from repro.core.lr_policies import hardsync_lr, softsync_lr

# (params, opt, metrics) -> (params, opt, metrics), after each update event
PostEvent = Callable[..., Tuple]


# ---------------------------------------------------------------------------
# per-event learning rates for one round
# ---------------------------------------------------------------------------
def round_event_lrs(run: RunConfig, n: int) -> np.ndarray:
    """LR for each of the n update events in a round.

    staleness_inverse: uniform α₀/⟨σ⟩ with the engine's measured ⟨σ⟩=(n−1)/2.
    per_gradient (footnote 3): event j gets α₀/max(1, σ_j) with σ_j = j.
    """
    if run.lr_policy == "per_gradient":
        return np.array([run.base_lr / max(1.0, float(j)) for j in range(n)])
    if run.lr_policy == "staleness_inverse":
        sigma = max(1.0, (n - 1) / 2.0)
        return np.full((n,), run.base_lr / sigma)
    if run.lr_policy == "sqrt_scale":
        return np.full((n,), hardsync_lr(run))
    return np.full((n,), run.base_lr)


def fused_coefficients(run: RunConfig, n: int) -> Tuple[np.ndarray, float]:
    """Fold n sequential momentum updates into one combination.

    Sequential: v_j = m·v_{j-1} + g_j ;  θ ← θ − lr_j·v_j   (j = 0..n−1)
    ⇒ θ_n = θ_0 − Σ_i (Σ_{j≥i} lr_j m^{j−i}) g_i − (Σ_j lr_j m^{j+1}) v_0
    Returns (per-group gradient coefficients c_i for the θ update,
    velocity-carry coefficient Σ_j lr_j m^{j+1}) — the fold algebra lives in
    ``repro.optim.sequential_fold``.  For plain SGD (m = 0) the coefficients
    are exactly the per-event LRs.
    """
    fold = _round_fold(run, n)
    return np.asarray(fold.theta_coef), fold.v0_coef


def _round_fold(run: RunConfig, n: int) -> optim.RoundFold:
    lrs = round_event_lrs(run, n)
    m = run.momentum if run.optimizer == "momentum" else 0.0
    return optim.sequential_fold(lrs, m)


# ---------------------------------------------------------------------------
# optimizer state (all applyUpdate math lives in repro.optim)
# ---------------------------------------------------------------------------
def init_opt_state(run: RunConfig, params) -> dict:
    return optim.init_state(optim.spec_from_run(run), params)


# ---------------------------------------------------------------------------
# gradient computation with optional micro-batch accumulation
# ---------------------------------------------------------------------------
def split_interleaved(x: jax.Array, n: int) -> jax.Array:
    """(B, …) → (n, B/n, …) where part j holds samples j, j+n, j+2n, ….
    The batch axis's data sharding stays on the per-part axis, so a scan
    over the parts runs on an unsharded leading axis and every part spans
    every data shard (a contiguous split would put the sharding on the
    scanned axis, which JAX rejects under explicit mesh axes)."""
    return jnp.swapaxes(x.reshape((x.shape[0] // n, n) + x.shape[1:]), 0, 1)


def fold_metrics(metrics: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Metrics stacked on a leading axis (micro-batches, events) → one:
    the counts under ``"counts"`` summed, the ``"maxima"`` maxed, every
    other metric the last one's."""
    rule = {"counts": lambda m: jnp.sum(m, axis=0),
            "maxima": lambda m: jnp.max(m, axis=0)}
    return {k: jax.tree.map(rule.get(k, lambda m: m[-1]), m)
            for k, m in metrics.items()}


def grad_with_accum(loss_fn: Callable, params, batch, num_microbatches: int,
                    sample_weights=None):
    """value_and_grad with gradient accumulation over micro-batches.
    Returns (loss, metrics, grads).  Gradients accumulate in fp32."""
    def total_loss(p, b, w):
        if w is None:
            return loss_fn(p, b)
        return loss_fn(p, b, sample_weights=w)

    if num_microbatches <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            total_loss, has_aux=True)(params, batch, sample_weights)
        return loss, metrics, grads

    mb = jax.tree.map(lambda x: split_interleaved(x, num_microbatches),
                      batch)
    wb = (None if sample_weights is None else
          split_interleaved(sample_weights, num_microbatches))

    def acc_body(carry, inp):
        g_acc, l_acc = carry
        if sample_weights is None:
            b, w = inp, None
        else:
            b, w = inp
        (loss, metrics), g = jax.value_and_grad(
            total_loss, has_aux=True)(params, b, w)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(a.dtype), g_acc, g)
        return (g_acc, l_acc + loss), metrics

    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    xs = mb if sample_weights is None else (mb, wb)
    (g_sum, loss_sum), metrics = jax.lax.scan(
        acc_body, (zeros, jnp.float32(0.0)), xs)
    grads = jax.tree.map(lambda g: g / num_microbatches, g_sum)
    return loss_sum / num_microbatches, fold_metrics(metrics), grads


# ---------------------------------------------------------------------------
# train steps
# ---------------------------------------------------------------------------
def _no_post_event(params, opt, metrics):
    return params, opt, metrics


def make_hardsync_step(run: RunConfig, loss_fn: Callable,
                       post_event: Optional[PostEvent] = None):
    """Standard data-parallel step: Δθ = mean over the global batch ≡ Eq. 3.
    LR follows the paper's hardsync scaling when lr_policy = sqrt_scale."""
    lr = hardsync_lr(run) if run.lr_policy == "sqrt_scale" else run.base_lr
    spec = optim.spec_from_run(run)
    post_event = post_event or _no_post_event

    def step(params, opt, batch):
        loss, metrics, grads = grad_with_accum(
            loss_fn, params, batch, run.num_microbatches)
        params_new, opt_new = optim.apply_single(spec, params, opt, grads, lr)
        return post_event(params_new, opt_new, metrics)

    return step


def make_softsync_step(run: RunConfig, loss_fn: Callable,
                       engine: str = "sequential",
                       post_event: Optional[PostEvent] = None):
    """Round-based n-softsync (DESIGN.md §2).  One call = one round = n
    update events.  The global batch is split into n logical learner groups
    along the batch axis, interleaved (group j holds samples j, j+n, …; see
    :func:`split_interleaved`).  ``post_event(params, opt, metrics)`` runs
    after each event's optimizer update with that event's metrics (the
    routing-bias rule, ``models.moe.routing_bias_update``); the round's
    metrics fold its events' (:func:`fold_metrics`).
    """
    n = max(1, run.n_softsync)
    if run.protocol == "async":
        n = run.n_learners
    post_event = post_event or _no_post_event

    if engine == "fused":
        return _make_fused_softsync_step(run, loss_fn, n, post_event)

    lrs = jnp.asarray(round_event_lrs(run, n), jnp.float32)
    spec = optim.spec_from_run(run)

    def step(params, opt, batch):
        grouped = jax.tree.map(lambda x: split_interleaved(x, n), batch)
        theta0 = params      # round-start weights: all groups' grads use θ(i)

        def event(carry, inp):
            params, opt, loss_acc = carry
            group_batch, lr = inp
            loss, metrics, grads = grad_with_accum(
                loss_fn, theta0, group_batch, run.num_microbatches)
            params, opt = optim.apply_single(spec, params, opt, grads, lr)
            params, opt, metrics = post_event(params, opt, metrics)
            return (params, opt, loss_acc + loss), metrics

        (params, opt, loss_sum), metrics = jax.lax.scan(
            event, (params, opt, jnp.float32(0.0)), (grouped, lrs))
        metrics = fold_metrics(metrics)
        metrics["loss_round_mean"] = loss_sum / n
        return params, opt, metrics

    return step


def _make_fused_softsync_step(run: RunConfig, loss_fn: Callable, n: int,
                              post_event: PostEvent):
    """Fused engine: one backward pass over a per-sample-weighted loss.

    The per-group θ-update coefficients c_i (fused_coefficients) become
    per-sample loss weights w_s = n·c_{g(s)} / Σc  scaled so that the single
    mean gradient equals Σ_i c_i · mean_{s∈i}(g_s) / (Σ_i c_i).  SGD /
    adagrad / adamw then do one apply with lr = Σ_i c_i; momentum applies
    the exact affine round fold — θ gets the v0_coef velocity carry and v
    advances by (m^n, Σ m^{n−1−i}) — so round-to-round momentum matches the
    sequential engine whenever the group-mean gradients coincide.
    """
    fold = _round_fold(run, n)
    coef = np.asarray(fold.theta_coef)
    total = float(coef.sum())
    group_w = jnp.asarray(coef / coef.mean(), jnp.float32)   # mean-1 weights
    spec = optim.spec_from_run(run)

    def step(params, opt, batch):
        B = jax.tree.leaves(batch)[0].shape[0]
        per_sample_w = jnp.tile(group_w, B // n)      # sample s: group s % n
        loss, metrics, grads = grad_with_accum(
            loss_fn, params, batch, run.num_microbatches,
            sample_weights=per_sample_w)
        # grads is the weighted MEAN (1/n)Σ_i (c_i/c̄)·mean_i = Σ_i c_i·mean_i/Σc,
        # so applying with total weight Σ_i c_i reproduces θ₀ − Σ_i c_i·mean_i.
        if run.optimizer == "momentum":
            params, opt = optim.apply_round_folded(spec, params, opt, grads,
                                                   fold)
        else:
            params, opt = optim.apply_single(spec, params, opt, grads, total)
        # the round is one update: the per-event rule runs once, on the
        # round's whole batch
        return post_event(params, opt, metrics)

    return step


def make_train_step(run: RunConfig, loss_fn: Callable,
                    engine: str = "sequential",
                    post_event: Optional[PostEvent] = None):
    if run.protocol == "hardsync":
        return make_hardsync_step(run, loss_fn, post_event)
    return make_softsync_step(run, loss_fn, engine=engine,
                              post_event=post_event)
