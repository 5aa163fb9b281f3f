"""The single source of truth for staleness-aware applyUpdate (DESIGN.md §3).

Every synchronization protocol in the paper — hardsync (Eq. 3), n-softsync
(Eq. 5), async (Eq. 4) — reduces at the parameter server to the same step:
a staleness-weighted combination of the c pending gradients folded into one
optimizer event,

    θ' = θ − α · Σ_i coef_i · G_i        (+ optimizer state update)

with the staleness-dependent LR modulation of Eq. 6 / footnote 3 deciding α
(scalar) or the per-gradient α_i (Zhang et al., "Staleness-aware Async-SGD",
2016).  This module defines that update rule ONCE:

* :class:`UpdateSpec`   — which optimizer + its hyperparameters.
* :func:`update_event`  — one optimizer event on plain fp32 arrays.  This
  exact function body is what the Pallas ``ps_update`` kernel executes per
  tile and what the pytree backends map over leaves — there is no second
  implementation of the math anywhere in the repo.
* :func:`init_state`    — optimizer state pytree (fp32 accumulators).
* :func:`sequential_fold` — the algebra that folds c *sequential* momentum
  events (per-gradient LRs) into one affine update, used by the fused
  softsync engine and by ``fused_coefficients``.

Two update modes (both supported by every backend, see ``backends.py``):

* ``combine``    — g = Σ_i coef_i·G_i, then ONE optimizer event with lr[0].
  This is the paper's Eq. 3/5 semantics (average, then apply).
* ``sequential`` — c optimizer events, event i applying gradient
  coef_i·G_i with its own lr_i.  This is the footnote-3 per-gradient
  modulation done right: momentum/adagrad state advances per event, fixing
  the seed bug where per-gradient LRs silently bypassed the optimizer.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

OPTIMIZERS = ("sgd", "momentum", "adagrad", "adamw")

# optimizers whose update is expressible as one fused Pallas kernel pass
# (adamw needs a scalar step counter — pytree backends only).
KERNEL_OPTIMIZERS = ("sgd", "momentum", "adagrad")


@dataclasses.dataclass(frozen=True)
class UpdateSpec:
    """Optimizer kind + hyperparameters.  Hashable → usable as a jit static."""

    optimizer: str = "sgd"
    momentum: float = 0.9
    eps: float = 1e-8
    beta1: float = 0.9
    beta2: float = 0.95
    weight_decay: float = 0.0

    def __post_init__(self):
        if self.optimizer not in OPTIMIZERS:
            raise ValueError(f"unknown optimizer {self.optimizer!r}")

    @property
    def state_keys(self) -> Tuple[str, ...]:
        return {"sgd": (), "momentum": ("velocity",), "adagrad": ("accum",),
                "adamw": ("mu", "nu", "count")}[self.optimizer]

    @property
    def kernel_supported(self) -> bool:
        return self.optimizer in KERNEL_OPTIMIZERS


def spec_from_run(run) -> UpdateSpec:
    """Build an UpdateSpec from a RunConfig (the repo-wide convention)."""
    return UpdateSpec(optimizer=run.optimizer, momentum=run.momentum,
                      weight_decay=run.weight_decay)


def needs_master(params) -> bool:
    """True when some weight is stored below float32 (bf16): its update
    then runs on a float32 master copy (``init_state``'s "master")."""
    return any(jnp.dtype(p.dtype) != jnp.float32
               for p in jax.tree.leaves(params))


def init_state(spec: UpdateSpec, params) -> dict:
    """Optimizer state pytree.  Accumulators are fp32 regardless of the
    parameter dtype (bf16 params train with fp32 velocity/variance).  Where
    a weight is stored below float32 the state also holds "master", a
    float32 copy of every weight: each event updates the master, and the
    stored weights are the master rounded to their dtype, so an update
    smaller than half a bf16 step accumulates instead of being lost."""
    f32 = lambda p: jnp.zeros(jnp.shape(p), jnp.float32)
    if spec.optimizer == "momentum":
        state = {"velocity": jax.tree.map(f32, params)}
    elif spec.optimizer == "adagrad":
        state = {"accum": jax.tree.map(f32, params)}
    elif spec.optimizer == "adamw":
        state = {"mu": jax.tree.map(f32, params),
                 "nu": jax.tree.map(f32, params),
                 "count": jnp.zeros((), jnp.int32)}
    else:
        state = {}
    if needs_master(params):
        # a copy also of float32 leaves: the step may donate both trees
        state["master"] = jax.tree.map(
            lambda p: jnp.array(p, jnp.float32, copy=True), params)
    return state


# ---------------------------------------------------------------------------
# THE applyUpdate rule.  One optimizer event on fp32 arrays.
# ---------------------------------------------------------------------------
def combine_terms(c: int, term):
    """ĝ = Σ_{i<c} term(i), summed left to right — the combine-mode
    contraction as c elementwise multiply-adds (``term(i)`` is coef_i·G_i;
    sequential mode passes each slot's term as a one-term sum).  The Pallas
    kernels and their jnp twins all phrase it through here, so they agree
    bitwise.  On a TPU it runs on the vector unit in exact fp32: Mosaic
    rejects the (c, D)·(c,) contraction, and an XLA matmul at default
    precision would round the gradients to bf16.

    The sum starts from ``0 − (−term(0))``, not the bare product: where a
    compiler contracts ``a·b + x`` into one fused multiply-add (XLA on the
    CPU does), each add then holds exactly one product to fuse.  An add of
    two bare products leaves it a choice, and the interpret-mode kernel and
    its twin choose differently (1 ulp apart)."""
    acc = 0.0 - (-term(0))
    for i in range(1, c):                                   # c is static
        acc = acc + term(i)
    return acc


def quantize(w, dtype):
    """fp32 weights ``w`` rounded to the ring dtype, nearest-even.

    For bf16 the rounding is done on the bits, so the bf16 conversion that
    follows is exact: a compiler allowed excess precision (XLA on the TPU)
    may drop the rounding of a bare f32 → bf16 → f32 convert pair, which
    would zero the error-feedback residue ``w − q`` in one program and not
    in another.  The kernels and their twins all quantize through here."""
    if jnp.dtype(dtype) == jnp.float32:
        return w
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f"no ring quantization to {dtype}")
    bits = jax.lax.bitcast_convert_type(w, jnp.int32)
    lsb = jax.lax.shift_right_logical(bits, 16) & 1
    bits = (bits + 0x7FFF + lsb) & jnp.int32(-65536)     # keep the top 16
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(dtype)


def update_event(spec: UpdateSpec, w, s, g, lr):
    """θ' = θ − α·step(g) with the optimizer state folded in.

    ``w``/``g`` are fp32 arrays of one leaf; ``s`` is that leaf's fp32 state
    (velocity or adagrad accumulator; ignored for sgd).  ``lr`` may be a
    traced scalar.  Returns ``(w', s')``.

    Called per-leaf by the pytree backends and per-tile *inside* the Pallas
    ``ps_update`` kernel — the kernel and the references share this body.
    (adamw carries two moments + a counter and is handled in backends.py.)
    """
    if spec.optimizer == "sgd":
        return w - lr * g, s
    if spec.optimizer == "momentum":
        v = spec.momentum * s + g
        return w - lr * v, v
    if spec.optimizer == "adagrad":
        a = s + jnp.square(g)
        return w - lr * g / (jnp.sqrt(a) + spec.eps), a
    raise ValueError(f"update_event does not support {spec.optimizer!r}")


# ---------------------------------------------------------------------------
# Folding algebra: c sequential momentum events → one affine update.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoundFold:
    """One-shot equivalent of c sequential momentum events.

    Sequential:  v_j = m·v_{j-1} + g_j ;  θ ← θ − lr_j·v_j   (j = 0..c−1)
    folds exactly into

        θ' = θ − Σ_i theta_coef_i·g_i − v0_coef·v
        v' = v_decay·v + Σ_i m^{c−1−i}·g_i

    ``v_gain`` = Σ_i m^{c−1−i} is the velocity gain when all g_i coincide
    (the fused engine's single weighted-mean gradient); with distinct g_i the
    velocity carry is a documented round-level approximation while the θ
    update stays exact for round 1 (see EXPERIMENTS.md §Perf).
    """

    theta_coef: np.ndarray     # (c,) per-gradient θ coefficients
    v0_coef: float             # θ's carry from the incoming velocity
    v_decay: float             # m^c
    v_gain: float              # Σ_i m^{c−1−i}


def sequential_fold(lrs: Sequence[float], momentum: float) -> RoundFold:
    """Fold per-event LRs + momentum into the affine round update."""
    lrs = np.asarray(lrs, np.float64)
    c = len(lrs)
    m = float(momentum)
    coef = np.zeros((c,))
    for i in range(c):
        for j in range(i, c):
            coef[i] += lrs[j] * (m ** (j - i))
    v0 = float(sum(lrs[j] * (m ** (j + 1)) for j in range(c)))
    gain = float(sum(m ** (c - 1 - i) for i in range(c)))
    return RoundFold(theta_coef=coef.astype(np.float64), v0_coef=v0,
                     v_decay=m ** c, v_gain=gain)
