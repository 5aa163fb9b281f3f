"""Problem registry for the experiment surface (DESIGN.md §5).

An :class:`ExperimentSpec` names its problem declaratively (a registry key +
keyword arguments) so a spec stays a frozen, JSON-serializable value; the
driver resolves the name to a **problem object** exposing the contract the
replay engine needs:

* ``init``                 — the initial parameter pytree;
* ``grad_fn(params, batch) -> grads`` — vmappable gradient;
* ``batch_fn_for(mu, seed) -> (learner, minibatch_idx) -> batch`` — host
  (numpy) batches, deterministic per (seed, learner, step);
* ``eval_fn(params) -> dict`` — the metric set (keys are metric names);
* ``dataset_size``         — samples per epoch (steps-from-epochs maths).

Problems are cached per (name, args): a sweep over 20 (protocol, seed) grid
points builds the teacher task and its jitted grad/eval functions once, and
every grid point shares the same ``grad_fn`` — the property that lets the
driver vmap shape-compatible grid points through one compiled scan.

``mlp_teacher`` — the repo's CIFAR-scale stand-in (2-layer MLP on the
teacher-classification task, DESIGN.md §11) — ships registered;
:func:`register_problem` adds new ones (see ``tests/test_experiments.py``
for a 4-line linear-regression example).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.data.synthetic import TeacherClassification


def updates_for_epochs(epochs: float, mu: int, c: int, dataset: int,
                       group_size: int = 1) -> int:
    """Weight updates s.t. total samples == epochs·dataset (every update
    consumes c·μ·gs samples: c slots, each aggregating ``group_size``
    member minibatches — 1 without learner groups; hardsync has c = P)."""
    return max(1, int(epochs * dataset / (mu * c * group_size)))


# ---------------------------------------------------------------------------
# MLP learner on the teacher-classification task (the paper's CNN stand-in)
# ---------------------------------------------------------------------------
class MLPProblem:
    """2-layer MLP trained on TeacherClassification — the accuracy-axis
    vehicle for Figs. 5-7 / Tables 2-4 (non-convex, overfits, LR-sensitive:
    the properties the paper's claims depend on)."""

    def __init__(self, hidden: int = 64, task: TeacherClassification = None,
                 seed: int = 0):
        self.task = task or TeacherClassification()
        self.hidden = hidden
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        nf, nc = self.task.n_features, self.task.n_classes
        self.init = {
            "w1": jax.random.normal(k1, (nf, hidden)) / np.sqrt(nf),
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, nc)) / np.sqrt(hidden),
            "b2": jnp.zeros((nc,)),
        }
        self._grad = jax.jit(jax.grad(self.loss))
        self._test_err = jax.jit(self._test_err_impl)

    @property
    def dataset_size(self) -> int:
        return self.task.n_train

    def loss(self, p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - ll)

    def _test_err_impl(self, p):
        x, y = self.task.x_test, self.task.y_test
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = jnp.argmax(h @ p["w2"] + p["b2"], axis=-1)
        return 1.0 - jnp.mean((pred == y).astype(jnp.float32))

    def grad_fn(self, p, batch):
        return self._grad(p, batch)

    def batch_fn_for(self, mu: int, seed: int = 0) -> Callable:
        # returns host (numpy) arrays: the jitted grad_fn transfers them on
        # call, and the replay engine stages the whole trace's batches with
        # ONE device transfer per leaf instead of one per minibatch.
        def fn(learner: int, step: int):
            return self.task.minibatch(learner, step, mu, seed=seed)
        return fn

    def stage_minibatches(self, learner, mb_index, mu: int, seed: int = 0):
        """Whole-trace staging in one vectorized hash (optional problem
        protocol, see DESIGN.md §5): (steps, c) counter matrices → the
        (steps, c, …) batch pytree, element-identical to per-slot
        ``batch_fn`` calls.  This is what lets ``run_sweep`` stage a whole
        sweep cell in milliseconds instead of a steps×c Python loop per
        grid point."""
        return self.task.minibatch_array(learner, mb_index, mu, seed=seed)

    def test_error(self, p) -> float:
        return float(self._test_err(p))

    def eval_fn(self, p) -> Dict[str, float]:
        return {"test_error": self.test_error(p)}

    # -- serving hooks (train-while-serve, DESIGN.md §14) --------------------
    _REQUEST_RNG_TAG = 0x53525645

    def stage_requests(self, serving, fleet, seed: int = 0):
        """One batch of held-out samples per inference request: arrays with
        a leading (R,) request axis, staged host-side in one draw.  The rng
        stream is tagged independently of training batches, and the draw
        depends only on (R, request_samples, seed) — the same traffic asks
        the same questions whatever publication policy answers them."""
        rng = np.random.default_rng([seed, self._REQUEST_RNG_TAG])
        idx = rng.integers(0, self.task.n_test,
                           (serving.n_requests, fleet.request_samples))
        return (np.asarray(self.task.x_test)[idx],
                np.asarray(self.task.y_test)[idx])

    def request_metric(self, p, batch):
        """Accuracy of one request batch under the published weights —
        vmappable (the engine maps it over the (R,) request axis)."""
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = jnp.argmax(h @ p["w2"] + p["b2"], axis=-1)
        return jnp.mean((pred == y).astype(jnp.float32))


# ---------------------------------------------------------------------------
# diagonal quadratic: the what-if replay vehicle (DESIGN.md §12)
# ---------------------------------------------------------------------------
class QuadraticProblem:
    """Diagonal quadratic loss ``0.5·mean(a·(w − w*)²)`` with closed-form
    gradients ``g = a ⊙ (w − w*)`` — the trace-driven *what-if* vehicle.

    Because the gradient is a flat elementwise expression, the replay
    engine evaluates it in-kernel (``flat_grad`` below) and never stages
    minibatch data: peak memory is the ring carry alone, which is what
    makes staleness what-if studies feasible at ``configs/`` big-model D
    (pass ``arch="qwen2_1_5b"`` etc. to size D to a registered
    architecture's parameter count).  ``a`` and ``w*`` are closed-form
    functions of the flat position (:meth:`coeffs`), evaluated on the
    device inside whichever program needs them — the problem stores no
    (D,) array, and each "ps" device of an SPMD replay computes only its
    own slice.  The ``grad_fn``/``batch_fn_for`` twins keep the problem
    valid on every non-what-if path (stock impl, legacy oracle, sharded
    traces): the batch is a 1-element dummy the gradient ignores.

    ``shards`` = S > 1 lays ``init`` out over the "ps" devices of
    ``placement="spmd"`` replay with S shards, one contiguous block of d/S
    per device, so at model size no device holds the whole (d,) vector
    (d must divide evenly).
    """

    def __init__(self, d: int = 4096, arch: str = None, seed: int = 0,
                 shards: int = 1):
        if arch is not None:
            from repro.configs import get_config
            d = int(get_config(arch).param_count())
        self.d = int(d)
        self._seed = seed
        self._layout = None
        if shards > 1:
            if self.d % shards:
                raise ValueError(f"d={self.d} does not split into "
                                 f"{shards} equal shards")
            from repro.launch.mesh import make_sim_mesh
            self._layout = NamedSharding(make_sim_mesh(shards, 1),
                                         PartitionSpec("ps"))
        self.flat_grad = ("quadratic", self.coeffs)
        self._loss = jax.jit(self._loss_impl)

    def coeffs(self, pos):
        """(a, w*) at int32 flat positions ``pos``, deterministic in
        (pos, seed): curvatures in [0.5, 1.5) (positive definite,
        non-isotropic) and a smooth target."""
        i = pos.astype(jnp.float32)
        a = 0.5 + ((i + 37.0 * self._seed) % 1000.0) / 1000.0
        wstar = jnp.sin(1e-3 * i + self._seed)
        return a, wstar

    def _loss_impl(self, w):
        a, wstar = self.coeffs(jnp.arange(self.d, dtype=jnp.int32))
        return 0.5 * jnp.mean(a * (w - wstar) ** 2)

    @property
    def init(self) -> Dict[str, jax.Array]:
        # a fresh zeros pytree per access: nothing of size D stays resident
        # in the problem between replays
        return {"w": jnp.zeros((self.d,), jnp.float32, device=self._layout)}

    @property
    def dataset_size(self) -> int:
        return 1 << 16          # synthetic: epochs-maths placeholder

    def grad_fn(self, p, batch):
        a, wstar = self.coeffs(jnp.arange(self.d, dtype=jnp.int32))
        return {"w": a * (p["w"] - wstar)}

    def batch_fn_for(self, mu: int, seed: int = 0) -> Callable:
        def fn(learner: int, step: int):
            return np.zeros((1,), np.float32)
        return fn

    def stage_minibatches(self, learner, mb_index, mu: int, seed: int = 0):
        return np.zeros(np.shape(learner) + (1,), np.float32)

    def eval_fn(self, p) -> Dict[str, float]:
        return {"loss": float(self._loss(p["w"]))}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable] = {}
_CACHE: Dict[Tuple, object] = {}


def register_problem(name: str, factory: Callable, version: int = 1) -> None:
    """Register ``factory(**kwargs) -> problem`` under ``name``.  The factory
    result must expose init / grad_fn / batch_fn_for / eval_fn /
    dataset_size (see module docstring).  ``version`` is the problem's
    content identity for spec hashing (DESIGN.md §15): bump it when the
    problem's semantics change and every cached result that used it goes
    stale."""
    from repro.experiments.spec_hash import register_problem_version
    register_problem_version(name, version)
    _REGISTRY[name] = factory


def problem_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_problem(name: str, args: Tuple[Tuple[str, object], ...] = ()):
    """Resolve (and cache) a registered problem.  ``args`` is the spec's
    hashable ``problem_args`` tuple-of-pairs."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown problem {name!r}; registered: "
                       f"{problem_names()}")
    key = (name, tuple(args))
    if key not in _CACHE:
        _CACHE[key] = _REGISTRY[name](**dict(args))
    return _CACHE[key]


register_problem("mlp_teacher", MLPProblem)
register_problem("quadratic_whatif", QuadraticProblem)
