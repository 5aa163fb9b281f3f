"""Three interchangeable backends for the unified applyUpdate (DESIGN.md §3).

* ``reference`` — eager pure-jnp, leaf-by-leaf Python loop.  The oracle.
* ``jit``       — the same pytree math under ``jax.jit`` (cached per
  (spec, mode, c)).  What the SPMD engines trace into their step functions.
* ``pallas``    — every leaf concatenated into one flat fp32 buffer and the
  whole model updated by a single fused ``ps_update`` kernel launch
  (interpret mode off-TPU).  The PS hot path.

All three execute :func:`repro.optim.spec.update_event` — the backends differ
only in how they schedule it over memory, never in the math.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.optim import flatten
from repro.optim.spec import (RoundFold, UpdateSpec, combine_terms,
                              quantize, update_event)

BACKENDS = ("reference", "jit", "pallas")

# host-side count of fused-kernel dispatches (tests/benchmarks assert the
# Pallas path really is the one being exercised), a telemetry counter
DISPATCHES = "optim.backends.pallas_dispatches"


def __getattr__(name: str):
    """``pallas_dispatches``, read from the telemetry counters."""
    if name == "pallas_dispatches":
        return telemetry.counters().get(DISPATCHES, 0)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _combine(grads: Sequence, coef) -> object:
    """Σ_i coef_i·G_i in fp32 — the staleness-weighted sumGradients."""
    return jax.tree.map(
        lambda *g: combine_terms(
            len(g), lambda i: coef[i] * g[i].astype(jnp.float32)), *grads)


# ---------------------------------------------------------------------------
# pytree event application (reference + jit backends)
# ---------------------------------------------------------------------------
def _adamw_event(spec: UpdateSpec, w32, state, g32, lr):
    b1, b2, eps = spec.beta1, spec.beta2, spec.eps
    cnt = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], g32)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * jnp.square(g),
                      state["nu"], g32)
    c1 = 1 - b1 ** cnt.astype(jnp.float32)
    c2 = 1 - b2 ** cnt.astype(jnp.float32)
    new_w = jax.tree.map(
        lambda w, m, n: w - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                  + spec.weight_decay * w),
        w32, mu, nu)
    return new_w, {"mu": mu, "nu": nu, "count": cnt}


def _weights(params, state):
    """The float32 weights an event updates: the master where the state
    keeps one, else the stored weights widened."""
    return state["master"] if "master" in state else _f32(params)


def _stored(params, state, new_w, new_state):
    """The stored weights (``new_w`` rounded to each leaf's dtype) and the
    state, with the master set to ``new_w`` where it is kept."""
    new_p = jax.tree.map(lambda p, w: w.astype(p.dtype), params, new_w)
    if "master" in state:
        new_state = dict(new_state, master=new_w)
    return new_p, new_state


def apply_single(spec: UpdateSpec, params, state, grad, lr):
    """ONE optimizer event with gradient ``grad`` (pytree) and lr ``lr``.

    Pure and jit-friendly (``lr`` may be traced) — this is what the
    distributed engines inline into their step functions.  With a float32
    master in ``state`` (``init_state``) the event updates the master."""
    g32 = _f32(grad)
    w32 = _weights(params, state)
    if spec.optimizer == "adamw":
        new_w, new_state = _adamw_event(spec, w32, state, g32, lr)
        return _stored(params, state, new_w, new_state)
    flat_w, treedef = jax.tree_util.tree_flatten(w32)
    flat_g = jax.tree_util.tree_leaves(g32)
    if spec.optimizer == "sgd":
        new_w = [update_event(spec, w, None, g, lr)[0]
                 for w, g in zip(flat_w, flat_g)]
        return _stored(params, state,
                       jax.tree_util.tree_unflatten(treedef, new_w), {})
    key = spec.state_keys[0]
    flat_s = jax.tree_util.tree_leaves(state[key])
    res = [update_event(spec, w, s.astype(jnp.float32), g, lr)
           for w, s, g in zip(flat_w, flat_s, flat_g)]
    new_w = jax.tree_util.tree_unflatten(treedef, [r[0] for r in res])
    new_s = jax.tree_util.tree_unflatten(
        treedef, [r[1].astype(s.dtype) for r, s in zip(res, flat_s)])
    return _stored(params, state, new_w, {key: new_s})


def apply_update_tree(spec: UpdateSpec, params, state, grads: Sequence,
                      coef, lrs, mode: str = "combine"):
    """The unified update on pytrees (reference semantics, jittable).

    ``grads`` is a sequence of c gradient pytrees; ``coef``/``lrs`` are
    length-c vectors (combination weights, per-event LRs)."""
    c = len(grads)
    if mode == "combine":
        return apply_single(spec, params, state, _combine(grads, coef),
                            lrs[0])
    if mode != "sequential":
        raise ValueError(f"unknown mode {mode!r}")
    for i in range(c):
        gi = jax.tree.map(
            lambda g: combine_terms(
                1, lambda _: coef[i] * g.astype(jnp.float32)), grads[i])
        params, state = apply_single(spec, params, state, gi, lrs[i])
    return params, state


def apply_round_folded(spec: UpdateSpec, params, state, ghat,
                       fold: RoundFold):
    """Apply a whole round of c sequential momentum events in one shot, given
    only their weighted-mean gradient ``ghat`` (the fused engine's single
    backward pass).  θ gets the exact affine fold — including the
    ``v0_coef`` carry from the incoming velocity that the seed engine
    dropped — and v advances by (v_decay, v_gain)."""
    if spec.optimizer != "momentum":
        raise ValueError("apply_round_folded is momentum-only; other "
                         "optimizers use apply_single with the folded lr")
    total = float(np.sum(fold.theta_coef))
    g32 = _f32(ghat)
    v = state["velocity"]
    new_v = jax.tree.map(lambda vv, g: fold.v_decay * vv + fold.v_gain * g,
                         v, g32)
    new_w = jax.tree.map(
        lambda w, g, vv: w - total * g - fold.v0_coef * vv,
        _weights(params, state), g32, v)
    return _stored(params, state, new_w, {"velocity": new_v})


def apply_event_flat(spec: UpdateSpec, w, s, g, coef, lrs,
                     mode: str = "combine"):
    """The unified multi-gradient update on flat fp32 buffers — the jit/scan
    friendly twin of the Pallas kernel's per-tile body (``ps_update._events``)
    with the identical ``update_event`` math and ``combine_terms`` sum.

    ``w``/``s``: (D,) fp32 (``s`` None for sgd); ``g``: (c, D); ``coef``/
    ``lrs``: (c,).  This is what the compiled replay engine's scan executes
    per update event (``core/engine.py``): one fused event over the whole
    concatenated model instead of a per-leaf pytree walk."""
    if not spec.kernel_supported:
        raise ValueError(f"{spec.optimizer!r} has no flat event path")
    g32 = g.astype(jnp.float32)
    if mode == "combine":
        coef = coef.astype(jnp.float32)
        ghat = combine_terms(g.shape[0], lambda i: coef[i] * g32[i])
        return update_event(spec, w, s, ghat, lrs[0])
    if mode != "sequential":
        raise ValueError(f"unknown mode {mode!r}")
    for i in range(g.shape[0]):                     # c is static
        gi = combine_terms(1, lambda _: coef[i] * g32[i])
        w, s = update_event(spec, w, s, gi, lrs[i])
    return w, s


def apply_event_ring(spec: UpdateSpec, ring, s, res, g, coef, lrs,
                     prev, slot, mode: str = "combine"):
    """ONE fused ring event on flat buffers — the jnp twin of the Pallas
    replay megakernel (``kernels/replay_ring.ring_apply``), and the
    engine's ``"fused"`` event body off the TPU.

    ``ring``: (K, Dp) in the ring dtype (fp32 or bf16); ``s``: (Dp,) fp32
    optimizer state or None (sgd); ``res``: (Dp,) fp32 error-feedback
    residue or None (fp32 ring); ``g``: (c, Dp) fp32; ``prev``/``slot``:
    ring row scalars.  The master chain is exact: the fp32 weights entering
    ``apply_event_flat`` are ``q(w) + (w − q(w)) = w``, so with a bf16 ring
    the only approximation anywhere is gradients being *evaluated* at
    quantized snapshots (DESIGN.md §12).  With an fp32 ring the casts are
    no-ops and this is bitwise the gather → ``apply_event_flat`` → row
    write chain."""
    w = ring[prev].astype(jnp.float32)
    if res is not None:
        w = w + res
    w, s = apply_event_flat(spec, w, s, g, coef, lrs, mode)
    q = quantize(w, ring.dtype)
    ring = ring.at[slot].set(q)
    if res is not None:
        res = w - q.astype(jnp.float32)
    return ring, s, res


def apply_event_ring_whatif(spec: UpdateSpec, ring, s, res, a, wstar, ts,
                            coef, lrs, prev, slot):
    """Fused ring event with closed-form gradients g_j = a⊙(w_ts_j − w*),
    streamed over the c slots with a ``fori_loop`` so the (c, Dp)
    pulled-weight/gradient matrices never materialize — peak extra memory
    is O(Dp), which is what makes trace-driven what-if replay feasible at
    10–100× larger D (the jnp twin of ``replay_ring.ring_apply_whatif``;
    combine mode only).  The accumulation order (slot 0 → c−1) matches the
    kernel's inner grid axis bitwise."""
    c = ts.shape[0]
    coef = coef.astype(jnp.float32)

    def body(j, acc):
        row = ring[ts[j]].astype(jnp.float32)
        return acc + coef[j] * (a * (row - wstar))

    ghat = jax.lax.fori_loop(0, c, body,
                             jnp.zeros(ring.shape[-1:], jnp.float32))
    w = ring[prev].astype(jnp.float32)
    if res is not None:
        w = w + res
    w, s = update_event(spec, w, s, ghat, lrs[0])
    q = quantize(w, ring.dtype)
    ring = ring.at[slot].set(q)
    if res is not None:
        res = w - q.astype(jnp.float32)
    return ring, s, res


# ---------------------------------------------------------------------------
# SPMD replay collectives (DESIGN.md §13)
# ---------------------------------------------------------------------------
def combine_spmd(g, coef, axis_name: str):
    """The combine-mode sum ĝ = Σ_j coef_j·g_j with the slot axis split
    over ``axis_name``: each learner device reduces its local slot block,
    then one ``psum`` folds the partials.  For a single learner device the
    psum is the identity and this is bitwise ``apply_event_flat``'s
    ``combine_terms``; with L > 1 the partial-sum tree reorders the fp32
    reduction (the documented ~1 ulp/event tolerance, DESIGN.md §13)."""
    g32, coef = g.astype(jnp.float32), coef.astype(jnp.float32)
    part = combine_terms(g.shape[0], lambda i: coef[i] * g32[i])
    return jax.lax.psum(part, axis_name)


# ---------------------------------------------------------------------------
# pallas backend: one fused kernel launch over the concatenated model
# ---------------------------------------------------------------------------
def apply_update_flat(spec: UpdateSpec, params, state, grads: Sequence,
                      coef, lrs, mode: str = "combine",
                      interpret: bool = True):
    """Flatten → single ``ps_update`` pallas_call → unflatten.  With a
    float32 master in ``state`` the kernel updates the master's flat
    buffer, and the stored weights are its leaves rounded."""
    from repro.kernels import ps_update as _psu   # lazy: breaks import cycle

    w_tree = _weights(params, state)
    w_layout = flatten.layout_of(w_tree)
    w = flatten.tree_to_flat(w_tree)
    g = flatten.stack_grads_flat(grads)
    if spec.optimizer == "sgd":
        w2, _ = _psu.ps_apply(w, None, g, coef, lrs, spec=spec, mode=mode,
                              interpret=interpret)
        new_state = {}
    else:
        key = spec.state_keys[0]
        s_layout = flatten.layout_of(state[key])
        s = flatten.tree_to_flat(state[key])
        w2, s2 = _psu.ps_apply(w, s, g, coef, lrs, spec=spec, mode=mode,
                               interpret=interpret)
        new_state = {key: flatten.flat_to_tree(s2, s_layout)}
    return _stored(params, state, flatten.flat_to_tree(w2, w_layout),
                   new_state)


# ---------------------------------------------------------------------------
# host-facing dispatch (jit-cached per static configuration)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jitted(spec: UpdateSpec, mode: str, c: int, backend: str,
            interpret: bool):
    if backend == "pallas":
        def fn(params, state, grads, coef, lrs):
            return apply_update_flat(spec, params, state, list(grads),
                                     coef, lrs, mode, interpret)
    else:
        def fn(params, state, grads, coef, lrs):
            return apply_update_tree(spec, params, state, list(grads),
                                     coef, lrs, mode)
    return jax.jit(fn)


def apply_update(spec: UpdateSpec, params, state, grads: Sequence,
                 coef, lrs, *, mode: str = "combine", backend: str = "jit",
                 interpret: Optional[bool] = None):
    """The one entry point every consumer routes through.

    ``grads``: sequence of c gradient pytrees.  ``coef``: (c,) combination
    weights.  ``lrs``: (c,) per-event LRs (``combine`` mode reads lrs[0]).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    grads = tuple(grads)
    coef = jnp.asarray(coef, jnp.float32)
    lrs = jnp.asarray(lrs, jnp.float32)
    if backend == "reference":
        return apply_update_tree(spec, params, state, list(grads),
                                 coef, lrs, mode)
    if backend == "pallas" and not spec.kernel_supported:
        backend = "jit"                      # adamw: pytree path
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if backend == "pallas":
        telemetry.count(DISPATCHES)
    fn = _jitted(spec, mode, len(grads), backend, bool(interpret))
    return fn(params, state, grads, coef, lrs)


def sgd_step(params, grad, lr):
    """Convenience plain-SGD event (baseline simulators)."""
    return apply_single(UpdateSpec(optimizer="sgd"), params, {}, grad, lr)[0]
