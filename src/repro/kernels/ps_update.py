"""Fused parameter-server update kernel (the paper's applyUpdate hot-spot).

The PS receives c gradient shards and applies the unified staleness-aware
update (repro.optim, DESIGN.md §3) in one pass over the parameters.  Two
modes, matching the optimizer subsystem:

* ``combine``    — g = Σ_i coef_i·G_i, then ONE optimizer event (Eq. 3/5
  with the footnote-3 per-gradient coefficients as kernel operands).
* ``sequential`` — c in-register optimizer events, event i applying
  coef_i·G_i with its own lr_i (exact per-gradient staleness semantics;
  momentum/adagrad state advances per event without extra HBM traffic).

Supported optimizers: sgd (stateless), momentum (velocity), adagrad
(accumulator) — the kernel body calls ``repro.optim.spec.update_event``,
the SAME function the pytree backends map over leaves, so there is exactly
one implementation of the update math in the repo.

Unfused this is c + 4 HBM round-trips over the model; fused it is one read
of (W, S, G_0..c) and one write of (W', S') — the memory-bound term of the
PS roofline drops by ~3× (see EXPERIMENTS.md §Perf).

Layout: the FULL parameter pytree is concatenated into a single fp32 vector
(repro.optim.flatten), padded and reshaped to (R, 128) lanes; the grid tiles
rows, so the whole model updates in ONE ``pallas_call`` instead of a
per-leaf Python loop.  Per-gradient coefficients and LRs arrive as (c, 1)
fp32 operands broadcast to every tile.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.optim.spec import UpdateSpec, combine_terms, update_event
from repro.optim import flatten as _flatten

LANES = 128
DEFAULT_ROW_BLOCK = 256


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------
def _events(spec: UpdateSpec, mode: str, c: int, coef_ref, lrs_ref, w, s, g_ref):
    """Run the update events on one (rblk, LANES) tile.  ``w``/``s`` are fp32
    tile arrays; gradients are read from ``g_ref`` ((c, rblk, LANES))."""
    if mode == "combine":
        g = combine_terms(
            c, lambda i: coef_ref[i, 0] * g_ref[i].astype(jnp.float32))
        return update_event(spec, w, s, g, lrs_ref[0, 0])
    for i in range(c):                                       # c is static
        gi = combine_terms(
            1, lambda _: coef_ref[i, 0] * g_ref[i].astype(jnp.float32))
        w, s = update_event(spec, w, s, gi, lrs_ref[i, 0])
    return w, s


def _stateful_kernel(coef_ref, lrs_ref, w_ref, s_ref, g_ref,
                     w_out_ref, s_out_ref, *, spec: UpdateSpec, mode: str,
                     c: int):
    w = w_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    w, s = _events(spec, mode, c, coef_ref, lrs_ref, w, s, g_ref)
    w_out_ref[...] = w.astype(w_out_ref.dtype)
    s_out_ref[...] = s.astype(s_out_ref.dtype)


def _stateless_kernel(coef_ref, lrs_ref, w_ref, g_ref, w_out_ref, *,
                      spec: UpdateSpec, mode: str, c: int):
    w = w_ref[...].astype(jnp.float32)
    w, _ = _events(spec, mode, c, coef_ref, lrs_ref, w, None, g_ref)
    w_out_ref[...] = w.astype(w_out_ref.dtype)


# ---------------------------------------------------------------------------
# flat entry point
# ---------------------------------------------------------------------------
def ps_apply(w_flat: jax.Array, s_flat: Optional[jax.Array],
             g_flat: jax.Array, coef: jax.Array, lrs: jax.Array, *,
             spec: UpdateSpec, mode: str = "combine",
             row_block: Optional[int] = None, interpret: bool = False
             ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The fused applyUpdate.  w/s: (D,); g: (c, D); coef/lrs: (c,) fp32.

    ``s_flat`` is the optimizer-state vector (velocity or adagrad
    accumulator); pass None for sgd.  Pads D up to a multiple of
    row_block·128 and reshapes to (R, 128) tiles.
    """
    if not spec.kernel_supported:
        raise ValueError(f"{spec.optimizer!r} has no kernel path")
    D = w_flat.shape[0]
    c = g_flat.shape[0]
    if row_block is None:
        row_block = int(min(DEFAULT_ROW_BLOCK, max(1, -(-D // LANES))))
    tile = row_block * LANES
    Dp = ((D + tile - 1) // tile) * tile
    pad = Dp - D
    wp = jnp.pad(w_flat, (0, pad)).reshape(-1, LANES)
    gp = jnp.pad(g_flat, ((0, 0), (0, pad))).reshape(c, -1, LANES)
    coef2 = coef.reshape(c, 1).astype(jnp.float32)
    lrs2 = lrs.reshape(c, 1).astype(jnp.float32)
    grid = (wp.shape[0] // row_block,)

    vec_spec = pl.BlockSpec((c, 1), lambda i: (0, 0))
    row_spec = pl.BlockSpec((row_block, LANES), lambda i: (i, 0))
    g_spec = pl.BlockSpec((c, row_block, LANES), lambda i: (0, i, 0))

    if spec.optimizer == "sgd":
        kernel = functools.partial(_stateless_kernel, spec=spec, mode=mode,
                                   c=c)
        w2 = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[vec_spec, vec_spec, row_spec, g_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct(wp.shape, w_flat.dtype),
            interpret=interpret,
        )(coef2, lrs2, wp, gp)
        return w2.reshape(-1)[:D], None

    sp = jnp.pad(s_flat, (0, pad)).reshape(-1, LANES)
    kernel = functools.partial(_stateful_kernel, spec=spec, mode=mode, c=c)
    w2, s2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec, vec_spec, row_spec, row_spec, g_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(wp.shape, w_flat.dtype),
            jax.ShapeDtypeStruct(sp.shape, s_flat.dtype),
        ],
        interpret=interpret,
    )(coef2, lrs2, wp, sp, gp)
    return w2.reshape(-1)[:D], s2.reshape(-1)[:D]


# ---------------------------------------------------------------------------
# back-compat wrappers (seed API: momentum-only, combine mode)
# ---------------------------------------------------------------------------
def ps_update_flat(w_flat: jax.Array, v_flat: jax.Array, g_flat: jax.Array,
                   coef: jax.Array, *, momentum: float = 0.9,
                   lr: float = 1.0, row_block: int = DEFAULT_ROW_BLOCK,
                   interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Momentum combine-mode entry.  w/v: (D,); g: (c, D); coef: (c,)."""
    c = g_flat.shape[0]
    spec = UpdateSpec(optimizer="momentum", momentum=momentum)
    lrs = jnp.full((c,), lr, jnp.float32)
    w2, v2 = ps_apply(w_flat, v_flat, g_flat, jnp.asarray(coef, jnp.float32),
                      lrs, spec=spec, mode="combine", row_block=row_block,
                      interpret=interpret)
    return w2, v2


def ps_update_tree(params, velocity, grads_list, coef, *, momentum=0.9,
                   lr=1.0, interpret: bool = False):
    """Pytree convenience wrapper: ONE fused kernel launch over the whole
    concatenated model (repro.optim.flatten), not a per-leaf loop."""
    spec = UpdateSpec(optimizer="momentum", momentum=momentum)
    p_layout = _flatten.layout_of(params)
    v_layout = _flatten.layout_of(velocity)
    w = _flatten.tree_to_flat(params)
    v = _flatten.tree_to_flat(velocity)
    g = _flatten.stack_grads_flat(grads_list)
    c = g.shape[0]
    lrs = jnp.full((c,), lr, jnp.float32)
    w2, v2 = ps_apply(w, v, g, jnp.asarray(coef, jnp.float32), lrs,
                      spec=spec, mode="combine", interpret=interpret)
    return (_flatten.flat_to_tree(w2, p_layout),
            _flatten.flat_to_tree(v2, v_layout))
