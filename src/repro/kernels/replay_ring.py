"""Fused replay megakernel over the weight ring buffer (DESIGN.md §12).

The compiled replay engine (``core/engine.py``) executes one update event
per ``lax.scan`` step against a (K, D) ring of parameter snapshots.  The
stock body is a chain of XLA ops — ring gather, combine sum, optimizer
update, dynamic-update-slice write — each a separate pass over D.  This
module fuses the whole event into ONE ``pallas_call``:

    ring-read(prev row) → [+ error-feedback residue] → combine/sequential
    optimizer event → quantize → ring-write(slot row) [+ residue write]

tiled over D exactly like ``kernels/ps_update.py`` ((R, 128) lanes,
row-block grid).  Two properties make it one launch per scan step:

* **Scalar-prefetch ring indices** — ``prev``/``slot`` (and the per-slot
  ``ts`` rows for the what-if kernel) arrive as a scalar-prefetch operand
  (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps pick the
  ring *rows* dynamically per launch while the grid stays static.
* **In-place ring writes** — ``input_output_aliases`` aliases the ring (and
  state/residue) inputs onto the outputs, so the kernel writes only the
  (1, row_block, 128) slot-row blocks of the ring it was given.  The
  what-if kernel takes the ring as ONE operand and reads its previous row
  through the aliased output; the replay scan (``core/engine.py``) carries
  that ring, and state, residue, a and w*, in the kernel's (rows, 128)
  tiles, so the flat views it passes here fold into the reshapes below
  and the event updates the loop carry itself: no copy of the K·D ring
  per event, and no scan temporaries of ring size
  (``tests/test_tpu_compile.py``).  ``ring_apply`` takes its ring once
  too, but the staged-gradient scan keeps a flat (K, Dp) carry, which a
  bf16 ring lays out across K: there the reshapes to the tiles and back
  are copies of the ring.

Compressed ring (``ring_dtype == bf16``): the ring rows store bf16
snapshots while the update math stays fp32.  The quantization error is not
lost — an fp32 **error-feedback residue** vector carries ``w − q(w)`` of the
*latest* row and is re-added before the next update, so the master weight
chain is exactly the fp32 trajectory *given the gradients*; the only
approximation is that gradients are evaluated at quantized snapshots
(tests/test_engine_megakernel.py pins both halves of that statement).

The **what-if** kernel goes one step further for trace-driven studies on
big-model shapes: for problems whose flat gradient is a closed form
(``g = a ⊙ (w_pulled − w*)``, the quadratic family), the c per-slot
gradients are computed *inside* the kernel, one (row_block, 128) tile at a
time over a (rows, c) grid — the (c, D) pulled-weight and gradient
matrices are never materialized, so peak memory drops from O((K + c)·D)
to O(K·D_bytes + D) and the feasible D grows ~10–100× (EXPERIMENTS.md
§Sim, max-feasible-D table).

Both kernels are **width-agnostic**: D is whatever the caller's last axis
is, so under ``placement="spmd"`` (DESIGN.md §13) the engine invokes them
per-device on the shard-local ``(K, padded_width(⌈D/S⌉))`` ring slice
inside ``shard_map`` — the grid/BlockSpec machinery never sees the mesh,
and the elementwise event math guarantees per-shard applies are exactly
the shard slices of the single-device apply.

Off-accelerator every entry point selects ``interpret=True`` automatically
(the CPU-CI fallback contract of ``kernels/ops.py``); the
``replay_ring.pallas_dispatches``/``replay_ring.last_interpret`` counters
of ``repro.telemetry`` (readable here as the module attributes
``pallas_dispatches``/``last_interpret``) record which dispatch branch
built the kernel so tests can assert the fused path is really the one
exercised.

Names in a profile: the kernels are the ``pallas_call``s
``replay_ring_whatif`` and ``replay_ring_apply``; the reshapes of the
ring, state, residue (and a, w*) to the kernel's (rows, 128) tiling and
back run under the scopes ``replay_ring.to_tiles`` and
``replay_ring.from_tiles`` where they are copies (the staged-gradient
body's flat carry).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry
from repro.kernels.ps_update import DEFAULT_ROW_BLOCK, LANES
from repro.optim.spec import (UpdateSpec, combine_terms, quantize,
                              update_event)

# trace-time dispatch telemetry: how many times a replay megakernel was
# built (counted at trace time — once per compiled scan, not per step) and
# whether the last build ran in interpret mode.  Tests assert on these to
# pin the CPU-CI fallback branch.
DISPATCHES = "replay_ring.pallas_dispatches"
LAST_INTERPRET = "replay_ring.last_interpret"


def __getattr__(name: str):
    """``pallas_dispatches`` and ``last_interpret`` (None before the first
    build), read from the telemetry counters."""
    if name == "pallas_dispatches":
        return telemetry.counters().get(DISPATCHES, 0)
    if name == "last_interpret":
        v = telemetry.counters().get(LAST_INTERPRET)
        return None if v is None else bool(v)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _count_build(interpret: bool) -> None:
    telemetry.count(DISPATCHES)
    telemetry.mark(LAST_INTERPRET, bool(interpret))


def _from_tiles(out, K: int, Dp: int, stateful: bool, ef: bool):
    """The kernel's tiled (ring, state, residue) outputs back to the
    engine's flat (K, Dp) / (Dp,) layout."""
    with jax.named_scope("replay_ring.from_tiles"):
        ring2 = out[0].reshape(K, Dp)
        k = 1
        s2 = out[k].reshape(Dp) if stateful else None
        k += int(stateful)
        res2 = out[k].reshape(Dp) if ef else None
    return ring2, s2, res2


def default_interpret() -> bool:
    """Pallas compiles on TPU only; everywhere else run the kernel in
    interpret mode (same math, XLA-executed) — tier-1 CI never skips the
    fused path, it just doesn't get TPU codegen."""
    return jax.default_backend() != "tpu"


def row_block_for(width: int) -> int:
    return int(min(DEFAULT_ROW_BLOCK, max(1, -(-width // LANES))))


def padded_width(width: int) -> int:
    """Ring width padded so (width / 128) rows tile evenly into row blocks
    (zero padding is inert through sgd/momentum/adagrad events)."""
    tile = row_block_for(width) * LANES
    return -(-width // tile) * tile


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------
def _tile_events(spec: UpdateSpec, mode: str, c: int, coef_ref, lrs_ref,
                 w, s, g_ref):
    """The update events on one (rb, LANES) tile — ``ps_update._events``
    with the combine contraction phrased through ``optim.combine_terms``
    like ``optim.apply_event_flat`` (the stock scan body), so the fp32
    megakernel replay is BITWISE-equal to the stock path."""
    if mode == "combine":
        ghat = combine_terms(
            c, lambda i: coef_ref[i, 0] * g_ref[i].astype(jnp.float32))
        return update_event(spec, w, s, ghat, lrs_ref[0, 0])
    for i in range(c):                                    # c is static
        gi = combine_terms(
            1, lambda _: coef_ref[i, 0] * g_ref[i].astype(jnp.float32))
        w, s = update_event(spec, w, s, gi, lrs_ref[i, 0])
    return w, s


def _apply_kernel(idx_ref, *refs, spec: UpdateSpec, mode: str, c: int,
                  stateful: bool, ef: bool):
    """One fused ring event, external gradients.  Grid: (row_blocks,).

    ``idx_ref`` = [prev_row, slot_row].  Input blocks (after the scalar
    prefetch): coef (c,1), lrs (c,1), ring (1,rb,L) at row prev, state
    (rb,L) if stateful, residue (rb,L) if ef, grads (c,rb,L).  Outputs
    (aliased in-place): ring block at row slot, state, residue."""
    n_in = 3 + int(stateful) + int(ef) + 1
    ins, outs = refs[:n_in], refs[n_in:]
    coef_ref, lrs_ref, ring_ref = ins[0], ins[1], ins[2]
    k = 3
    s_ref = ins[k] if stateful else None
    k += int(stateful)
    res_ref = ins[k] if ef else None
    k += int(ef)
    g_ref = ins[k]
    ring_out = outs[0]
    s_out = outs[1] if stateful else None
    res_out = outs[1 + int(stateful)] if ef else None

    w = ring_ref[0].astype(jnp.float32)
    if ef:
        w = w + res_ref[...]                     # re-add quantization error
    s = s_ref[...].astype(jnp.float32) if stateful else None
    w, s = _tile_events(spec, mode, c, coef_ref, lrs_ref, w, s, g_ref)
    q = quantize(w, ring_out.dtype)
    ring_out[0] = q
    if stateful:
        s_out[...] = s
    if ef:
        res_out[...] = w - q.astype(jnp.float32)


def _whatif_kernel(idx_ref, *refs, spec: UpdateSpec, c: int, nb: int,
                   rb: int, stateful: bool, ef: bool):
    """One fused ring event with IN-KERNEL quadratic gradients.

    Grid: (row_blocks, c) — the inner grid axis streams the c slots, each
    reading its pulled ring row block (``idx_ref[2 + j]``, the ring
    operand's block) and accumulating ``coef_j · a ⊙ (w_ts − w*)`` into a
    VMEM scratch tile; the last slot runs the optimizer event and writes
    ring/state/residue.  The (c, D) gradient matrix never exists.

    The ring output stays in HBM and is the ring operand's own buffer
    (aliased): the kernel copies the previous row block (``idx_ref[0]``)
    in from it one row block ahead, and the new slot row block
    (``idx_ref[1]``) out to it, double-buffered.  The caller guarantees
    K ≥ 2, so the slot row is not the previous row; it may be a pulled
    row, but blocks are column-disjoint and block i is written only after
    its last read."""
    n_in = 5 + int(stateful) + int(ef)
    ins, outs = refs[:n_in], refs[n_in:]
    coef_ref, lrs_ref, ring_ref, a_ref, ws_ref = ins[:5]
    s_ref = ins[5] if stateful else None
    res_ref = ins[5 + int(stateful)] if ef else None
    ring_out = outs[0]
    s_out = outs[1] if stateful else None
    res_out = outs[1 + int(stateful)] if ef else None
    acc_ref, prev_buf, out_buf, sems = outs[1 + int(stateful) + int(ef):]

    i, j = pl.program_id(0), pl.program_id(1)

    def cols(b):
        return pl.ds(pl.multiple_of(b * rb, rb), rb)

    def prev(b):
        return pltpu.make_async_copy(ring_out.at[idx_ref[0], cols(b)],
                                     prev_buf.at[b % 2], sems.at[0, b % 2])

    def write(b):
        return pltpu.make_async_copy(out_buf.at[b % 2],
                                     ring_out.at[idx_ref[1], cols(b)],
                                     sems.at[1, b % 2])

    @pl.when((i == 0) & (j == 0))
    def _first():
        prev(i).start()

    @pl.when((j == 0) & (i + 1 < nb))
    def _next_prev():
        prev(i + 1).start()

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g_j = a_ref[...] * (ring_ref[0].astype(jnp.float32) - ws_ref[...])
    acc_ref[...] += coef_ref[j, 0] * g_j

    @pl.when(j == c - 1)
    def _apply():
        prev(i).wait()
        w = prev_buf[i % 2].astype(jnp.float32)
        if ef:
            w = w + res_ref[...]
        s = s_ref[...].astype(jnp.float32) if stateful else None
        w2, s2 = update_event(spec, w, s, acc_ref[...], lrs_ref[0, 0])
        q = quantize(w2, out_buf.dtype)
        out_buf[i % 2] = q
        write(i).start()
        if stateful:
            s_out[...] = s2
        if ef:
            res_out[...] = w2 - q.astype(jnp.float32)

        @pl.when(i > 0)
        def _():
            write(i - 1).wait()

        @pl.when(i == nb - 1)
        def _():
            write(i).wait()


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def ring_apply(ring: jax.Array, s: Optional[jax.Array],
               res: Optional[jax.Array], g: jax.Array, coef: jax.Array,
               lrs: jax.Array, idx: jax.Array, *, spec: UpdateSpec,
               mode: str = "combine", row_block: Optional[int] = None,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, Optional[jax.Array],
                          Optional[jax.Array]]:
    """ONE fused ring event: read row ``idx[0]``, apply the c-gradient
    update, write row ``idx[1]`` in place.

    ``ring``: (K, Dp) in ring dtype (fp32 or bf16), Dp a
    :func:`padded_width` multiple; ``s``: (Dp,) fp32 optimizer state or
    None (sgd); ``res``: (Dp,) fp32 error-feedback residue or None (fp32
    ring); ``g``: (c, Dp) fp32; ``coef``/``lrs``: (c,); ``idx``: (2,)
    int32 [prev, slot].  Returns the updated (ring, s, res)."""
    if not spec.kernel_supported:
        raise ValueError(f"{spec.optimizer!r} has no kernel path")
    if interpret is None:
        interpret = default_interpret()
    _count_build(interpret)

    K, Dp = ring.shape
    c = g.shape[0]
    if row_block is None:
        row_block = row_block_for(Dp)
    if Dp % (row_block * LANES):
        raise ValueError(f"ring width {Dp} is not a multiple of the "
                         f"{row_block}x{LANES} tile; pad via padded_width()")
    rows = Dp // LANES
    grid = (rows // row_block,)
    stateful, ef = s is not None, res is not None

    with jax.named_scope("replay_ring.to_tiles"):
        ringt = ring.reshape(K, rows, LANES)
        gt = g.reshape(c, rows, LANES)
        st = s.reshape(rows, LANES) if stateful else None
        rt = res.reshape(rows, LANES) if ef else None
    coef2 = coef.reshape(c, 1).astype(jnp.float32)
    lrs2 = lrs.reshape(c, 1).astype(jnp.float32)

    vec = pl.BlockSpec((c, 1), lambda i, idx: (0, 0))
    row = pl.BlockSpec((row_block, LANES), lambda i, idx: (i, 0))
    ring_in = pl.BlockSpec((1, row_block, LANES),
                           lambda i, idx: (idx[0], i, 0))
    ring_out = pl.BlockSpec((1, row_block, LANES),
                            lambda i, idx: (idx[1], i, 0))
    g_spec = pl.BlockSpec((c, row_block, LANES), lambda i, idx: (0, i, 0))

    operands = [coef2, lrs2, ringt]
    in_specs = [vec, vec, ring_in]
    out_shape = [jax.ShapeDtypeStruct(ringt.shape, ringt.dtype)]
    out_specs = [ring_out]
    # scalar prefetch counts as input 0, so the ring is input index 3
    aliases = {3: 0}
    if stateful:
        aliases[len(operands) + 1] = len(out_shape)
        operands.append(st)
        in_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(st.shape, st.dtype))
        out_specs.append(row)
    if ef:
        aliases[len(operands) + 1] = len(out_shape)
        operands.append(rt)
        in_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(rt.shape, rt.dtype))
        out_specs.append(row)
    operands.append(gt)
    in_specs.append(g_spec)

    kernel = functools.partial(_apply_kernel, spec=spec, mode=mode, c=c,
                               stateful=stateful, ef=ef)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="replay_ring_apply",
    )(idx.astype(jnp.int32), *operands)
    return _from_tiles(out, K, Dp, stateful, ef)


def ring_apply_whatif(ring: jax.Array, s: Optional[jax.Array],
                      res: Optional[jax.Array], a: jax.Array,
                      wstar: jax.Array, coef: jax.Array, lrs: jax.Array,
                      idx: jax.Array, *, spec: UpdateSpec,
                      row_block: Optional[int] = None,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, Optional[jax.Array],
                                 Optional[jax.Array]]:
    """ONE fused ring event with in-kernel gradients g_j = a⊙(w_ts_j − w*).

    ``idx``: (2 + c,) int32 [prev, slot, ts_0 … ts_{c-1}].  ``a``/``wstar``:
    (Dp,) fp32 (zero-padded — padded a makes padded gradients zero, so the
    pad stays inert).  Combine mode only; requires K ≥ 2 (the engine falls
    back to the streamed jnp twin for K = 1).  The ring is one operand,
    aliased to the returned ring: the event writes its slot row into the
    caller's buffer."""
    if not spec.kernel_supported:
        raise ValueError(f"{spec.optimizer!r} has no kernel path")
    if ring.shape[0] < 2:
        raise ValueError("whatif kernel needs K >= 2 (slot row must not be "
                         "a pulled row); use the jnp twin for K = 1")
    if interpret is None:
        interpret = default_interpret()
    _count_build(interpret)

    K, Dp = ring.shape
    c = idx.shape[0] - 2
    if row_block is None:
        row_block = row_block_for(Dp)
    if Dp % (row_block * LANES):
        raise ValueError(f"ring width {Dp} is not a multiple of the "
                         f"{row_block}x{LANES} tile; pad via padded_width()")
    rows = Dp // LANES
    stateful, ef = s is not None, res is not None

    with jax.named_scope("replay_ring.to_tiles"):
        ringt = ring.reshape(K, rows, LANES)
        at = a.reshape(rows, LANES)
        wt = wstar.reshape(rows, LANES)
        st = s.reshape(rows, LANES) if stateful else None
        rt = res.reshape(rows, LANES) if ef else None
    coef2 = coef.reshape(c, 1).astype(jnp.float32)
    lrs2 = lrs.reshape(c, 1).astype(jnp.float32)

    nb = rows // row_block
    vec = pl.BlockSpec((c, 1), lambda i, j, idx: (0, 0))
    row = pl.BlockSpec((row_block, LANES), lambda i, j, idx: (i, 0))
    ring_ts = pl.BlockSpec((1, row_block, LANES),
                           lambda i, j, idx: (idx[2 + j], i, 0))

    operands = [coef2, lrs2, ringt, at, wt]
    in_specs = [vec, vec, ring_ts, row, row]
    out_shape = [jax.ShapeDtypeStruct(ringt.shape, ringt.dtype)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    aliases = {3: 0}          # scalar prefetch is input 0: the ring is 3
    if stateful:
        aliases[len(operands) + 1] = len(out_shape)
        operands.append(st)
        in_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(st.shape, st.dtype))
        out_specs.append(row)
    if ef:
        aliases[len(operands) + 1] = len(out_shape)
        operands.append(rt)
        in_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(rt.shape, rt.dtype))
        out_specs.append(row)

    tile = (2, row_block, LANES)
    kernel = functools.partial(_whatif_kernel, spec=spec, c=c, nb=nb,
                               rb=row_block, stateful=stateful, ef=ef)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb, c),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((row_block, LANES), jnp.float32),
                            pltpu.VMEM(tile, ringt.dtype),
                            pltpu.VMEM(tile, ringt.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the row copies run ahead across grid steps: keep the grid in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="replay_ring_whatif",
    )(idx.astype(jnp.int32), *operands)
    return _from_tiles(out, K, Dp, stateful, ef)

