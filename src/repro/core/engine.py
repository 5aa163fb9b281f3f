"""Replay pass of the compiled PS simulator (DESIGN.md §4).

Phase 2 of the trace/replay split: given the :class:`ArrivalTrace` a
schedule pass produced (``core/trace.py``), execute every update event in
ONE compiled ``jax.lax.scan`` instead of the legacy per-arrival Python loop
(one un-jitted ``grad_fn`` dispatch and one host→device optimizer
round-trip per gradient).

The staleness semantics — each gradient is computed against exactly the
weights its learner pulled — are preserved with a **device-resident weight
ring buffer**: a (K, D) fp32 buffer of the last K parameter snapshots in
the ``optim.flatten`` layout, where ``K = trace.max_staleness + 1`` (the
trace knows its own bound; n-softsync keeps it at ~2n, Fig. 4).  Snapshot
of timestamp ``ts`` lives in row ``ts % K``; event j gathers its c source
rows, unflattens them, computes the c gradients with a vmapped ``grad_fn``,
and applies ONE fused multi-gradient event through the unified subsystem —
``repro.optim.apply_event_flat`` on the flat buffers (the jnp twin of the
Pallas ``ps_update`` tile; pytree ``apply_update_tree`` for adamw), in
``combine`` or ``sequential`` mode per the trace's LR policy — before
writing the new snapshot to row ``(j+1) % K``.  The row being overwritten
belongs to timestamp j+1−K, which no later event can reference — σ would
exceed the trace's own max.  The ring keeps fp32 master weights; the final
parameters are cast back to their original dtypes on exit.

Oracle: the legacy loop in ``core/simulator.py``; equivalence on identical
traces is pinned by ``tests/test_trace_engine.py`` (EXPERIMENTS.md §Sim).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import optim, telemetry
from repro.config import RunConfig
from repro.core.lr_policies import resolve_trace_lrs
from repro.core.protocols import init_ps_state
from repro.core.simulator import SimResult
from repro.core.topology import Topology
from repro.core.trace import ArrivalTrace, PlacementPlan, placement_plan
from repro.kernels.ps_update import LANES
from repro.launch import mesh as mesh_lib
from repro.optim import flatten
from repro.optim.spec import quantize

# names in a profile: the single-device replay scan's scope, and the
# prefix of the segment loop's host spans (:func:`_run_segments`)
SCAN_SCOPE = "engine.replay.scan"
SPAN = "repro.engine.replay"
# ring rows handed to the what-if kernel: slots (c per event and shard)
# and the distinct rows among them
PULL_SLOTS = "replay_ring.pull_slots"
PULL_ROWS = "replay_ring.pull_rows"
# what-if scans built (traced) with the ring carried in the kernel's tiles
TILED_CARRY_BUILDS = "engine.replay.tiled_carry_builds"

# cross-shard pull assembly for the SPMD replay (DESIGN.md §13): one fused
# all_gather over the "ps" axis, or the equivalent S−1 neighbor-ppermute
# ring exchange (bitwise-equal data movement; slower on emulated devices)
SPMD_ASSEMBLIES = ("all_gather", "ppermute")


@functools.lru_cache(maxsize=32)
def _unflatten_jit(layout: flatten.TreeLayout) -> Callable:
    """Jitted (D,) → pytree restore (eager slice-per-leaf costs ~ms/call)."""
    return jax.jit(lambda flat: flatten.flat_to_tree(flat, layout))


def _unstack_tree(tree, c: int):
    """Tree with a leading (c,) axis → list of c pytrees (c is static)."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(c)]


def _lanes(width: int, tiled: bool) -> tuple:
    """The carry shape of one ring row: flat ``(width,)``, or the Pallas
    kernels' ``(width / 128, 128)`` tiles.  A bf16 (K, width) ring is laid
    out across K, so reshaping it to the kernel's tiles and back copies the
    whole ring; carried in the tiles, the kernel updates it in place."""
    return (width // LANES, LANES) if tiled else (width,)


def _flat_views(carry, K: int):
    """The (K, W) ring and (W,) state / residue of a ``(ring, state,
    residue)`` carry held in any :func:`_lanes` (with or without a leading
    device axis of 1): reshapes that fold into the kernels' own."""
    ring, s, res = carry
    return (ring.reshape(K, -1), None if s is None else s.reshape(-1),
            None if res is None else res.reshape(-1))


def _carry_like(carry, ring, s, res):
    """Flat ``(ring, state, residue)`` back in the shapes of ``carry``."""
    return tuple(None if v is None else v.reshape(c.shape)
                 for c, v in zip(carry, (ring, s, res)))


# the carry of the fused and Pallas bodies, and the weights of one of its
# ring rows, each made by one program: run op by op, their steps leave
# model-sized intermediates queued on the device
@functools.partial(jax.jit, static_argnames=("lanes", "K", "ring_dtype"))
def _ring_carry(params, state, *, lanes: tuple, K: int, ring_dtype: str):
    """``(ring, state, residue)``: K copies of the params quantized to the
    ring dtype, the optimizer state (or None) and, for a bf16 ring, the
    float32 residue of the quantization, each row zero-padded to the
    ``lanes`` width."""
    width = int(np.prod(lanes))
    flat = flatten.pad_flat(flatten.tree_to_flat(params), width)
    flat = flat.reshape(lanes)
    q0 = quantize(flat, jnp.bfloat16 if ring_dtype == "bf16"
                  else jnp.float32)
    ring = jnp.broadcast_to(q0[None], (K,) + lanes)
    res = flat - q0.astype(jnp.float32) if ring_dtype == "bf16" else None
    if state is not None:
        state = flatten.pad_flat(flatten.tree_to_flat(state), width)
        state = state.reshape(lanes)
    return ring, state, res


@functools.partial(jax.jit, static_argnames=("layout",))
def _ring_row_params(ring, res, i, *, layout: flatten.TreeLayout):
    """The weights of ring row ``i`` (plus the residue, if any) as the
    params pytree."""
    row = jax.lax.dynamic_index_in_dim(ring, i, keepdims=False)
    row = row.astype(jnp.float32)
    if res is not None:
        row = row + res
    return flatten.flat_to_tree(row.reshape(-1)[:layout.total], layout)


def _rows(ring: jax.Array, idx: jax.Array) -> jax.Array:
    """``ring[idx]`` for a static-length index vector, as one dynamic slice
    per row: a TPU compiles a vector-index row gather in time that grows
    with the row width (minutes at D = 2^26), a dynamic slice in constant
    time.  Pure data movement, so bitwise the gather."""
    return jnp.stack([ring[idx[i]] for i in range(idx.shape[0])])


@functools.lru_cache(maxsize=32)
def _make_scan_fn(grad_fn, spec, mode: str, c: int, K: int,
                  layout: flatten.TreeLayout, batched: bool = False,
                  shards: int = 1, group_size: int = 1,
                  masked: bool = False, member_masked: bool = False,
                  ring_impl: str = "stock", ring_dtype: str = "fp32",
                  whatif: bool = False, publish: bool = False):
    """The jitted scan over update events — cached per static config so
    repeated replays (benchmark/sweep loops) reuse the compiled program;
    the LRU bound keeps long-lived processes from pinning every grad_fn
    closure + executable ever seen.

    Kernel-supported optimizers (sgd / momentum / adagrad) never leave the
    flat domain: the carry is just the (K, D) ring plus the (D,) state
    vector, gradients are flattened once per event, and the apply is ONE
    fused ``optim.apply_event_flat`` over the whole model — the scan body
    is the jnp twin of the Pallas ``ps_update`` tile.  adamw (scalar step
    counter, no kernel path) falls back to the pytree apply.

    Topology (DESIGN.md §6) — the trivial (1, 1) case compiles the exact
    pre-topology body:

    * ``shards`` = S > 1: the carry ring becomes S per-shard (K, Dp) rings
      stacked as (S, K, Dp); each event gathers every slot's weight vector
      from per-shard rows (``x["ts"]`` is (c, S) — inconsistent reads) and
      applies the fused event per shard slice via the vmapped
      ``optim.apply_event_sharded``.
    * ``group_size`` = gs > 1: each slot aggregates gs member gradients
      computed against the slot's pulled weights (the group pulls once and
      broadcasts); minibatches carry a (c, gs, …) leading shape and the
      member gradients are averaged before the apply.

    Elastic membership (DESIGN.md §7) stays branch-free: ``masked=True``
    reads each event's combine coefficients from the trace
    (``x["coef"]``, zero on cancelled slots — the schedule pass resolved
    who committed) instead of the static 1/c; ``member_masked=True`` does
    the same for the group-member average (``x["mcoef"]``: a crashed
    member's gradient gets weight 0, survivors renormalize).  The scan
    body is otherwise identical — cancelled work is computed and then
    folded with coefficient 0, which XLA treats as data, not control flow.

    ``batched=True`` returns ``jit(vmap(scan))``: the identical per-event
    body mapped over a leading batch axis of B independent grid points —
    one device program executes a whole multi-seed/multi-config sweep cell
    (``replay_batch``, trivial topology only).  The ring-buffer *write*
    position (and the previous snapshot's row) depend only on the step
    index and the shared K, so ``prev``/``slot`` stay unbatched
    (``in_axes=None``): the per-event ring update remains a
    dynamic-update-slice at a common row instead of a per-lane scatter —
    the difference between the batched scan keeping the (B, K, D) ring in
    place and copying it every event.  Only ``ts`` (which snapshots each
    lane's c gradients read), ``lrs``, and the minibatches are per-lane.

    Ring scan bodies (DESIGN.md §12) — ``ring_impl`` selects how a
    kernel-supported event executes:

    * ``stock``  — the original gather → ``apply_event_flat`` →
      ``.at[slot].set`` chain (the bitwise baseline; adamw always lands
      here via its pytree body).
    * ``fused``  — ``optim.apply_event_ring``: the same math phrased as
      ONE fused read-update-write over a flat (K, Dp) ring (bitwise-equal
      to stock at fp32), plus the bf16 error-feedback residue when
      ``ring_dtype == "bf16"``.  Sharded traces unify onto the flat padded
      buffer: the per-shard structure only matters for the *gather* (each
      slot assembles per-shard rows at per-shard timestamps); the update
      itself is elementwise, so one fused event over the (K, S·Dp) buffer
      computes the same values as the stacked per-shard applies.  Bitwise
      it matches the flat ``apply_event_flat`` reference — the *stock
      sharded* body runs the combine on (S, c, Dp) operands, which XLA
      may fuse with different rounding (~1 ulp/event), so sharded fused
      vs stock agree to fp32 accumulation tolerance only.
    * ``pallas`` — the ``kernels/replay_ring`` megakernel: one pallas_call
      per event with scalar-prefetched ring rows and in-place aliased
      writes (interpret mode off-TPU).

    For non-stock impls the carry is ``(ring, state, residue)`` and the
    jitted scan **donates** it (``donate_argnums=0``): the K·D ring stops
    being double-buffered across scan dispatches.  ``whatif=True`` swaps
    the gradient stage for the in-kernel/streamed closed-form gradients
    (``g = a ⊙ (w_pulled − w*)``; combine mode, trivial topology): the
    scan fn then takes ``(carry, xs, (a, w*))`` and no minibatches ride
    the trace at all.  The what-if body takes its carry and (a, w*) in
    any :func:`_lanes`; ``replay`` hands the Pallas kernel its own tiles,
    which it then updates in place (counted once per traced scan in
    ``TILED_CARRY_BUILDS``).
    """
    coef = jnp.full((c,), 1.0 / c, jnp.float32)
    D = layout.total
    Dp = -(-D // shards)                  # Topology.padded_width(D)

    def coef_of(x):
        return x["coef"] if masked else coef

    def slot_weights(ring, x):
        """The (c, D) weight vectors the slots' gradients are computed
        against: one ring gather, or the per-shard assembly (each slot
        concatenates its S pulled slices — possibly different timestamps:
        weights that never existed as one consistent version, §3.1)."""
        if shards == 1:
            return ring[x["ts"]]      # (c, D) gather; ts pre-wrapped mod K
        # ring: (S, K, Dp); x["ts"]: (c, S) → per-shard (S, c, Dp) gather
        parts = jax.vmap(lambda r, t: r[t], in_axes=(0, 1))(ring, x["ts"])
        return flatten.shard_unpack(jnp.moveaxis(parts, 0, 1), D)

    def gradients_of(pulled_flat, x):
        """vmapped grad_fn at the (c, D) fp32 pulled weights, cast to fp32
        ONCE right after the backward pass — the member-mean/flatten
        stages downstream see fp32 and their casts are no-ops (one cast
        per event instead of one per reduction on the hot loop)."""
        pulled = flatten.batched_flat_to_tree(pulled_flat, layout)
        if group_size == 1:
            g = jax.vmap(grad_fn)(pulled, x["batch"])
            return jax.tree.map(lambda a: a.astype(jnp.float32), g)
        # member gradients share the slot's pulled weights; average the
        # (c, gs) gradient stack over the group axis (Eq. 3 locally) —
        # weighted by the survivor mask when membership is elastic (a
        # group with a crashed member aggregates over survivors)
        g = jax.vmap(lambda p, b: jax.vmap(lambda bb: grad_fn(p, bb))(b))(
            pulled, x["batch"])
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        if member_masked:
            mc = x["mcoef"]                              # (c, gs)
            def wmean(a):
                w = mc.reshape(mc.shape + (1,) * (a.ndim - 2))
                return (a * w).sum(axis=1)
            return jax.tree.map(wmean, g)
        return jax.tree.map(lambda a: a.mean(axis=1), g)

    def gradients(ring, x):
        return gradients_of(slot_weights(ring, x), x)

    fused = ring_impl in ("fused", "pallas") and spec.kernel_supported
    if fused:
        from repro.kernels import replay_ring   # lazy: breaks import cycle

        def slot_weights_flat(ring, x):
            """Fused-impl gather off the flat (K, Dp) ring (padding and —
            with a bf16 ring — quantization stripped): the (c, D) fp32
            weights the slot gradients see.  Sharded traces view the
            buffer as (K, S, Dp) rows for the per-shard-timestamp
            assembly; the flat layout is the shard rows concatenated, so
            this is bitwise the stock per-shard gather."""
            if shards == 1:
                return _rows(ring, x["ts"])[..., :D].astype(jnp.float32)
            # slot i, shard j: row ts[i, j], columns [j·Dp, (j+1)·Dp)
            parts = [jnp.concatenate([
                jax.lax.dynamic_slice(ring, (x["ts"][i, j], j * Dp),
                                      (1, Dp))[0]
                for j in range(shards)]) for i in range(c)]
            return jnp.stack(parts)[:, :D].astype(jnp.float32)

        if whatif:
            def event(aux, carry, x):
                ring, s, res = _flat_views(carry, K)
                a, wstar = (v.reshape(-1) for v in aux)
                if ring_impl == "pallas" and K >= 2:
                    idx = jnp.concatenate(
                        [jnp.stack([x["prev"], x["slot"]]), x["ts"]])
                    ring, s, res = replay_ring.ring_apply_whatif(
                        ring, s, res, a, wstar, coef_of(x), x["lrs"], idx,
                        spec=spec)
                else:
                    ring, s, res = optim.apply_event_ring_whatif(
                        spec, ring, s, res, a, wstar, x["ts"], coef_of(x),
                        x["lrs"], x["prev"], x["slot"])
                return _carry_like(carry, ring, s, res), None
        else:
            def event(carry, x):
                ring, s, res = carry
                g = flatten.batched_tree_to_flat(
                    gradients_of(slot_weights_flat(ring, x), x))
                gp = flatten.pad_flat(g, ring.shape[1])
                if ring_impl == "pallas":
                    idx = jnp.stack([x["prev"], x["slot"]])
                    ring, s, res = replay_ring.ring_apply(
                        ring, s, res, gp, coef_of(x), x["lrs"], idx,
                        spec=spec, mode=mode)
                else:
                    ring, s, res = optim.apply_event_ring(
                        spec, ring, s, res, gp, coef_of(x), x["lrs"],
                        x["prev"], x["slot"], mode)
                return (ring, s, res), None
    elif spec.kernel_supported and shards > 1:
        def event(carry, x):
            ring, s = carry
            g = flatten.batched_tree_to_flat(gradients(ring, x))
            gp = flatten.shard_pack_grads(g, shards, Dp)     # (S, c, Dp)
            w, s = optim.apply_event_sharded(
                spec, ring[:, x["prev"]], s, gp, coef_of(x), x["lrs"], mode)
            return (ring.at[:, x["slot"]].set(w), s), None
    elif spec.kernel_supported:
        def event(carry, x):
            ring, s = carry
            g = flatten.batched_tree_to_flat(gradients(ring, x))
            w, s = optim.apply_event_flat(spec, ring[x["prev"]], s, g,
                                          coef_of(x), x["lrs"], mode)
            return (ring.at[x["slot"]].set(w), s), None
    else:
        def event(carry, x):
            ring, (params, opt_state) = carry
            grads = _unstack_tree(gradients(ring, x), c)
            params, opt_state = optim.apply_update_tree(
                spec, params, opt_state, grads, coef_of(x), x["lrs"], mode)
            ring = ring.at[x["slot"]].set(flatten.tree_to_flat(params))
            return (ring, (params, opt_state)), None

    if publish:
        # serving lane (DESIGN.md §14): capture each *published* weight
        # version as the scan writes it — the ring row is read at its birth
        # instant, which is exactly what every publication policy resolves
        # to (a ring read always returns the newest row; the host-side
        # schedule_serving already mapped refreshes/requests to versions).
        # x["pub"] indexes the snapshot buffer riding the carry: the
        # published-version position for rows some replica serves, or the
        # inert dummy row (branch-free — unpublished rows write there).
        # Snapshots store the raw ring row in fp32: with a bf16 ring the
        # published weights are the quantized snapshots, residue excluded
        # (the serving tolerance policy — §14).
        if batched or whatif:
            raise ValueError(
                "publish capture supports the single-lane staged-gradient "
                "scan only (replay_batch and the what-if replay reject "
                "serving traces upstream)")
        base_event = event

        def event(carry, x):
            core, snaps = carry
            core, _ = base_event(core, x)
            row = core[0][x["slot"]].astype(jnp.float32)
            return (core, snaps.at[x["pub"]].set(row)), None

    # single lane: unroll a few events per while-loop iteration (the body
    # is tiny, loop bookkeeping is a measurable fraction).  The batched
    # body is B× wider — unrolling only bloats its code and measured ~25%
    # slower — and the what-if body streams O(D) temporaries whose
    # lifetimes unrolling would overlap, so both stay rolled.
    unroll = 1 if (batched or whatif) else 8

    # the scope names the loop's ops in a profile (SCAN_SCOPE)
    if whatif:
        def run(carry, xs, aux):
            if carry[0].ndim == 3:               # the ring in _lanes tiles
                telemetry.count(TILED_CARRY_BUILDS)
            with jax.named_scope(SCAN_SCOPE):
                return jax.lax.scan(functools.partial(event, aux), carry,
                                    xs, unroll=unroll)[0]
        return jax.jit(run, donate_argnums=0)

    def run(carry, xs):
        with jax.named_scope(SCAN_SCOPE):
            return jax.lax.scan(event, carry, xs, unroll=unroll)[0]

    if batched:
        axes = {"ts": 0, "prev": None, "slot": None, "lrs": 0, "batch": 0}
        if masked:
            axes["coef"] = 0
        vrun = jax.vmap(run, in_axes=(0, axes))
        return (jax.jit(vrun, donate_argnums=0) if fused else jax.jit(vrun))
    # non-stock carries are donated: the ring/state/residue buffers are
    # updated in place across scan dispatches instead of double-buffered
    return jax.jit(run, donate_argnums=0) if fused else jax.jit(run)


def _spmd_local_width(D: int, shards: int, ring_impl: str) -> int:
    """Per-"ps"-device ring row width: the shard slice Dp = ⌈D/S⌉, padded
    to the megakernel tile multiple when the local body is Pallas."""
    Dp = -(-D // shards)
    if ring_impl == "pallas":
        from repro.kernels import replay_ring   # lazy: import cycle
        return replay_ring.padded_width(Dp)
    return Dp


@functools.lru_cache(maxsize=32)
def _make_spmd_scan_fn(grad_fn, spec, mode: str, c: int, K: int,
                       layout: flatten.TreeLayout, plan: PlacementPlan,
                       xs_keys: tuple, group_size: int = 1,
                       masked: bool = False, member_masked: bool = False,
                       ring_impl: str = "fused", ring_dtype: str = "fp32",
                       whatif: bool = False, assembly: str = "all_gather"):
    """The replay scan shard_mapped over a ``(ps, learner)`` device mesh —
    the distributed twin of :func:`_make_scan_fn` (DESIGN.md §13).

    Placement: PS shard s's (K, Wl) ring slice (plus its optimizer-state /
    residue rows) lives on "ps"-device s; learner-group device l owns the
    contiguous slot block [l·cl, (l+1)·cl) of every update's c gradient
    slots.  The per-event body then runs the paper's PS protocol as real
    collectives:

    * **pull** — each PS device gathers its own ring rows at its own
      per-shard timestamps (the inconsistent-read column ``ts[:, s]``) and
      an ``all_gather`` over "ps" assembles the (c, D) pulled weights on
      every device (``assembly="ppermute"`` swaps in the bitwise-equal
      S−1-hop neighbor ring exchange, ``optim.ring_all_gather``);
    * **push** — combine mode reduces each learner device's local-slot
      partial of ĝ = Σ coef_j·g_j with ONE ``psum`` over "learner"
      (``optim.combine_spmd``); sequential mode ``all_gather``s the slot
      gradients over "learner" instead (every event needs every slot);
    * **update** — each PS device applies the fused/Pallas ring body
      (``optim.apply_event_ring`` / ``replay_ring.ring_apply``) to its own
      slice of ĝ — elementwise math, so per-shard applies are exactly the
      shard slices of the single-device apply.

    Equivalence to ``placement="single"`` (pinned by tests/test_spmd.py;
    tolerance policy in DESIGN.md §13): the **what-if** body is bitwise
    against single-device replay, any S — shard-local closed-form
    gradients, no reduction to reorder — and ``assembly="ppermute"`` is
    bitwise against ``"all_gather"``.  The **staged-gradient** bodies
    track single-device replay to ~1 ulp per event even at L = 1: the
    math is op-for-op identical, but XLA fuses the combine/update chain
    differently (fma contraction) inside the shard_map body, and L > 1
    additionally reorders the fp32 combine reduction through the psum's
    partial-sum tree.  Elastic masks stay branch-free: the
    trace coefficients ride in replicated and each device slices its
    block, so cancelled slots fold with weight 0 exactly as on one device.

    The gradient stage intentionally mirrors ``_make_scan_fn.gradients_of``
    op-for-op (vmapped grad_fn → ONE fp32 cast → member mean) — the
    duplication is what keeps both paths' pins independent.  What-if
    replay needs no learner axis at all (closed-form gradients are
    shard-local); callers plan it with L = 1 and the body never touches
    "learner".
    """
    S, L = plan.shards, plan.learners
    cl = c // L
    D = layout.total
    Dp = -(-D // S)
    Wl = _spmd_local_width(D, S, ring_impl)
    from repro.kernels import replay_ring       # lazy: import cycle
    from repro.launch import sharding as sharding_lib

    if assembly not in SPMD_ASSEMBLIES:
        raise ValueError(f"unknown spmd_assembly {assembly!r}: expected "
                         f"one of {SPMD_ASSEMBLIES}")
    mesh = mesh_lib.make_sim_mesh(S, L)
    coef = jnp.full((c,), 1.0 / c, jnp.float32)

    def coef_of(x):
        return x["coef"] if masked else coef

    def assemble(mine):
        if assembly == "ppermute":
            return optim.ring_all_gather(mine, "ps", S)
        return jax.lax.all_gather(mine, "ps", axis=0)

    def pulled_weights(rl, x):
        """(c, D) fp32 pulled weights, assembled from every shard's local
        gather (pallas pad stripped per shard) — the same moveaxis/reshape
        assembly as the single-device fused ``slot_weights_flat``."""
        mine = _rows(rl, x["ts"][:, 0])[:, :Dp]       # (c, Dp) local rows
        parts = assemble(mine)                        # (S, c, Dp)
        full = jnp.moveaxis(parts, 0, 1).reshape(c, S * Dp)
        return full[:, :D].astype(jnp.float32)

    def local_gradients(w_full, x, lo):
        """(cl, D) fp32 gradients of this learner device's slot block —
        op-for-op the single-device ``gradients_of`` on the block."""
        wl = jax.lax.dynamic_slice_in_dim(w_full, lo, cl, 0)
        pulled = flatten.batched_flat_to_tree(wl, layout)
        if group_size == 1:
            g = jax.vmap(grad_fn)(pulled, x["batch"])
            g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
            return flatten.batched_tree_to_flat(g)
        g = jax.vmap(lambda p, b: jax.vmap(lambda bb: grad_fn(p, bb))(b))(
            pulled, x["batch"])
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        if member_masked:
            mc = jax.lax.dynamic_slice_in_dim(x["mcoef"], lo, cl, 0)

            def wmean(a):
                w = mc.reshape(mc.shape + (1,) * (a.ndim - 2))
                return (a * w).sum(axis=1)
            g = jax.tree.map(wmean, g)
        else:
            g = jax.tree.map(lambda a: a.mean(axis=1), g)
        return flatten.batched_tree_to_flat(g)

    def shard_slice(vec, si):
        """(…, D) → this PS device's (…, Dp) slice (last shard zero-padded,
        matching the flat-ring layout exactly)."""
        vp = flatten.pad_flat(vec, S * Dp)
        return jax.lax.dynamic_slice_in_dim(vp, si * Dp, Dp, vp.ndim - 1)

    # this device's carry block is (1, K, *lanes) / (1, *lanes), lanes
    # from _lanes: the Pallas body keeps the kernel's tiles
    if whatif:
        def event(aux, carry, x):
            rl, sl, resl = _flat_views(carry, K)
            a_l, ws_l = (v.reshape(-1) for v in aux)
            ts_col = x["ts"][:, 0]
            if ring_impl == "pallas" and K >= 2:
                idx = jnp.concatenate(
                    [jnp.stack([x["prev"], x["slot"]]), ts_col])
                rl, sl, resl = replay_ring.ring_apply_whatif(
                    rl, sl, resl, a_l, ws_l, coef_of(x), x["lrs"], idx,
                    spec=spec)
            else:
                rl, sl, resl = optim.apply_event_ring_whatif(
                    spec, rl, sl, resl, a_l, ws_l, ts_col, coef_of(x),
                    x["lrs"], x["prev"], x["slot"])
            return _carry_like(carry, rl, sl, resl), None
    else:
        def event(carry, x):
            rl, sl, resl = _flat_views(carry, K)
            w = pulled_weights(rl, x)
            lo = jax.lax.axis_index("learner") * cl
            g = local_gradients(w, x, lo)             # (cl, D)
            si = jax.lax.axis_index("ps")
            if mode == "combine":
                coef_l = jax.lax.dynamic_slice_in_dim(coef_of(x), lo, cl, 0)
                ghat = optim.combine_spmd(g, coef_l, "learner")   # (D,)
                gp = flatten.pad_flat(shard_slice(ghat, si), Wl)[None]
                cvec = jnp.ones((1,), jnp.float32)
                lvec = x["lrs"][:1]
            else:
                g_all = jax.lax.all_gather(g, "learner", axis=0, tiled=True)
                gp = flatten.pad_flat(shard_slice(g_all, si), Wl)  # (c, Wl)
                cvec = coef_of(x)
                lvec = x["lrs"]
            if ring_impl == "pallas":
                idx = jnp.stack([x["prev"], x["slot"]])
                rl, sl, resl = replay_ring.ring_apply(
                    rl, sl, resl, gp, cvec, lvec, idx, spec=spec, mode=mode)
            else:
                rl, sl, resl = optim.apply_event_ring(
                    spec, rl, sl, resl, gp, cvec, lvec, x["prev"],
                    x["slot"], mode)
            return _carry_like(carry, rl, sl, resl), None

    carry_specs = sharding_lib.spmd_carry_specs()
    xs_specs = sharding_lib.spmd_xs_specs(xs_keys)
    if whatif:
        def run(carry, xs, aux):
            return jax.lax.scan(functools.partial(event, aux), carry, xs)[0]
        smapped = mesh_lib.shard_map(
            run, mesh,
            in_specs=(carry_specs, xs_specs, sharding_lib.spmd_aux_specs()),
            out_specs=carry_specs)
    else:
        def run(carry, xs):
            return jax.lax.scan(event, carry, xs)[0]
        smapped = mesh_lib.shard_map(run, mesh,
                                     in_specs=(carry_specs, xs_specs),
                                     out_specs=carry_specs)
    return jax.jit(smapped, donate_argnums=0)


def _whatif_aux(coeffs: Callable, base: jax.Array, width: int, real: int,
                D: int):
    """The what-if kernel's (a, w*) operands as (rows, width) fp32 rows:
    row r, column j holds flat position ``base[r] + j``.  Padding (column
    ≥ ``real`` or position ≥ D) gets a = 0, so its gradients are zero and
    the pad stays inert.  ``base`` is a traced argument, so every layout
    evaluates ``coeffs`` at run time with the same code — never folded to
    a compile-time constant, which rounds differently."""
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    pos = base[:, None] + col
    valid = (col < real) & (pos < D)
    a, wstar = coeffs(pos)
    return (jnp.where(valid, a, 0.0).astype(jnp.float32),
            jnp.where(valid, wstar, 0.0).astype(jnp.float32))


def _materialize_batches(trace: ArrivalTrace, batch_fn: Callable):
    """Evaluate ``batch_fn(learner, minibatch_idx)`` for every trace slot
    and stack into a pytree with leading (steps, c) axes — (steps, c, gs)
    with learner groups: slot (j, i) aggregates the gs member minibatches
    ``batch_fn(member, push_counter)``.  Stacking happens host-side so the
    whole trace's data moves to device in ONE transfer per leaf (batch_fns
    returning numpy avoid per-minibatch device_puts)."""
    members = trace.member_learners()          # None when ungrouped
    rows = []
    for j in range(trace.steps):
        if members is None:
            slots = [batch_fn(int(trace.learner[j, i]),
                              int(trace.mb_index[j, i]))
                     for i in range(trace.c)]
        else:
            slots = [jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *[batch_fn(int(m), int(trace.mb_index[j, i]))
                  for m in members[j, i]])
                for i in range(trace.c)]
        rows.append(jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *slots))
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *rows)


def _check_trace(trace: ArrivalTrace, run: RunConfig) -> None:
    """A trace is only valid for the RunConfig that scheduled it."""
    if (trace.protocol != run.protocol
            or trace.n_learners != run.n_learners
            or trace.c != run.gradients_per_update):
        raise ValueError(
            f"trace ({trace.protocol}, λ={trace.n_learners}, c={trace.c}) "
            f"was not scheduled from this RunConfig ({run.protocol}, "
            f"λ={run.n_learners}, c={run.gradients_per_update})")
    topo = Topology.from_run(run)
    if trace.topology != topo:
        raise ValueError(
            f"trace topology ({trace.topology}) disagrees with this "
            f"RunConfig's ({topo}) — reschedule the trace for this config")
    # the trace bakes policy-resolved LRs in; re-resolving from this run's
    # policy must reproduce them, or the caller is silently sweeping
    # base_lr/lr_policy on a stale trace
    want_lrs, want_mode = resolve_trace_lrs(run, trace.pulled_ts)
    if trace.mode != want_mode or not np.allclose(trace.lrs, want_lrs):
        raise ValueError(
            f"trace LRs/mode ({trace.mode}) disagree with this RunConfig's "
            f"lr_policy={run.lr_policy!r}/base_lr={run.base_lr} — reschedule "
            f"the trace for this config")
    if (trace.serving is None) != (run.serving is None):
        raise ValueError(
            f"trace {'carries' if trace.serving is not None else 'has no'} "
            f"serving lane but run.serving is "
            f"{'unset' if run.serving is None else 'set'} — reschedule the "
            f"trace for this config")


def _trace_xs(trace: ArrivalTrace, K: int, batch_fn: Optional[Callable],
              batches=None) -> dict:
    """The scan inputs of one trace: ring indices (pre-wrapped mod K),
    per-event LRs, and the whole trace's minibatches — materialized per
    slot via ``batch_fn``, or taken pre-staged from ``batches`` (a pytree
    with leading (steps, c) axes — (steps, c, gs) with learner groups —
    e.g. a problem's vectorized ``stage_minibatches`` output), or omitted
    entirely when both are None (the what-if replay computes closed-form
    gradients in-kernel and never touches data).  With S > 1 PS shards
    ``ts`` carries the (steps, c, S) per-shard pulled rows."""
    steps_idx = np.arange(trace.steps)
    if batches is not None:
        batches = jax.tree.map(jnp.asarray, batches)
    elif batch_fn is not None:
        batches = _materialize_batches(trace, batch_fn)
    ts = (trace.pulled_ts if trace.shard_pulled_ts is None
          else trace.shard_pulled_ts)
    xs = {
        "ts": jnp.asarray(ts % K, jnp.int32),
        "prev": jnp.asarray(steps_idx % K, jnp.int32),
        "slot": jnp.asarray((steps_idx + 1) % K, jnp.int32),
        "lrs": jnp.asarray(trace.lrs, jnp.float32),
    }
    if batches is not None:
        xs["batch"] = batches
    if trace.valid is not None:
        xs["coef"] = jnp.asarray(trace.event_coef())
    if trace.member_valid is not None:
        xs["mcoef"] = jnp.asarray(trace.member_coef())
    return xs


def replay(trace: ArrivalTrace, run: RunConfig, *,
           grad_fn: Optional[Callable] = None,
           init_params,
           batch_fn: Optional[Callable] = None,
           batches=None,
           eval_fn: Optional[Callable] = None,
           eval_every: int = 0,
           flat_grad=None,
           placement: Optional[str] = None,
           spmd_assembly: str = "all_gather",
           serve_batches=None,
           serve_eval_fn: Optional[Callable] = None) -> SimResult:
    """Execute a scheduled trace against real gradients, compiled.

    ``grad_fn(params, batch) -> grads`` must be vmappable (any jit-able JAX
    function is).  Minibatches come from exactly one of ``batch_fn``
    (``(learner_idx, minibatch_idx) -> batch``, evaluated host-side per
    trace slot) or ``batches`` (a pre-staged pytree with leading (steps, c)
    axes — e.g. a problem's vectorized ``stage_minibatches`` output, which
    skips the per-slot Python staging loop entirely; this is where most of
    the single-replay wall clock went before PR 6).

    ``run.ring_impl``/``run.ring_dtype`` select the scan body and ring
    storage (DESIGN.md §12): the default ``auto`` runs the fused megakernel
    path (Pallas on TPU, its bitwise jnp twin elsewhere) with a donated
    carry; ``stock`` forces the pre-megakernel chain.

    ``flat_grad = ("quadratic", coeffs)`` opts into the **what-if
    replay**: ``coeffs(pos) -> (a, w*)`` gives the fp32 curvature and
    target at int32 flat positions of the ``optim.flatten`` layout,
    gradients are computed in-kernel as ``a ⊙ (w_pulled − w*)``, and no
    data is staged
    — peak memory O(K·D_ring + D), which is what makes trace-driven studies
    at ``configs/`` big-model D feasible.  Requires a kernel-supported
    optimizer, combine mode, the trivial topology and a non-stock impl
    (``placement="spmd"`` lifts the topology restriction: closed-form
    gradients are shard-local, so every PS device what-ifs its own slice);
    anything else falls back to the staged-gradient path (so ``batch_fn``/
    ``batches`` must still be provided when those conditions can miss).

    ``placement`` (default ``run.placement``) selects where the scan runs
    (DESIGN.md §13): ``"single"`` is the one-device program above;
    ``"spmd"`` shard_maps it over a ``make_sim_mesh(S, L)`` device mesh —
    per-shard rings on distinct "ps" devices, slot blocks on distinct
    "learner" devices, cross-shard pulls / combine pushes as real
    all_gather/psum (or ppermute, ``spmd_assembly="ppermute"``)
    collectives.  What-if spmd replay is bitwise-equal to single-device;
    staged-gradient paths track it to ~1 ulp/event (XLA fusion inside the
    shard_map body; psum reduction order at L > 1) — see DESIGN.md §13.

    With ``eval_every`` set, the scan runs in eval_every-sized segments;
    a trailing remainder segment (steps % eval_every != 0) has a different
    scan length and compiles a second program — pick eval_every | steps in
    compile-sensitive sweeps.

    **Serving lane** (DESIGN.md §14): a trace scheduled with
    ``run.serving`` set carries a resolved ``ServingTrace``; the scan then
    additionally captures every *published* weight version (a ring-row
    read at the version's birth — branch-free, one extra
    dynamic-update-slice per event) and, post-scan, evaluates each request
    batch against the version that served it.  ``serve_batches`` (a pytree
    with a leading (R,) request axis, e.g. a problem's ``stage_requests``)
    and ``serve_eval_fn(params, request_batch) -> scalar metric`` are then
    required.  A serving trace disables the what-if fast path (the
    staged-gradient scan carries the snapshot buffer); a run *without*
    serving compiles the exact pre-serving program — same scan-fn cache
    entry, bitwise-identical replay.
    """
    _check_trace(trace, run)
    serving = trace.serving
    if serving is not None and (serve_batches is None
                                or serve_eval_fn is None):
        raise ValueError(
            "this trace carries a serving lane: pass serve_batches (a "
            "pytree with a leading (R,) request axis, e.g. "
            "problem.stage_requests(trace.serving, run.serving)) and "
            "serve_eval_fn(params, request_batch) -> scalar metric")
    if serving is None and (serve_batches is not None
                            or serve_eval_fn is not None):
        raise ValueError(
            "serve_batches/serve_eval_fn passed but the trace has no "
            "serving lane — schedule it from a RunConfig with "
            "serving=FleetConfig(...)")
    steps, c = trace.steps, trace.c
    K = trace.max_staleness + 1
    topo = trace.topology
    S, gs = topo.shards, trace.group_size
    spec = optim.spec_from_run(run)
    layout = flatten.layout_of(init_params)
    if S > 1 and not spec.kernel_supported:
        raise ValueError(
            f"{spec.optimizer!r} has no flat event path, so no sharded "
            f"replay (shards={S}); use a kernel-supported optimizer")
    if trace.valid is not None and trace.mode != "combine":
        raise ValueError(
            f"elastic traces replay in 'combine' mode only (cancelled "
            f"slots fold with coefficient 0; sequential optimizer events "
            f"cannot be masked), got mode={trace.mode!r}")

    place = placement if placement is not None else run.placement
    if place == "spmd":
        return _replay_spmd(trace, run, spec=spec,
                            layout=layout, grad_fn=grad_fn,
                            init_params=init_params, batch_fn=batch_fn,
                            batches=batches, eval_fn=eval_fn,
                            eval_every=eval_every, flat_grad=flat_grad,
                            assembly=spmd_assembly)
    if place != "single":
        raise ValueError(f"unknown placement {place!r}: expected "
                         f"'single' or 'spmd'")
    _, opt_state = init_ps_state(run, init_params)

    impl = optim.resolve_ring_impl(run.ring_impl, spec)
    whatif = (flat_grad is not None and impl != "stock"
              and trace.mode == "combine" and S == 1 and gs == 1
              and serving is None)
    if whatif:
        kind = flat_grad[0]
        if kind != "quadratic":
            raise ValueError(f"unknown flat_grad kind {kind!r}; expected "
                             f"('quadratic', coeffs)")
    elif grad_fn is None:
        raise ValueError("grad_fn is required outside the what-if replay")
    elif (batch_fn is None) == (batches is None):
        raise ValueError("pass exactly one of batch_fn / batches")

    scan_fn = _make_scan_fn(None if whatif else grad_fn, spec, trace.mode,
                            c, K, layout, shards=S, group_size=gs,
                            masked=trace.valid is not None,
                            member_masked=trace.member_valid is not None,
                            ring_impl=impl, ring_dtype=run.ring_dtype,
                            whatif=whatif, publish=serving is not None)

    xs = _trace_xs(trace, K, None if whatif else batch_fn,
                   batches=None if whatif else batches)
    if serving is not None:
        xs["pub"] = jnp.asarray(_pub_index(serving, steps), jnp.int32)
    D = layout.total
    Dp = topo.padded_width(D)
    if impl != "stock":
        # (K, width) ring in the ring dtype — sharded traces use the
        # concatenated shard rows (width = S·Dp ≥ D), the Pallas megakernel
        # a row-block tile multiple on top; padding zeros are inert.  With
        # a bf16 ring the fp32 error-feedback residue of the latest row
        # completes the carry; the scan donates all three buffers.  The
        # what-if kernel takes its rows in _lanes tiles, which the carry
        # keeps (the K = 1 fallback and the fused twin run flat).
        from repro.kernels import replay_ring   # lazy: import cycle
        width = D if S == 1 else S * Dp
        if impl == "pallas":
            width = replay_ring.padded_width(width)
        lanes = _lanes(width, whatif and impl == "pallas" and K >= 2)
        carry = _ring_carry(
            init_params,
            opt_state[spec.state_keys[0]] if spec.state_keys else None,
            lanes=lanes, K=K, ring_dtype=run.ring_dtype)

        def params_of(carry, done):
            return _ring_row_params(carry[0], carry[2], done % K,
                                    layout=layout)

        aux = None
        if whatif:
            aux = jax.jit(lambda base: tuple(
                v[0].reshape(lanes)
                for v in _whatif_aux(flat_grad[1], base, width, width,
                                     D)))(jnp.zeros((1,), jnp.int32))
    elif S > 1:
        # per-shard rings: (S, K, Dp), row r of shard s = snapshot ts=r of
        # the shard's slice (the σ_s ≤ σ invariant keeps K a valid bound)
        ring = jnp.broadcast_to(flatten.shard_pack(
            flatten.tree_to_flat(init_params), S, Dp)[:, None, :], (S, K, Dp))
    else:
        ring = jnp.broadcast_to(flatten.tree_to_flat(init_params), (K, D))
    if impl == "stock" and spec.kernel_supported:
        # flat-domain carry: ring + the (D,)/(S, Dp) state vector (or None)
        s0 = None
        if spec.state_keys:
            s0 = flatten.tree_to_flat(opt_state[spec.state_keys[0]])
            if S > 1:
                s0 = flatten.shard_pack(s0, S, Dp)
        carry = (ring, s0)

        def params_of(carry, done):
            row = (carry[0][done % K] if S == 1
                   else flatten.shard_unpack(carry[0][:, done % K], D))
            return _unflatten_jit(layout)(row)
    elif impl == "stock":
        carry = (ring, (init_params, opt_state))

        def params_of(carry, done):
            return carry[1][0]

    if serving is not None:
        # snapshot buffer riding the carry: one row per published version
        # (+ the inert dummy row unpublished versions write).  Row 0 is
        # version 0 — the init weights every replica boots with, i.e. the
        # ring's initial row (already quantized under a bf16 ring: the
        # publication tolerance policy).
        P = int(serving.pub_versions.shape[0])
        row0 = carry[0][0].astype(jnp.float32)
        snaps0 = jnp.zeros((P + 1,) + row0.shape, jnp.float32).at[0].set(row0)
        core_params_of = params_of

        def params_of(carry, done):
            return core_params_of(carry[0], done)

        carry = (carry, snaps0)

    def advance(carry, seg):
        return (scan_fn(carry, seg, aux) if whatif
                else scan_fn(carry, seg))

    carry, history = _run_segments(trace, xs, carry, advance, params_of,
                                   eval_fn, eval_every,
                                   _pull_counts(trace, K) if whatif else None)
    params = params_of(carry, steps)
    serve_result = None
    if serving is not None:
        serve_result = _serve_eval(carry[1], layout, D, serving,
                                   serve_batches, serve_eval_fn)
    return SimResult(trace.clock_log(), steps, trace.simulated_time,
                     trace.minibatches, params, history,
                     serving=serve_result)


def _pull_counts(trace: ArrivalTrace, K: int):
    """Cumulative ``(slots, rows)`` the what-if events pull from the ring:
    entry j counts events [0, j).  An event's slots are its c pulled
    indices (per PS shard); its rows are the distinct ``ts % K`` among
    them."""
    ts = np.asarray(trace.pulled_ts if trace.shard_pulled_ts is None
                    else trace.shard_pulled_ts)
    ts = np.sort((ts if ts.ndim == 3 else ts[..., None]) % K, axis=1)
    rows = (1 + np.count_nonzero(np.diff(ts, axis=1), axis=1)).sum(axis=-1)
    slots = np.full(trace.steps, ts.shape[1] * ts.shape[2])
    return tuple(np.concatenate([[0], np.cumsum(v, dtype=np.int64)])
                 for v in (slots, rows))


def _run_segments(trace: ArrivalTrace, xs, carry, advance: Callable,
                  params_of: Callable, eval_fn: Optional[Callable],
                  eval_every: int, pulls=None):
    """Drive the replay scan over the trace's events: in one dispatch, or,
    with ``eval_fn`` and ``eval_every``, in segments of ``eval_every``
    events, handing the weights after each full one to ``eval_fn``.
    Returns ``(carry, history)``.

    Each segment is a step span (``<SPAN>.segment``, numbered from 0)
    holding the spans ``<SPAN>.inputs`` (the slice of the scan inputs),
    ``<SPAN>.dispatch`` (the scan call) and ``<SPAN>.handoff`` (the
    weights to ``eval_fn``).  ``pulls``, from :func:`_pull_counts`, adds
    each segment's pulled ring slots and rows to the telemetry counters
    as it is dispatched."""
    steps = trace.steps
    segmented = bool(eval_fn and eval_every)
    bounds = ([(lo, min(lo + eval_every, steps))
               for lo in range(0, steps, eval_every)] if segmented
              else [(0, steps)])
    history = []
    for index, (lo, hi) in enumerate(bounds):
        with telemetry.span(f"{SPAN}.segment", step=index):
            with telemetry.span(f"{SPAN}.inputs"):
                seg = (xs if (lo, hi) == (0, steps)
                       else jax.tree.map(lambda a: a[lo:hi], xs))
            with telemetry.span(f"{SPAN}.dispatch"):
                if pulls is not None:
                    telemetry.count(PULL_SLOTS, pulls[0][hi] - pulls[0][lo])
                    telemetry.count(PULL_ROWS, pulls[1][hi] - pulls[1][lo])
                carry = advance(carry, seg)
            if segmented and hi % eval_every == 0:
                with telemetry.span(f"{SPAN}.handoff"):
                    history.append(
                        {"update": hi,
                         "time": float(trace.event_time[hi - 1]),
                         **eval_fn(params_of(carry, hi))})
    return carry, history


def _pub_index(serving, steps: int) -> np.ndarray:
    """(steps,) snapshot-buffer index per scan step: version j + 1 is born
    when event j fires, so step j writes its new ring row to the version's
    position in ``pub_versions`` when some replica publishes it, else to
    the inert dummy row (index P — branch-free capture)."""
    pv = np.asarray(serving.pub_versions, np.int64)
    P = pv.shape[0]
    born = np.arange(1, steps + 1)
    idx = np.searchsorted(pv, born)
    hit = (idx < P) & (pv[np.minimum(idx, P - 1)] == born)
    return np.where(hit, idx, P)


def _serve_eval(snaps, layout, D: int, serving, serve_batches,
                serve_eval_fn, chunk: int = 512):
    """The serving lane's evaluation stage: map each request batch onto the
    captured snapshot of the version that served it, in chunked vmap lanes
    (at most two compiled programs: full chunks + one remainder).  Dropped
    requests (no live replica) score 0."""
    from repro.serve.fleet import ServingResult   # lazy: layering
    rows = snaps[:, :D]                           # (P + 1, D) fp32
    req_pub = jnp.asarray(serving.req_pub, jnp.int32)

    @jax.jit
    def lane(idx, batch):
        def one(i, b):
            return serve_eval_fn(flatten.flat_to_tree(rows[i], layout), b)
        return jax.vmap(one)(idx, batch)

    R = serving.n_requests
    parts = []
    for lo in range(0, R, chunk):
        hi = min(lo + chunk, R)
        part = lane(req_pub[lo:hi],
                    jax.tree.map(lambda a: jnp.asarray(a)[lo:hi],
                                 serve_batches))
        parts.append(np.asarray(part))
    metric = (np.concatenate(parts) if parts
              else np.zeros(0, np.float32))
    metric = np.where(serving.served, metric, 0.0).astype(np.float32)
    return ServingResult(trace=serving, request_metric=metric)


def _replay_spmd(trace: ArrivalTrace, run: RunConfig, *, spec, layout,
                 grad_fn, init_params, batch_fn, batches, eval_fn,
                 eval_every, flat_grad, assembly) -> SimResult:
    """The ``placement="spmd"`` arm of :func:`replay`: resolve the trace's
    :func:`placement_plan` against the visible devices, build the sharded
    ``(S, K, Wl)`` carry, and drive the shard_mapped scan
    (:func:`_make_spmd_scan_fn`).  Validations shared with the single
    placement already ran in ``replay``."""
    steps, c = trace.steps, trace.c
    K = trace.max_staleness + 1
    topo = trace.topology
    S, gs = topo.shards, trace.group_size
    if trace.serving is not None:
        raise ValueError(
            "serving traces cannot replay with placement='spmd': the "
            "serving lane captures published ring rows inside the "
            "single-device scan, which shard_map splits into per-shard "
            "(K, Dp) rings; replay with placement='single' (the default)")
    if not spec.kernel_supported:
        raise ValueError(
            f"placement='spmd' needs a kernel-supported optimizer (flat "
            f"per-shard ring carries); {spec.optimizer!r} has none")
    # "stock" has no per-device flat ring body; its fused twin is bitwise
    # at fp32 (RunConfig validation already keeps bf16 off stock)
    impl = optim.resolve_ring_impl(run.ring_impl, spec)
    if impl == "stock":
        impl = "fused"
    ef = run.ring_dtype == "bf16"
    whatif = (flat_grad is not None and trace.mode == "combine" and gs == 1)
    if whatif:
        kind = flat_grad[0]
        if kind != "quadratic":
            raise ValueError(f"unknown flat_grad kind {kind!r}; expected "
                             f"('quadratic', coeffs)")
    elif grad_fn is None:
        raise ValueError("grad_fn is required outside the what-if replay")
    elif (batch_fn is None) == (batches is None):
        raise ValueError("pass exactly one of batch_fn / batches")

    plan = placement_plan(trace, run, jax.device_count())
    if whatif:
        # closed-form gradients are shard-local: no learner axis needed
        plan = PlacementPlan(shards=plan.shards, learners=1, c=c)

    xs = _trace_xs(trace, K, None if whatif else batch_fn,
                   batches=None if whatif else batches)
    if xs["ts"].ndim == 2:
        xs["ts"] = xs["ts"][..., None]      # (steps, c, 1): one shard column
    scan_fn = _make_spmd_scan_fn(None if whatif else grad_fn, spec,
                                 trace.mode, c, K, layout, plan,
                                 tuple(sorted(xs)), group_size=gs,
                                 masked=trace.valid is not None,
                                 member_masked=trace.member_valid is not None,
                                 ring_impl=impl, ring_dtype=run.ring_dtype,
                                 whatif=whatif, assembly=assembly)

    # the carry (and the what-if auxiliaries) are built by one program whose
    # outputs are laid out per "ps" device, so each device computes only
    # its own (K, Wl) ring slice, state and residue rows.  Inputs already
    # laid out over those devices (e.g. QuadraticProblem(shards=S)) are
    # packed in place; nothing of model size ever sits whole on one device.
    D = layout.total
    Dp = topo.padded_width(D)
    Wl = _spmd_local_width(D, S, impl)
    rdt = jnp.bfloat16 if ef else jnp.float32
    mesh = mesh_lib.make_sim_mesh(plan.shards, plan.learners)
    per_ps = NamedSharding(mesh, PartitionSpec("ps"))

    lanes = _lanes(Wl, impl == "pallas")

    def pack(vec):                                           # (S, *lanes)
        return flatten.pad_flat(flatten.shard_pack(vec, S, Dp),
                                Wl).reshape((S,) + lanes)

    @functools.partial(jax.jit, out_shardings=per_ps)
    def build(init_params, base):
        packed = pack(flatten.tree_to_flat(init_params))
        q0 = quantize(packed, rdt)
        ring = jnp.broadcast_to(q0[:, None], (S, K) + lanes)
        res0 = (packed - q0.astype(jnp.float32)) if ef else None
        s0 = None
        if spec.state_keys:
            state = optim.init_state(spec, init_params)[spec.state_keys[0]]
            s0 = pack(flatten.tree_to_flat(state))
        aux = None
        if whatif:        # shard s, local column j: flat position s·Dp + j
            aux = tuple(v.reshape((S,) + lanes)
                        for v in _whatif_aux(flat_grad[1], base, Wl, Dp, D))
        return (ring, s0, res0), aux

    carry, aux = build(init_params, jnp.arange(S, dtype=jnp.int32) * Dp)

    @functools.partial(jax.jit, out_shardings=per_ps)
    def flat_params(carry, row_idx):
        """The weights of ring row ``row_idx`` as the zero-padded (S·Dp,)
        flat vector, left laid out per "ps" device: an eval never gathers
        the model onto one device."""
        row = carry[0][:, row_idx].astype(jnp.float32)
        if ef:
            row = row + carry[2]
        return row.reshape(S, Wl)[:, :Dp].reshape(-1)

    def params_of(carry, done):
        return _unflatten_jit(layout)(flat_params(carry, done % K))

    def advance(carry, seg):
        return (scan_fn(carry, seg, aux) if whatif
                else scan_fn(carry, seg))

    carry, history = _run_segments(trace, xs, carry, advance, params_of,
                                   eval_fn, eval_every,
                                   _pull_counts(trace, K) if whatif else None)
    params = params_of(carry, steps)
    return SimResult(trace.clock_log(), steps, trace.simulated_time,
                     trace.minibatches, params, history)


def replay_batch(traces: Sequence[ArrivalTrace],
                 runs: Sequence[RunConfig], *,
                 grad_fn: Callable,
                 init_params,
                 batch_fns: Optional[Sequence[Callable]] = None,
                 batches: Optional[Sequence] = None,
                 eval_fn: Optional[Callable] = None,
                 eval_every: int = 0) -> list:
    """Replay B shape-compatible traces as ONE vmapped device program.

    The sweep fast path (DESIGN.md §5): grid points that share trace shape
    — same ``steps`` and ``c`` (and therefore the same scan length and
    event arity) — plus the same optimizer spec, update mode, ``grad_fn``
    and parameter layout differ only in *data*: ring indices, LRs, and
    minibatches.  Stacking those along a leading (B,) axis and vmapping the
    identical per-event scan body executes a 5-seed × 4-config cell as one
    ``lax.scan`` instead of 20 sequential replays.  The ring is sized to
    the **group maximum** staleness (ring size never changes the math —
    only which row a snapshot lands in), so traces with different measured
    σ_max still batch.

    Per-lane results match :func:`replay` of the same trace to fp32
    accumulation tolerance (the vmapped body computes the same per-lane
    math, but XLA fuses the batched ops differently — observed drift
    ~1e-7 after tens of updates, same order as the legacy-vs-compiled
    drift in EXPERIMENTS.md §Sim).
    Restrictions (the driver falls back to sequential replays otherwise):
    kernel-supported optimizers only (sgd / momentum / adagrad — adamw's
    pytree carry has no flat lane layout), trivial (Rudra-base) topology
    only (sharded/grouped traces replay per-spec), all lanes agreeing on
    elasticity (masked combine-mode traces batch with other masked lanes —
    the per-event coefficients are just more lane data), one shared ``grad_fn`` and
    ``init_params`` (same problem), per-lane ``batch_fns`` — or per-lane
    pre-staged ``batches`` (leading (steps, c) axes; a problem's vectorized
    ``stage_minibatches``), which skips the per-slot staging loop entirely.
    """
    traces, runs = list(traces), list(runs)
    B = len(traces)
    if (batch_fns is None) == (batches is None):
        raise ValueError("pass exactly one of batch_fns / batches")
    lanes = list(batch_fns) if batches is None else list(batches)
    if not (B and len(runs) == B and len(lanes) == B):
        raise ValueError("traces / runs / batch data must align, non-empty")
    for trace, run in zip(traces, runs):
        _check_trace(trace, run)
        if trace.serving is not None:
            raise ValueError(
                "batched replay does not support serving traces: the "
                "serving lane adds a per-lane snapshot carry plus a "
                "post-scan request evaluation; replay serving specs "
                "individually (the experiment driver excludes them from "
                "batch cells automatically)")
    steps, c, mode = traces[0].steps, traces[0].c, traces[0].mode
    masked = traces[0].valid is not None
    for trace in traces[1:]:
        if (trace.steps, trace.c, trace.mode) != (steps, c, mode):
            raise ValueError(
                f"batch members must share trace shape: "
                f"(steps={steps}, c={c}, mode={mode!r}) vs "
                f"(steps={trace.steps}, c={trace.c}, mode={trace.mode!r})")
        if (trace.valid is not None) != masked:
            raise ValueError(
                "batch members must agree on elasticity: masked (elastic) "
                "and dense traces compile different scan bodies — group "
                "them separately")
    if masked and mode != "combine":
        raise ValueError("elastic traces replay in 'combine' mode only")
    spec = optim.spec_from_run(runs[0])
    for run in runs[1:]:
        other = optim.spec_from_run(run)
        if other != spec:
            raise ValueError(f"batch members must share the optimizer "
                             f"spec: {spec} vs {other}")
    ring_cfg = (runs[0].ring_impl, runs[0].ring_dtype)
    for run in runs[1:]:
        if (run.ring_impl, run.ring_dtype) != ring_cfg:
            raise ValueError(
                f"batch members must share (ring_impl, ring_dtype): "
                f"{ring_cfg} vs {(run.ring_impl, run.ring_dtype)} — a bf16 "
                f"lane's carry has a different dtype/residue layout")
    for run in runs:
        if run.placement != "single":
            raise ValueError(
                f"batched replay is single-placement only (a lane axis and "
                f"a device mesh cannot share the carry); replay "
                f"placement={run.placement!r} specs individually")
    opt_state = optim.init_state(spec, init_params)
    if not spec.kernel_supported:
        raise ValueError(f"{spec.optimizer!r} has no flat lane layout; "
                         f"replay each trace sequentially")
    for trace, run in zip(traces, runs):
        if not trace.topology.is_trivial(run.n_learners):
            raise ValueError(
                f"batched replay supports the trivial (Rudra-base) "
                f"topology only; got {trace.topology} — replay "
                f"sharded/grouped traces sequentially")
    K = max(trace.max_staleness for trace in traces) + 1
    layout = flatten.layout_of(init_params)
    impl = optim.resolve_ring_impl(runs[0].ring_impl, spec)
    ef = runs[0].ring_dtype == "bf16"
    scan_fn = _make_scan_fn(grad_fn, spec, mode, c, K, layout, batched=True,
                            masked=masked, ring_impl=impl,
                            ring_dtype=runs[0].ring_dtype)

    if batches is None:
        xs_lanes = [_trace_xs(trace, K, fn)
                    for trace, fn in zip(traces, lanes)]
    else:
        xs_lanes = [_trace_xs(trace, K, None, batches=b)
                    for trace, b in zip(traces, lanes)]
    # prev/slot are step-indexed mod the shared K — identical in every lane;
    # keep them unbatched so the scan's ring write stays a common-row
    # dynamic-update-slice (see _make_scan_fn)
    xs = jax.tree.map(
        lambda *a: jnp.stack(a),
        *[{k: v for k, v in lane.items() if k not in ("prev", "slot")}
          for lane in xs_lanes])
    xs["prev"] = xs_lanes[0]["prev"]
    xs["slot"] = xs_lanes[0]["slot"]
    flat0 = flatten.tree_to_flat(init_params)
    D = flat0.shape[0]
    if impl != "stock":
        from repro.kernels import replay_ring   # lazy: import cycle
        width = replay_ring.padded_width(D) if impl == "pallas" else D
        rdt = jnp.bfloat16 if ef else jnp.float32
        flat_pad = flatten.pad_flat(flat0, width)
        q0 = quantize(flat_pad, rdt)
        ring = jnp.tile(q0[None, None], (B, K, 1))
        res0 = (jnp.tile((flat_pad - q0.astype(jnp.float32))[None], (B, 1))
                if ef else None)
        s0 = None
        if spec.state_keys:
            s_flat = flatten.pad_flat(
                flatten.tree_to_flat(opt_state[spec.state_keys[0]]), width)
            s0 = jnp.tile(s_flat[None], (B, 1))
        carry = (ring, s0, res0)

        def params_of(carry, lane, done):
            row = carry[0][lane, done % K].astype(jnp.float32)
            if ef:
                row = row + carry[2][lane]
            return _unflatten_jit(layout)(row[:D])
    else:
        ring = jnp.broadcast_to(flat0, (B, K) + flat0.shape)
        s0 = None
        if spec.state_keys:
            s_flat = flatten.tree_to_flat(opt_state[spec.state_keys[0]])
            s0 = jnp.broadcast_to(s_flat, (B,) + s_flat.shape)
        carry = (ring, s0)

        def params_of(carry, lane, done):
            return _unflatten_jit(layout)(carry[0][lane, done % K])

    def segment(lo, hi):
        # prev/slot are unbatched (steps,); everything else is (B, steps, …)
        return {k: (v[lo:hi] if k in ("prev", "slot")
                    else jax.tree.map(lambda a: a[:, lo:hi], v))
                for k, v in xs.items()}

    histories = [[] for _ in range(B)]
    if eval_fn and eval_every:
        done = 0
        while done < steps:
            take = min(eval_every, steps - done)
            seg = segment(done, done + take)
            carry = scan_fn(carry, seg)
            done += take
            if done % eval_every == 0:
                for b in range(B):
                    histories[b].append(
                        {"update": done,
                         "time": float(traces[b].event_time[done - 1]),
                         **eval_fn(params_of(carry, b, done))})
    else:
        carry = scan_fn(carry, xs)

    return [SimResult(trace.clock_log(), steps, trace.simulated_time,
                      trace.minibatches, params_of(carry, b, steps),
                      histories[b])
            for b, trace in enumerate(traces)]
