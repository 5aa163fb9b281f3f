"""Mixture-of-Experts with capacity-based scatter dispatch (GShard-style).

TPU adaptation notes (DESIGN.md §9): experts are sharded over the ``model``
mesh axis (expert parallelism); tokens are grouped so that the per-group
dispatch buffers stay small and the dispatch crossing the data→model axes
lowers to all-to-all-style collectives under GSPMD.

We deliberately avoid the one-hot dispatch *einsum* of the original GShard
formulation: its (groups, tokens, experts, capacity) tensor is ~10 TB at our
train_4k shape.  Instead tokens are scattered into per-expert capacity
buffers and gathered back (Megablocks-style dense-capacity variant), which
keeps memory O(tokens · d_model) while remaining fully static-shaped.

The DeepSeek-V3 block (``BLOCK_MLA_MOE``) takes the dropless path at the
end of this file instead: sigmoid routing over all experts with a
balancing bias, every slot computed for the experts this device holds
(``ModelConfig.experts_held``) by one grouped matmul, shared experts.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.config import BLOCK_MLA_MOE, ModelConfig
from repro.models.layers import init_swiglu, swiglu


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    M = cfg.d_model
    F = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = float(1.0 / np.sqrt(M)), float(1.0 / np.sqrt(F))
    return {
        "w_router": jax.random.normal(ks[0], (M, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (E, M, F), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, M, F), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, F, M), dtype) * s_out,
    }


def capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    E, k = cfg.n_experts, cfg.top_k
    c = int(math.ceil(tokens_per_group * k / E * cfg.capacity_factor))
    return max(c, 1)


def _dispatch_one_group(tokens, fidx, pos, keep, E, C):
    """tokens (T*k, M) already gathered per slot; scatter to (E, C, M)."""
    M = tokens.shape[-1]
    buf = jnp.zeros((E, C, tokens.shape[-1]), tokens.dtype)
    contrib = tokens * keep[:, None].astype(tokens.dtype)
    return buf.at[fidx, pos].add(contrib)


def moe_forward(cfg: ModelConfig, p: dict, x: jax.Array
                ) -> Tuple[jax.Array, dict]:
    """x: (B, S, M).  Returns (out (B,S,M), aux dict with losses/metrics)."""
    B, S, M = x.shape
    E, k = cfg.n_experts, cfg.top_k
    # Group tokens.  Groups must (a) hold ≥ E tokens so the capacity stays
    # integral with bounded waste, and (b) stay ≤ GROUP_T tokens and aligned
    # with the sequence sharding so the slot bookkeeping (cumsum over the
    # group) and the scatter stay shard-local — long sequences are split into
    # (B · S/GROUP_T) groups instead of one 32k-token group per batch row
    # (EXPERIMENTS.md §Perf iteration A1).  Decode batches (S == 1) fold into
    # one group.
    GROUP_T = 2048
    if S >= E:
        T = min(S, GROUP_T)
        while S % T:
            T //= 2
        G = B * (S // T)
        xg = x.reshape(G, T, M)
    else:
        G, T = 1, B * S
        xg = x.reshape(1, T, M)
    C = capacity(T, cfg)

    logits = jnp.einsum("gtm,me->gte", xg.astype(jnp.float32), p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (G, T, E)
    gate_w, idx = jax.lax.top_k(probs, k)                      # (G, T, k)
    gate_w = gate_w / jnp.maximum(jnp.sum(gate_w, -1, keepdims=True), 1e-9)

    # ---- aux losses ------------------------------------------------------
    # Switch-style load balance: E * Σ_e fraction_e · prob_e
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    one_hot_top1 = jax.nn.one_hot(idx[..., 0], E)
    ce = jnp.mean(one_hot_top1, axis=(0, 1))
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # ---- slot assignment: position of each routed token within its expert -
    fidx = idx.reshape(G, T * k)                               # (G, T*k)
    onehot = jax.nn.one_hot(fidx, E, dtype=jnp.int32)          # (G, T*k, E)
    pos = jnp.cumsum(onehot, axis=1) - 1                       # (G, T*k, E)
    pos = jnp.take_along_axis(pos, fidx[..., None], axis=-1)[..., 0]
    keep = pos < C                                             # capacity drop

    # ---- dispatch --------------------------------------------------------
    slot_tokens = jnp.repeat(xg, k, axis=1)                    # (G, T*k, M)
    buf = jax.vmap(_dispatch_one_group, in_axes=(0, 0, 0, 0, None, None))(
        slot_tokens, fidx, pos, keep, E, C)                    # (G, E, C, M)

    # ---- expert compute (SwiGLU) ------------------------------------------
    g = jnp.einsum("gecm,emf->gecf", buf, p["w_gate"])
    u = jnp.einsum("gecm,emf->gecf", buf, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    eo = jnp.einsum("gecf,efm->gecm", h, p["w_down"])          # (G, E, C, M)

    # ---- combine -----------------------------------------------------------
    gathered = jax.vmap(lambda o, f, q: o[f, q])(eo, fidx, pos)  # (G,T*k,M)
    w = (gate_w.reshape(G, T * k) * keep).astype(gathered.dtype)
    out = (gathered * w[..., None]).reshape(G, T, k, M).sum(axis=2)
    out = out.reshape(B, S, M)

    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {
        "lb_loss": lb_loss * cfg.load_balance_loss,
        "z_loss": z_loss * cfg.router_z_loss,
        "dropped_fraction": dropped,
    }
    return out, aux


# ---------------------------------------------------------------------------
# Dropless fine-grained MoE over the experts held here (DeepSeek-V3 routing)
# ---------------------------------------------------------------------------
# counters (repro.telemetry): slots routed, slots that reached a held
# expert, the largest held expert's slots in one update event, and the
# dropless layers built (at trace time).  The first two are the step's
# "counts" metrics: ROUTED_SLOTS per expert (the loads the bias rule
# reads), HELD_SLOTS in all; the rule adds HELD_SLOTS_MAX to "maxima".
ROUTED_SLOTS = "moe.routed_slots"
HELD_SLOTS = "moe.held_slots"
HELD_SLOTS_MAX = "moe.held_slots_max"
DROPLESS_BUILDS = "moe.dropless_builds"


def init_moe_dropless(key, cfg: ModelConfig, dtype) -> dict:
    """Router over all ``n_experts``, its balancing bias (float32, moved by
    the sign rule and never by a gradient), the held experts' SwiGLU
    weights and the shared experts as one SwiGLU of n_shared·moe_d_ff."""
    M, F, held = cfg.d_model, cfg.moe_d_ff, cfg.n_experts_held
    ks = jax.random.split(key, 5)
    s_in, s_out = float(M ** -0.5), float(F ** -0.5)
    return {
        "w_router": jax.random.normal(ks[0], (M, cfg.n_experts), dtype) * s_in,
        "e_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_gate": jax.random.normal(ks[1], (held, M, F), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (held, M, F), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (held, F, M), dtype) * s_out,
        "shared": init_swiglu(ks[4], M, cfg.n_shared_experts * F, dtype),
    }


def route(cfg: ModelConfig, w_router, e_bias, x):
    """Sigmoid scores, the top-k of score + bias (the bias picks experts and
    nothing else), and the gates: the picked scores, normalised to sum 1
    and scaled.  x (N, M) → scores (N, E) fp32, idx (N, k), gates (N, k)."""
    logits = jnp.einsum("nm,me->ne", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(e_bias),
                           cfg.top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return scores, idx, gates * cfg.routed_scaling


def seq_balance_loss(cfg: ModelConfig, scores, idx, B: int):
    """DeepSeek-V3's sequence-wise balance loss, Σ_i f_i P_i averaged over
    sequences: f_i = E/(k·S) × slots of sequence that picked expert i (no
    gradient), P_i = mean over its tokens of the scores normalised over all
    experts."""
    E, k = cfg.n_experts, cfg.top_k
    S = scores.shape[0] // B
    counts = jax.nn.one_hot(idx.reshape(B, S * k), E,
                            dtype=jnp.float32).sum(axis=1)          # (B, E)
    f = jax.lax.stop_gradient(counts * (E / (k * S)))
    norm = scores / jnp.sum(scores, -1, keepdims=True)
    P = jnp.mean(norm.reshape(B, S, E), axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def moe_dropless_forward(cfg: ModelConfig, p: dict, x: jax.Array
                         ) -> Tuple[jax.Array, dict]:
    """x: (B, S, M) → (routed part of the held experts + shared experts,
    aux).  Every slot that picked a held expert is computed: the slots are
    sorted by expert and one grouped matmul (``lax.ragged_dot``) runs each
    held expert over its own rows.  What the experts held elsewhere would
    add is left out.  aux carries the balance loss (× α) and the routing
    counts of this batch under "counts": the slots routed to each of the
    E experts and the slots held here."""
    telemetry.count(DROPLESS_BUILDS)
    B, S, M = x.shape
    N, k = B * S, cfg.top_k
    first, held = cfg.experts_held[0], cfg.n_experts_held
    xt = x.reshape(N, M)
    with jax.named_scope("moe.route"):
        scores, idx, gates = route(cfg, p["w_router"], p["e_bias"], xt)
        load = jnp.zeros((cfg.n_experts,), jnp.int32).at[
            idx.reshape(-1)].add(1)
        bal = seq_balance_loss(cfg, scores, idx, B)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(-1) - first                            # (N·k,)
        is_held = (local >= 0) & (local < held)
        order = jnp.argsort(jnp.where(is_held, local, held), stable=True)
        group_sizes = jax.lax.dynamic_slice_in_dim(load, first, held)
        row_held = is_held[order][:, None]
        # rows past the held groups belong to no expert, and the TPU's
        # grouped matmul leaves them unwritten in its results, forward
        # and backward: every operand and result is masked to them, so
        # nothing read from there reaches a value or a gradient
        rows = jnp.where(row_held, xt[order // k], 0)  # held first, by expert
    with jax.named_scope("moe.experts"):
        def gmm(a, w):
            return jnp.where(row_held, jax.lax.ragged_dot(
                a, w, group_sizes, preferred_element_type=jnp.float32), 0.0)
        g = gmm(rows, p["w_gate"])
        u = gmm(rows, p["w_up"])
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = gmm(h, p["w_down"]).astype(x.dtype)
    with jax.named_scope("moe.combine"):
        inverse = jnp.argsort(order)
        w = jnp.where(is_held, gates.reshape(-1), 0.0)
        out = jnp.einsum("nkm,nk->nm", y[inverse].reshape(N, k, M),
                         w.reshape(N, k)).astype(x.dtype)
    with jax.named_scope("moe.shared"):
        out = out + swiglu(xt, p["shared"])
    aux = {"lb_loss": bal * cfg.seq_aux_loss, "z_loss": 0.0,
           "counts": {ROUTED_SLOTS: load, HELD_SLOTS: jnp.sum(group_sizes)}}
    return out.reshape(B, S, M), aux


def _bias_paths(cfg: ModelConfig):
    return [("units", f"block_{i}", "moe", "e_bias")
            for i, b in enumerate(cfg.block_pattern) if b == BLOCK_MLA_MOE]


def _add_at(tree: dict, path, delta) -> dict:
    head, *rest = path
    if not rest:
        return {**tree, head: tree[head] + delta}
    return {**tree, head: _add_at(tree[head], rest, delta)}


def routing_bias_update(cfg: ModelConfig):
    """The per-event hook of ``core.distributed.make_train_step`` for a
    biased router, or None: after each update event, b_i ← b_i +
    γ·sign(mean load − load_i) over all experts of each MoE layer, with the
    loads of that event's batch (in a deployment they would be summed over
    every chip; here they are this chip's).  The float32 master, where the
    optimizer keeps one, moves with it.  It also reports the event's
    largest held load under "maxima"."""
    if not cfg.router_bias_speed:
        return None
    gamma = float(cfg.router_bias_speed)
    first, held = cfg.experts_held[0], cfg.n_experts_held
    paths = _bias_paths(cfg)

    def post_event(params, opt, metrics):
        slots = metrics["counts"][ROUTED_SLOTS]             # (units, E)
        load = slots.astype(jnp.float32)
        delta = gamma * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)
        for path in paths:
            params = _add_at(params, path, delta)
            if "master" in opt:
                opt = {**opt, "master": _add_at(opt["master"], path, delta)}
        maxima = dict(metrics.get("maxima", {}), **{
            HELD_SLOTS_MAX: jnp.max(slots[:, first:first + held])})
        return params, opt, dict(metrics, maxima=maxima)

    return post_event
