"""The DeepSeek-V3 block (Moonlight-16B-A3B's SMOKE size) against the plain
float32 reference ``repro.models.ref``: latent attention, the dropless MoE
over the experts held here, the leading dense layer, and one softsync round
with the float32 master, the routing-bias rule and the balance loss."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.config import RunConfig
from repro.configs import get_smoke
from repro.core import make_train_step
from repro.models import model_forward, model_loss, moe
from repro.models import ref as R
from repro.models.mla import mla_forward
from repro.optim import init_state, spec_from_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = get_smoke("moonlight_16b_a3b")
F32 = dataclasses.replace(SMOKE, dtype="float32")
NAIVE = RunConfig(attn_impl="naive")


def hf(c) -> dict:
    """The reference's configuration (``deepseek_v3`` keys) of ``c``."""
    return {"hidden_size": c.d_model, "num_attention_heads": c.n_heads,
            "kv_lora_rank": c.kv_lora_rank,
            "qk_nope_head_dim": c.qk_nope_head_dim,
            "qk_rope_head_dim": c.qk_rope_head_dim,
            "v_head_dim": c.v_head_dim, "intermediate_size": c.d_ff,
            "moe_intermediate_size": c.moe_d_ff,
            "n_shared_experts": c.n_shared_experts,
            "n_routed_experts": c.n_experts_held,
            "first_held_expert": c.experts_held[0],
            "published": {"n_routed_experts": c.n_experts},
            "num_experts_per_tok": c.top_k,
            "first_k_dense_replace": c.first_k_dense,
            "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
            "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "routed_scaling_factor": c.routed_scaling,
            "norm_topk_prob": c.norm_topk_prob,
            "assumed": {"bias_update_speed": c.router_bias_speed,
                        "seq_aux_alpha": c.seq_aux_loss}}


def _f32_weights(c, seed=0):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        R.make_weights(hf(c), seed))


def _tokens(c, B=2, S=24, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, c.vocab_size, (B, S)), jnp.int32)


def test_reference_is_the_benchmarks_copy():
    with open(os.path.join(ROOT, "src/repro/models/ref.py"), "rb") as a, \
            open(os.path.join(ROOT, "bench/ref_mla_moe.py"), "rb") as b:
        assert a.read() == b.read()


def test_reference_layout_is_the_programs():
    from repro.models import init_model
    for c in (SMOKE, dataclasses.replace(SMOKE, experts_held=(2, 2))):
        prog = R.flatten(init_model(c, jax.random.PRNGKey(0)))
        lay = R.layout(hf(c))
        assert sorted(prog) == sorted(lay)
        for k, (shape, init) in lay.items():
            assert prog[k].shape == shape, k
            assert prog[k].dtype == (jnp.float32 if init == "zeros"
                                     else jnp.bfloat16), k


@pytest.mark.parametrize("impl", ["naive", "chunked"])
def test_mla_forward_and_gradients_match_reference(impl):
    dec = R.Decoder(hf(F32))
    flat = R.flatten(_f32_weights(F32))
    p = {k[len(R.lead(0) + "attn/"):]: v for k, v in flat.items()
         if k.startswith(R.lead(0) + "attn/")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, F32.d_model))
    run = RunConfig(attn_impl=impl, attn_q_chunk=8, attn_kv_chunk=8)

    def prog(p, x):
        return mla_forward(F32, run, p, x, jnp.arange(x.shape[1]))

    def ref(p, x):
        pp = {"attn/" + k: v for k, v in p.items()}
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda xb: dec.attention(pp, xb))(x)

    out_p, vjp_p = jax.vjp(prog, p, x)
    out_r, vjp_r = jax.vjp(ref, p, x)
    # chunked attention feeds bf16 probabilities to its PV product
    tol = 1e-4 if impl == "naive" else 2e-2
    scale = float(jnp.max(jnp.abs(out_r)))
    np.testing.assert_allclose(out_p, out_r, atol=tol * scale)
    ct = jax.random.normal(jax.random.PRNGKey(2), out_r.shape)
    for gp, gr in zip(jax.tree.leaves(vjp_p(ct)), jax.tree.leaves(vjp_r(ct))):
        np.testing.assert_allclose(gp, gr, atol=tol * float(
            jnp.max(jnp.abs(gr))))


def test_routing_rule():
    """Sigmoid scores; the bias picks experts and never weighs them; the k
    gates sum to the routed scaling; every slot is computed (dropless)."""
    c = F32
    x = jax.random.normal(jax.random.PRNGKey(3), (64, c.d_model))
    w = jax.random.normal(jax.random.PRNGKey(4), (c.d_model, c.n_experts))
    bias = jnp.zeros((c.n_experts,)).at[3].set(10.0)
    scores, idx, gates = moe.route(c, w, bias, x)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(x @ w), rtol=1e-5)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))     # the bias picks
    np.testing.assert_allclose(jnp.sum(gates, -1), c.routed_scaling,
                               rtol=1e-5)
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True) * c.routed_scaling,
        rtol=1e-5)
    # the whole MoE against the reference's dense one: nothing dropped,
    # even when one expert takes every token
    p = R.nest({k[len(R.UNITS + "moe/"):]: v[0] for k, v in R.flatten(
        _f32_weights(c)).items() if k.startswith(R.UNITS + "moe/")})
    p["e_bias"] = bias
    h = x.reshape(2, 32, c.d_model)
    out, aux = moe.moe_dropless_forward(c, p, h)
    dec = R.Decoder(hf(c))
    flat = {"moe/" + k: v for k, v in R.flatten(p).items()}
    with jax.default_matmul_precision("highest"):
        ref_out, load, bal = jax.vmap(
            lambda hb: dec.moe(flat, bias, hb))(h)
    np.testing.assert_allclose(out, ref_out, atol=1e-4)
    counts = aux["counts"]
    np.testing.assert_array_equal(counts[moe.ROUTED_SLOTS], load.sum(0))
    assert int(counts[moe.HELD_SLOTS]) == \
        int(counts[moe.ROUTED_SLOTS].sum()) == 64 * c.top_k
    np.testing.assert_allclose(aux["lb_loss"], c.seq_aux_loss * bal.mean(),
                               rtol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Across all expert shares, the routed parts plus the shared experts
    counted once equal the layer with every expert held."""
    c = F32
    full = R.flatten(_f32_weights(c))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, c.d_model))

    def layer(held, first):
        cc = dataclasses.replace(c, experts_held=(first, held))
        p = R.nest({k[len(R.UNITS + "moe/"):]: v[0] for k, v in full.items()
                    if k.startswith(R.UNITS + "moe/")})
        for w in ("w_gate", "w_up", "w_down"):
            p[w] = p[w][first:first + held]
        return moe.moe_dropless_forward(cc, p, x)[0]

    p = R.nest({k[len(R.UNITS + "moe/"):]: v[0] for k, v in full.items()
                if k.startswith(R.UNITS + "moe/")})
    from repro.models.layers import swiglu
    shared = swiglu(x, p["shared"])
    shares = [layer(2, 0), layer(2, 2)]
    whole = layer(4, 0)
    np.testing.assert_allclose(sum(s - shared for s in shares) + shared,
                               whole, atol=1e-5)


def test_leading_dense_layer_in_the_spine():
    """The whole forward, one leading dense layer then the scanned MoE
    units, against the reference (logits, loads and balance terms); each
    unit's routing counts come out of the scan."""
    c = dataclasses.replace(F32, experts_held=(1, 2))
    params = _f32_weights(c)
    tok = _tokens(c)
    logits, aux = jax.jit(lambda p, t: model_forward(
        c, NAIVE, p, {"tokens": t}))(params, tok)
    ref_logits, ref_aux = R.Decoder(hf(c)).forward(params, tok)
    scale = float(jnp.max(jnp.abs(ref_logits)))
    np.testing.assert_allclose(logits[..., :c.vocab_size], ref_logits,
                               atol=1e-4 * scale)
    load = aux["counts"][moe.ROUTED_SLOTS]
    assert load.shape == (c.n_units, c.n_experts)
    np.testing.assert_array_equal(load, ref_aux["load"])
    np.testing.assert_allclose(
        aux["lb_loss"], c.seq_aux_loss * ref_aux["bal"].mean(1).sum(),
        rtol=1e-5)
    # without the leading layer the output moves: it is not skipped
    no_lead = jax.tree.map(jnp.zeros_like, params["lead"])
    out2, _ = model_forward(c, NAIVE, dict(params, lead=no_lead),
                            {"tokens": tok})
    assert float(jnp.max(jnp.abs(out2 - logits))) > 1e-3


def _round_run(**kw):
    return RunConfig(protocol="softsync", n_softsync=2, n_learners=4,
                     minibatch=2, base_lr=0.05,
                     lr_policy="staleness_inverse", optimizer="momentum",
                     momentum=0.9, num_microbatches=2, attn_impl="naive",
                     **kw)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_softsync_round_matches_reference(dtype):
    """One n-softsync round of ``make_train_step`` (momentum, the bias rule
    per event, the balance loss) against the reference's round from the
    same weights and batch.  In float32 the two agree to round-off; in
    bfloat16 the program updates a float32 master, and the weights it
    reads are that master rounded, while its bf16 gradients differ from
    the reference's by bf16 noise."""
    c = dataclasses.replace(SMOKE, experts_held=(1, 2), dtype=dtype,
                            router_bias_speed=1e-2, seq_aux_loss=1e-2)
    h = hf(c)
    run = _round_run()
    params = R.make_weights(h, 0)
    if dtype == "float32":
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    tok = _tokens(c, B=8, S=32, seed=1)
    lab = jnp.roll(tok, -1, axis=1)
    step = jax.jit(make_train_step(
        run, lambda p, b, sample_weights=None: model_loss(c, run, p, b),
        post_event=moe.routing_bias_update(c)))
    opt = init_state(spec_from_run(run), params)
    assert ("master" in opt) == (dtype == "bfloat16")
    with jax.default_matmul_precision("highest"):
        p1, o1, m = step(params, opt, {"tokens": tok, "labels": lab})

    dec = R.Decoder(h)
    state = dec.init_state(params)
    lrs = [run.base_lr / max(1.0, (run.n_softsync - 1) / 2.0)] * 2
    loss = dec.round(state, np.asarray(tok), np.asarray(lab), lrs,
                     run.momentum)
    tight = dtype == "float32"
    assert abs(float(m["loss_round_mean"]) - loss) < (
        1e-5 if tight else 1e-2) * loss
    ref = R.stored(state)
    # the bias rule saw the same loads, event by event
    np.testing.assert_allclose(p1["units"]["block_0"]["moe"]["e_bias"],
                               ref["units"]["block_0"]["moe"]["e_bias"],
                               atol=1e-7)
    f0, f1 = R.flatten(params), R.flatten(p1)
    master = R.flatten(o1.get("master", p1))
    for k, w in master.items():
        # the weights a learner reads are the master rounded
        np.testing.assert_array_equal(f1[k], w.astype(f0[k].dtype))
        dp = np.asarray(w) - np.asarray(f0[k], np.float32)
        dr = np.asarray(state["master"][k]) - np.asarray(f0[k], np.float32)
        if tight:
            assert np.linalg.norm(dp - dr) <= 1e-4 * np.linalg.norm(dr), k
        else:
            assert abs(np.linalg.norm(dp) - np.linalg.norm(dr)) <= \
                0.1 * np.linalg.norm(dr) + 1e-9, k
    load = np.asarray(m["counts"][moe.ROUTED_SLOTS])
    assert int(load.sum()) == 8 * 32 * c.top_k * c.n_units
    assert int(np.sum(m["counts"][moe.HELD_SLOTS])) == int(
        np.sum(load[:, 1:3]))


def test_routing_bias_rule():
    """b_i ← b_i + γ·sign(mean load − load_i) per MoE layer, on the stored
    bias and its float32 master alike; an expert at the mean stays."""
    c = dataclasses.replace(SMOKE, router_bias_speed=0.5)
    hook = moe.routing_bias_update(c)
    bias = jnp.zeros((c.n_units, c.n_experts), jnp.float32)
    params = {"units": {"block_0": {"moe": {"e_bias": bias}}}}
    opt = {"master": {"units": {"block_0": {"moe": {"e_bias": bias}}}}}
    load = jnp.asarray([[4, 2, 2, 0], [1, 1, 1, 1]], jnp.int32)
    p, o, m = hook(params, opt, {"counts": {moe.ROUTED_SLOTS: load}})
    want = np.asarray([[-0.5, 0.0, 0.0, 0.5], [0.0, 0.0, 0.0, 0.0]])
    np.testing.assert_array_equal(p["units"]["block_0"]["moe"]["e_bias"],
                                  want)
    np.testing.assert_array_equal(
        o["master"]["units"]["block_0"]["moe"]["e_bias"], want)
    assert int(m["maxima"][moe.HELD_SLOTS_MAX]) == 4
    assert moe.routing_bias_update(
        dataclasses.replace(SMOKE, router_bias_speed=0.0)) is None


def _garbage_ragged_dot(real):
    """``lax.ragged_dot`` whose output rows past the last group, forward
    and in the lhs cotangent, hold NaN: the TPU compiler's kernel leaves
    them unwritten."""
    def fill(x, group_sizes):
        row = jnp.arange(x.shape[0])[:, None]
        return jnp.where(row < jnp.sum(group_sizes), x, jnp.nan)

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        @jax.custom_vjp
        def f(lhs, rhs):
            return fill(real(lhs, rhs, group_sizes, **kw), group_sizes)

        def fwd(lhs, rhs):
            out, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes, **kw),
                               lhs, rhs)
            return fill(out, group_sizes), vjp

        def bwd(vjp, ct):
            d_lhs, d_rhs = vjp(ct)
            return fill(d_lhs, group_sizes), d_rhs

        f.defvjp(fwd, bwd)
        return f(lhs, rhs)
    return ragged_dot


def test_rows_past_the_held_groups_are_never_read(monkeypatch):
    """Slots routed to experts held elsewhere sort past the held groups;
    whatever the grouped matmul leaves in those rows, forward or
    backward, reaches neither the output nor any gradient."""
    c = dataclasses.replace(F32, experts_held=(1, 2))
    p = R.nest({k[len(R.UNITS + "moe/"):]: v[0] for k, v in R.flatten(
        _f32_weights(c)).items() if k.startswith(R.UNITS + "moe/")})
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, c.d_model))

    def loss(p, x):
        out, aux = moe.moe_dropless_forward(c, p, x)
        return jnp.sum(out ** 2) + aux["lb_loss"]

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _garbage_ragged_dot(jax.lax.ragged_dot))
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
