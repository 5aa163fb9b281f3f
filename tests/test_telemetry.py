"""The program's own spans and counters (``repro.telemetry``) on the
what-if replay: the pulled-row counters against an independent count, the
segment loop's spans in a profiler trace, and the build counters that
moved into telemetry."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim, telemetry
from repro.config import RunConfig
from repro.core import engine, replay, schedule
from repro.kernels import replay_ring

D = 600


def _cfg(ring_dtype="bf16"):
    # 1-softsync over 6 learners: c = 6 pulled slots per event
    return RunConfig(protocol="softsync", n_softsync=1, n_learners=6,
                     minibatch=1, base_lr=0.02, optimizer="momentum",
                     ring_dtype=ring_dtype, seed=5)


def _whatif(cfg, trace, body, **kw):
    """The what-if replay with its event body held to ``body``."""
    i = jnp.arange(D, dtype=jnp.float32)
    a, wstar = 0.5 + (i % 100.0) / 100.0, jnp.sin(0.01 * i)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine, "_event_body", lambda spec: body)
        return replay(trace, cfg,
                      init_params={"w": jnp.zeros((D,), jnp.float32)},
                      flat_grad=("quadratic",
                                 lambda pos: (a[pos], wstar[pos])), **kw)


def _delta(before, name):
    return telemetry.counters().get(name, 0) - before.get(name, 0)


def test_count_mark_and_span():
    before = telemetry.counters()
    telemetry.count("test.telemetry.n")
    telemetry.count("test.telemetry.n", 4)
    assert _delta(before, "test.telemetry.n") == 5
    telemetry.mark("test.telemetry.last", 7)
    telemetry.mark("test.telemetry.last", 2)
    assert telemetry.counters()["test.telemetry.last"] == 2
    # a snapshot, not the live table
    snap = telemetry.counters()
    telemetry.count("test.telemetry.n")
    assert snap["test.telemetry.n"] + 1 == telemetry.counters()[
        "test.telemetry.n"]
    with telemetry.span("test.telemetry.span"):
        with telemetry.span("test.telemetry.step", step=3):
            pass


@pytest.mark.parametrize("impl,every", [("pallas", 8), ("pallas", 0),
                                        ("fused", 8)])
def test_pull_counters_match_an_independent_count(impl, every):
    cfg = _cfg()
    trace = schedule(cfg, 40)
    K = trace.max_staleness + 1
    assert trace.c == 6 and K >= 2
    before = telemetry.counters()
    _whatif(cfg, trace, impl, eval_fn=(lambda p: {}) if every else None,
            eval_every=every)
    rows = sum(len({int(t) % K for t in trace.pulled_ts[j]})
               for j in range(trace.steps))
    assert _delta(before, engine.PULL_SLOTS) == trace.steps * trace.c
    assert _delta(before, engine.PULL_ROWS) == rows
    # the schedule does pull some row twice in one event, so the two differ
    assert rows < trace.steps * trace.c


def test_staged_replay_counts_no_pulls():
    """Only the what-if events count: the staged-gradient scan pulls its
    rows through its own gather, which the counters do not describe."""
    cfg = _cfg(ring_dtype="fp32")
    trace = schedule(cfg, 12)
    before = telemetry.counters()
    replay(trace, cfg, grad_fn=lambda p, b: {"w": p["w"] * 0.1},
           init_params={"w": jnp.ones((8,), jnp.float32)},
           batch_fn=lambda l, i: np.zeros((1,), np.float32))
    assert _delta(before, engine.PULL_SLOTS) == 0
    assert _delta(before, engine.PULL_ROWS) == 0


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(engine.SPAN)]
    return spans


def test_segment_spans_in_a_profile(tmp_path):
    cfg = _cfg()
    trace = schedule(cfg, 24)
    _whatif(cfg, trace, "pallas", eval_every=8,
            eval_fn=lambda p: {})                               # compile
    with jax.profiler.trace(str(tmp_path)):
        res = _whatif(cfg, trace, "pallas", eval_every=8,
                      eval_fn=lambda p: {"w0": float(p["w"][0])})
    assert len(res.history) == 3
    spans = _host_spans(str(tmp_path))
    segs = sorted((s for s in spans if s[0] == f"{engine.SPAN}.segment"),
                  key=lambda s: s[1])
    assert len(segs) == 3
    assert [int(s[3]["step_num"]) for s in segs] == [0, 1, 2]
    for _, a, b, _ in segs:
        inner = sorted((s for s in spans if s[1] >= a and s[2] <= b
                        and s[0] != f"{engine.SPAN}.segment"),
                       key=lambda s: s[1])
        assert [s[0] for s in inner] == [f"{engine.SPAN}.{p}" for p in
                                         ("inputs", "dispatch", "handoff")]


def test_build_counters_read_through_telemetry():
    spec = optim.UpdateSpec(optimizer="sgd")
    ring = jnp.zeros((4, 1024), jnp.float32)
    before = replay_ring.pallas_dispatches
    replay_ring.ring_apply(ring, None, None, jnp.ones((2, 1024)),
                           jnp.full((2,), 0.5), jnp.full((2,), 0.1),
                           jnp.array([0, 1], jnp.int32), spec=spec)
    assert replay_ring.pallas_dispatches == before + 1
    assert (telemetry.counters()[replay_ring.DISPATCHES]
            == replay_ring.pallas_dispatches)
    assert replay_ring.last_interpret is (jax.default_backend() != "tpu")
    assert (optim.backends.pallas_dispatches
            == telemetry.counters().get(optim.backends.DISPATCHES, 0))
    with pytest.raises(AttributeError):
        replay_ring.no_such_counter
    with pytest.raises(AttributeError):
        optim.backends.no_such_counter


# ---------------------------------------------------------------------------
# the dropless MoE and latent attention (Moonlight-16B-A3B's SMOKE size)
# ---------------------------------------------------------------------------
def _moonlight(held=(1, 2)):
    import dataclasses
    from repro.configs import get_smoke
    return dataclasses.replace(get_smoke("moonlight_16b_a3b"),
                               experts_held=held)


_MOE_RUN = RunConfig(protocol="softsync", n_softsync=2, n_learners=4,
                     minibatch=1, base_lr=0.01,
                     lr_policy="staleness_inverse", optimizer="momentum",
                     attn_q_chunk=16, attn_kv_chunk=16, num_microbatches=2)


def test_moe_counters_add_up_to_the_slots_routed():
    """Over the rounds of ``train``, ``moe.routed_slots`` is every slot
    routed (tokens × k × MoE layers), ``moe.held_slots`` the share that
    reached a held expert (the sum of the rounds' held loads), and
    ``moe.held_slots_max`` the largest held expert's slots in one event."""
    from repro.core import init_opt_state, make_train_step
    from repro.data.pipeline import make_batch_fn
    from repro.models import init_model, model_loss, moe
    from repro.train.loop import train
    cfg, run = _moonlight(), _MOE_RUN
    before = telemetry.counters()
    train(cfg, run, steps=2, batch=4, seq=16)
    routed = _delta(before, moe.ROUTED_SLOTS)
    assert routed == 2 * 4 * 16 * cfg.top_k * cfg.n_units

    # the same two rounds by hand: the held loads of each event
    params = init_model(cfg, jax.random.PRNGKey(run.seed))
    step = jax.jit(make_train_step(
        run, lambda p, b, sample_weights=None: model_loss(cfg, run, p, b),
        post_event=moe.routing_bias_update(cfg)))
    opt = init_opt_state(run, params)
    feed = make_batch_fn(cfg, 4, 16, seed=run.seed)
    # one event's loads: the loss's own metrics over that event's rows,
    # summed over its micro-batches
    loads = jax.jit(lambda p, b: sum(
        model_loss(cfg, run, p, jax.tree.map(lambda x: x[i::2], b))[1][
            "counts"][moe.ROUTED_SLOTS] for i in range(2)))
    held, held_max = 0, 0
    for r in range(2):
        b = jax.tree.map(jnp.asarray, feed(r))
        for j in range(2):                          # the round's events
            ev = jax.tree.map(lambda x: x[j::2], b)
            load = np.asarray(loads(params, ev))[:, 1:3]
            held += int(load.sum())
            held_max = max(held_max, int(load.max()))
        params, opt, m = step(params, opt, b)
    assert _delta(before, moe.HELD_SLOTS) == held
    assert 0 < held < routed
    assert telemetry.counters()[moe.HELD_SLOTS_MAX] >= held_max


def test_dropless_builds_count_at_trace_time():
    from repro.models import init_model, model_forward, moe
    cfg = _moonlight()
    params = init_model(cfg, jax.random.PRNGKey(0))
    fwd = jax.jit(lambda p, t: model_forward(cfg, _MOE_RUN, p,
                                             {"tokens": t})[0])
    before = telemetry.counters()
    tok = jnp.zeros((2, 16), jnp.int32)
    fwd(params, tok)
    fwd(params, tok + 1)                 # same shapes: no new build
    assert _delta(before, moe.DROPLESS_BUILDS) == 1
    fwd(params, jnp.zeros((2, 32), jnp.int32))
    assert _delta(before, moe.DROPLESS_BUILDS) == 2


@pytest.mark.parametrize("causal,window,ranges", [
    (True, 0, [(0, 1), (0, 2), (0, 3), (0, 4)]),
    (False, 0, [(0, 4)] * 4),
    # a 1,500-token window leaves the last Q chunk's first KV chunk out
    (True, 1500, [(0, 1), (0, 2), (0, 3), (1, 4)]),
])
def test_chunked_attention_counts_and_scans_live_blocks(causal, window,
                                                        ranges):
    """At 4,096 tokens in 1,024-wide chunks (the train cell's shapes), traced
    only: the counters read the live blocks, and the traced program scans
    exactly those, one scan per Q chunk."""
    from repro.models import attention
    assert attention.kv_block_ranges(4096, 4096, 1024, 1024, causal=causal,
                                     window=window) == ranges
    q = jax.ShapeDtypeStruct((1, 4096, 2, 8), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4096, 1, 8), jnp.bfloat16)
    before = telemetry.counters()
    jaxpr = jax.make_jaxpr(lambda q, k, v: attention.chunked_attention(
        q, k, v, causal=causal, window=window))(q, kv, kv)
    computed = sum(hi - lo for lo, hi in ranges)
    assert _delta(before, attention.KV_BLOCKS) == computed
    assert _delta(before, attention.KV_BLOCKS_SKIPPED) == 16 - computed
    lengths = [e.params["length"] for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "scan"]
    assert lengths == [hi - lo for lo, hi in ranges]


def test_compiled_step_carries_the_mla_and_moe_scopes():
    """The scopes land in the compiled step's op metadata, where a
    profiler's viewer shows them."""
    from repro.core import init_opt_state, make_train_step
    from repro.data.pipeline import make_batch_fn
    from repro.models import init_model, model_loss, moe
    cfg, run = _moonlight(), _MOE_RUN
    params = init_model(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        run, lambda p, b, sample_weights=None: model_loss(cfg, run, p, b),
        post_event=moe.routing_bias_update(cfg)))
    batch = jax.tree.map(jnp.asarray, make_batch_fn(cfg, 4, 16)(0))
    text = step.lower(params, init_opt_state(run, params),
                      batch).compile().as_text()
    for scope in ("mla.project", "mla.attend", "moe.route", "moe.dispatch",
                  "moe.experts", "moe.shared", "moe.combine"):
        assert scope + "/" in text, scope
