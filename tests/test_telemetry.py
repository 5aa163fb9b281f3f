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


def _cfg(impl, ring_dtype="bf16"):
    # 1-softsync over 6 learners: c = 6 pulled slots per event
    return RunConfig(protocol="softsync", n_softsync=1, n_learners=6,
                     minibatch=1, base_lr=0.02, optimizer="momentum",
                     ring_dtype=ring_dtype, seed=5, ring_impl=impl)


def _whatif(cfg, trace, **kw):
    i = jnp.arange(D, dtype=jnp.float32)
    a, wstar = 0.5 + (i % 100.0) / 100.0, jnp.sin(0.01 * i)
    return replay(trace, cfg, init_params={"w": jnp.zeros((D,), jnp.float32)},
                  flat_grad=("quadratic", lambda pos: (a[pos], wstar[pos])),
                  **kw)


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
    cfg = _cfg(impl)
    trace = schedule(cfg, 40)
    K = trace.max_staleness + 1
    assert trace.c == 6 and K >= 2
    before = telemetry.counters()
    _whatif(cfg, trace, eval_fn=(lambda p: {}) if every else None,
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
    cfg = _cfg("stock", ring_dtype="fp32")
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
    cfg = _cfg("pallas")
    trace = schedule(cfg, 24)
    _whatif(cfg, trace, eval_every=8, eval_fn=lambda p: {})   # compile
    with jax.profiler.trace(str(tmp_path)):
        res = _whatif(cfg, trace, eval_every=8,
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
