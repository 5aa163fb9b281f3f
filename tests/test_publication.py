"""Train-while-serve publication semantics (DESIGN.md §14).

The exactly-testable contract of ``repro.serve``:

* a publication is a ring-row read — the weights version v's snapshot, bit
  for bit the trained weights at v (prefix-replay comparison);
* a ``staleness`` policy's budget is never exceeded at any request (the
  refresh-before-request tie rule makes this exact, not probabilistic);
* attaching a fleet never perturbs training: the arrival schedule AND the
  replayed parameters are bitwise-identical to a no-serving run, under
  learner churn and replica churn alike;
* every guardrail (spmd, batched replay, the legacy oracle, missing serve
  hooks) errors actionably instead of silently degrading.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.config import RunConfig
from repro.core.engine import replay, replay_batch
from repro.core.simulator import simulate
from repro.core.trace import schedule, schedule_cached
from repro.experiments import (ExperimentSpec, Sweep, envelope, run,
                               run_sweep, validate_record)
from repro.experiments.problems import MLPProblem
from repro.membership import MembershipTimeline
from repro.serve.fleet import FleetConfig, ServingResult
from repro.serve.publication import PublicationPolicy, schedule_serving

MU = 16


def _run(policy=None, serving=True, **kw):
    fleet = None
    if serving:
        fleet = FleetConfig(replicas=2,
                            policy=policy or PublicationPolicy(),
                            request_rate=2.0, request_samples=8)
    base = dict(protocol="softsync", n_learners=4, n_softsync=2,
                minibatch=MU, lr_policy="staleness_inverse",
                optimizer="momentum", serving=fleet)
    base.update(kw)
    return RunConfig(**base)


@pytest.fixture(scope="module")
def prob():
    return MLPProblem()


def _replay(trace, cfg, prob, **kw):
    serve_kw = {}
    if trace.serving is not None:
        serve_kw = dict(
            serve_batches=prob.stage_requests(trace.serving, cfg.serving,
                                              seed=cfg.seed),
            serve_eval_fn=prob.request_metric)
    return replay(trace, cfg, grad_fn=prob.grad_fn, init_params=prob.init,
                  batch_fn=prob.batch_fn_for(cfg.minibatch),
                  **serve_kw, **kw)


def _tree_equal(a, b):
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------
def test_policy_validation():
    with pytest.raises(ValueError, match="unknown publication kind"):
        PublicationPolicy(kind="sometimes")
    with pytest.raises(ValueError, match="every must be >= 1"):
        PublicationPolicy(kind="every_n", every=0)
    with pytest.raises(ValueError, match="max_version_lag"):
        PublicationPolicy(max_version_lag=-1)
    with pytest.raises(ValueError, match="max_time_lag"):
        PublicationPolicy(kind="time", max_time_lag=0.0)


def test_fleet_validation():
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError, match="request_rate"):
        FleetConfig(request_rate=0.0)
    with pytest.raises(ValueError, match="diurnal_amplitude"):
        FleetConfig(diurnal_amplitude=1.5)
    with pytest.raises(ValueError, match="policy must be a"):
        FleetConfig(policy="every_n")
    # replica churn rides MembershipTimeline, validated against replicas
    with pytest.raises(ValueError, match="learner 5"):
        FleetConfig(replicas=2,
                    membership=MembershipTimeline(((1.0, 5, "crash"),)))
    # raw event tuples normalize, like RunConfig.membership
    fleet = FleetConfig(replicas=2, membership=((1.0, 0, "crash"),
                                                (2.0, 0, "join")))
    assert isinstance(fleet.membership, MembershipTimeline)
    assert "churn" not in str(FleetConfig())  # compact sweep-fragment tag
    assert "crash" in str(fleet)


def test_runconfig_serving_guardrails():
    with pytest.raises(ValueError, match="FleetConfig"):
        RunConfig(serving="fleet")
    with pytest.raises(ValueError, match="placement='spmd'"):
        _run(placement="spmd")
    _run(shards=2)          # the sharded ring serves fine


# ---------------------------------------------------------------------------
# schedule_serving semantics
# ---------------------------------------------------------------------------
def test_arrival_schedule_bitwise_unchanged_by_serving():
    cfg = _run()
    t_on = schedule(cfg, 30)
    t_off = schedule(cfg.replace(serving=None), 30)
    assert t_on.serving is not None and t_off.serving is None
    for field in ("learner", "pulled_ts", "mb_index", "event_time", "lrs"):
        np.testing.assert_array_equal(getattr(t_on, field),
                                      getattr(t_off, field))


@pytest.mark.parametrize("budget", [0, 1, 3])
def test_staleness_budget_never_exceeded(budget):
    cfg = _run(PublicationPolicy(kind="staleness", max_version_lag=budget))
    sv = schedule(cfg, 40).serving
    assert sv.n_requests > 0
    assert int(sv.staleness[sv.served].max(initial=0)) <= budget


def test_every_n_version_lag_bound():
    cfg = _run(PublicationPolicy(kind="every_n", every=5))
    sv = schedule(cfg, 40).serving
    assert int(sv.staleness[sv.served].max(initial=0)) <= 4


def test_on_demand_reads_are_fresh():
    cfg = _run(PublicationPolicy(kind="on_demand"))
    sv = schedule(cfg, 40).serving
    assert sv.n_requests > 0
    assert (sv.staleness[sv.served] == 0).all()
    v_now = schedule(cfg, 40).version_at(sv.request_time)
    np.testing.assert_array_equal(sv.version[sv.served], v_now[sv.served])


def test_time_budget_bounds_seconds_lag():
    cfg = _run(PublicationPolicy(kind="time", max_time_lag=3.0))
    sv = schedule(cfg, 40).serving
    assert sv.n_requests > 0
    assert float(sv.staleness_s[sv.served].max(initial=0.0)) <= 3.0


def test_tighter_budget_means_more_refreshes():
    refreshes = [schedule(_run(PublicationPolicy(max_version_lag=b)),
                          40).serving.n_refreshes
                 for b in (1, 4, 16)]
    assert refreshes[0] > refreshes[1] > refreshes[2]


def test_version_at_tie_rule():
    cfg = _run()
    trace = schedule(cfg, 10)
    t0 = float(trace.event_time[0])
    # an event applies before a same-instant read; strictly-before reads
    # still see the old version
    assert int(trace.version_at(t0)) == 1
    assert int(trace.version_at(np.nextafter(t0, 0.0))) == 0
    assert int(trace.version_at(0.0)) == 0
    assert int(trace.version_at(float(trace.event_time[-1]))) == 10


def test_diurnal_traffic_and_caps():
    flat = FleetConfig(request_rate=4.0)
    diurnal = dataclasses.replace(flat, diurnal_amplitude=0.9)
    trace = schedule(_run(serving=False), 40)
    sv_flat = schedule_serving(trace, flat, seed=0)
    sv_diur = schedule_serving(trace, diurnal, seed=0)
    assert sv_flat.n_requests > 0 and sv_diur.n_requests > 0
    # thinning only removes arrivals relative to the homogeneous envelope
    assert sv_diur.n_requests <= schedule_serving(
        trace, dataclasses.replace(flat, request_rate=4.0 * 1.9),
        seed=0).n_requests
    capped = schedule_serving(
        trace, dataclasses.replace(flat, max_requests=3), seed=0)
    assert capped.n_requests == 3 and capped.truncated


def test_replica_churn_drops_requests_only_while_fleet_dead():
    trace = schedule(_run(serving=False), 40)
    horizon = trace.simulated_time
    lo, hi = 0.25 * horizon, 0.5 * horizon
    fleet = FleetConfig(replicas=1, request_rate=8.0,
                        membership=((lo, 0, "crash"), (hi, 0, "join")))
    sv = schedule_serving(trace, fleet, seed=0)
    dead = (sv.request_time >= lo) & (sv.request_time < hi)
    assert dead.any() and (~dead).any()
    assert (sv.replica[dead] == -1).all()
    assert (sv.replica[~dead] == 0).all()
    # the restart re-publishes before serving again: budget still holds
    after = sv.served & (sv.request_time >= hi)
    assert int(sv.staleness[after].max(initial=0)) <= fleet.policy.max_version_lag


# ---------------------------------------------------------------------------
# the replay serving lane
# ---------------------------------------------------------------------------
# the component exported: the model's first weight, and with 2 PS shards
# its last, which the last shard holds
@pytest.mark.parametrize("shards,leaf,at", [(1, "w1", (0, 0)),
                                            (2, "w2", (-1, -1))])
def test_published_row_bitwise_equals_trained_weights(shards, leaf, at,
                                                      prob):
    """The tentpole contract: the snapshot serving version v is bit-for-bit
    the trained weights after v updates — checked by replaying each prefix
    of the (serving-free twin of the) trace and comparing a raw weight
    component exported through serve_eval_fn.  With PS shards a ring row
    is the shard slices concatenated, and the published row is still the
    whole model."""
    cfg = _run(PublicationPolicy(kind="every_n", every=1),
               protocol="async", shards=shards)
    steps = 10
    trace = schedule(cfg, steps)
    sv = trace.serving
    assert sv.n_requests > 0
    sim = replay(trace, cfg, grad_fn=prob.grad_fn, init_params=prob.init,
                 batch_fn=prob.batch_fn_for(cfg.minibatch),
                 serve_batches=prob.stage_requests(sv, cfg.serving),
                 serve_eval_fn=lambda p, b: p[leaf][at])
    got = sim.serving.request_metric

    bare = cfg.replace(serving=None)
    by_version = {0: float(np.asarray(prob.init[leaf])[at])}
    for i in np.flatnonzero(sv.served):
        v = int(sv.version[i])
        if v not in by_version:
            prefix = schedule(bare, v)   # same rng: the first v rows
            np.testing.assert_array_equal(prefix.pulled_ts,
                                          trace.pulled_ts[:v])
            psim = replay(prefix, bare, grad_fn=prob.grad_fn,
                          init_params=prob.init,
                          batch_fn=prob.batch_fn_for(cfg.minibatch))
            by_version[v] = float(np.asarray(psim.params[leaf])[at])
        assert got[i] == np.float32(by_version[v]), (i, v)


def test_serving_leaves_training_bitwise_unchanged(prob):
    cfg = _run()
    sim = _replay(schedule(cfg, 24), cfg, prob)
    bare = cfg.replace(serving=None)
    sim0 = _replay(schedule(bare, 24), bare, prob)
    assert _tree_equal(sim.params, sim0.params)
    assert isinstance(sim.serving, ServingResult) and sim0.serving is None


def test_serving_with_learner_churn_bitwise_pin(prob):
    """Replica crash/restart AND learner churn mid-trace leave the training
    replay bitwise-unchanged vs the same churny run without serving."""
    fleet = FleetConfig(replicas=2, request_rate=2.0, request_samples=8,
                        membership=((2.0, 1, "crash"), (6.0, 1, "join")))
    cfg = _run(serving=False,
               membership=MembershipTimeline.crash_restart([1], 3.0, 8.0))
    cfg = cfg.replace(serving=fleet)
    sim = _replay(schedule(cfg, 24), cfg, prob)
    bare = cfg.replace(serving=None)
    sim0 = _replay(schedule(bare, 24), bare, prob)
    assert _tree_equal(sim.params, sim0.params)
    assert sim.serving.summary()["n_served"] > 0


def test_bf16_ring_publishes_quantized_snapshots(prob):
    """Tolerance policy (§14): with a bf16 ring the published snapshot is
    the quantized row — error-feedback residue excluded — so a served
    weight component equals the prefix-replayed fp32 weights rounded
    through bf16."""
    cfg = _run(PublicationPolicy(kind="every_n", every=1),
               protocol="async", ring_dtype="bf16")
    trace = schedule(cfg, 8)
    sv = trace.serving
    sim = replay(trace, cfg, grad_fn=prob.grad_fn, init_params=prob.init,
                 batch_fn=prob.batch_fn_for(cfg.minibatch),
                 serve_batches=prob.stage_requests(sv, cfg.serving),
                 serve_eval_fn=lambda p, b: p["w1"][0, 0])
    import jax.numpy as jnp
    bare = cfg.replace(serving=None)
    for i in np.flatnonzero(sv.served)[:3]:
        v = int(sv.version[i])
        want = (np.asarray(prob.init["w1"])[0, 0] if v == 0 else
                np.asarray(_replay(schedule(bare, v), bare, prob)
                           .params["w1"])[0, 0])
        want_q = np.float32(jnp.asarray(want).astype(jnp.bfloat16)
                            .astype(jnp.float32))
        assert sim.serving.request_metric[i] == want_q, (i, v)


def test_serving_metrics_flow_through_driver():
    spec = ExperimentSpec(run=_run(), problem="mlp_teacher", steps=20)
    res = run(spec)
    for key in ("serving_accuracy", "serving_staleness_mean",
                "serving_latency_p99_s"):
        assert key in res.metrics
    summary = res.runtime["serving"]
    assert summary["n_requests"] == summary["n_served"] + summary["n_dropped"]
    assert 0.0 <= res.metrics["serving_accuracy"] <= 1.0
    # record JSON roundtrip, serving config echoed
    rec = json.loads(json.dumps(res.record()))
    validate_record(rec)
    assert rec["spec"]["run"]["serving"]["replicas"] == 2


def test_sweep_serving_axis_runs_sequential():
    spec = ExperimentSpec(run=_run(), problem="mlp_teacher", steps=16)
    fleets = [None] + [
        FleetConfig(replicas=2, request_rate=2.0, request_samples=8,
                    policy=PublicationPolicy(max_version_lag=b))
        for b in (1, 8)]
    grid = list(Sweep.over(spec, serving=fleets))
    assert len(grid) == 3
    with pytest.warns(RuntimeWarning, match="serving lane"):
        results = run_sweep(grid)
    assert "serving_accuracy" not in results[0].metrics
    assert all("serving_accuracy" in r.metrics for r in results[1:])
    assert results[1].runtime["replay_path"] == "sequential"
    env = envelope("t", records=[r.record() for r in results])
    json.dumps(env)   # sweep fragments + records all JSON-serializable


def test_schedule_cached_keys_on_fleet():
    schedule_cached.cache_clear()
    cfg = _run()
    t1 = schedule_cached(cfg, 10)
    assert schedule_cached(cfg, 10) is t1
    t2 = schedule_cached(
        cfg.replace(serving=dataclasses.replace(
            cfg.serving, request_rate=9.0)), 10)
    assert t2 is not t1
    assert t2.serving.n_requests != t1.serving.n_requests


# ---------------------------------------------------------------------------
# guardrails
# ---------------------------------------------------------------------------
def test_replay_requires_serve_hooks(prob):
    cfg = _run()
    trace = schedule(cfg, 10)
    with pytest.raises(ValueError, match="serve_batches"):
        replay(trace, cfg, grad_fn=prob.grad_fn, init_params=prob.init,
               batch_fn=prob.batch_fn_for(cfg.minibatch))
    bare = cfg.replace(serving=None)
    with pytest.raises(ValueError, match="no serving lane"):
        replay(schedule(bare, 10), bare, grad_fn=prob.grad_fn,
               init_params=prob.init, batch_fn=prob.batch_fn_for(MU),
               serve_eval_fn=prob.request_metric)
    # trace/run serving mismatch is caught before any compile
    with pytest.raises(ValueError, match="serving lane"):
        replay(trace, bare, grad_fn=prob.grad_fn, init_params=prob.init,
               batch_fn=prob.batch_fn_for(MU))


def test_replay_batch_rejects_serving_traces(prob):
    cfg = _run()
    traces = [schedule(cfg.replace(seed=s), 10) for s in (0, 1)]
    with pytest.raises(ValueError, match="batched replay does not support "
                                         "serving"):
        replay_batch(traces, [cfg.replace(seed=s) for s in (0, 1)],
                     grad_fn=prob.grad_fn, init_params=prob.init,
                     batch_fns=[prob.batch_fn_for(MU)] * 2)


def test_spmd_replay_rejects_serving_traces(prob):
    cfg = _run()
    trace = schedule(cfg, 10)
    with pytest.raises(ValueError, match="placement='spmd'"):
        replay(trace, cfg, grad_fn=prob.grad_fn, init_params=prob.init,
               batch_fn=prob.batch_fn_for(MU), placement="spmd",
               serve_batches=prob.stage_requests(trace.serving, cfg.serving),
               serve_eval_fn=prob.request_metric)


def test_legacy_and_oracle_reject_serving(prob):
    with pytest.raises(ValueError, match="legacy"):
        ExperimentSpec(run=_run(), problem="mlp_teacher", steps=10,
                       engine="legacy")
    with pytest.raises(ValueError, match="oracle has no serving lane"):
        simulate(_run(), steps=5, grad_fn=prob.grad_fn,
                 init_params=prob.init, batch_fn=prob.batch_fn_for(MU))


def test_driver_errors_on_problem_without_serve_hooks():
    spec = ExperimentSpec(run=_run(optimizer="momentum"),
                          problem="quadratic_whatif",
                          problem_args={"d": 64}, steps=10)
    with pytest.raises(ValueError, match="serving hooks"):
        run(spec)
