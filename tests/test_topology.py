"""The topology-aware PS subsystem (DESIGN.md §6): Rudra-base degeneracy
pinned bit-identical, shard partition invariance, per-shard staleness
semantics, learner-group aggregation, and the topology gates across the
engine / experiments surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.config import RunConfig
from repro.core import (ParameterServerState, Topology, replay, replay_batch,
                        schedule, simulate)
from repro.core.engine import _materialize_batches
from repro.experiments import ExperimentSpec, Sweep, run_sweep, validate_record
from repro.experiments import run as run_spec
from repro.experiments.problems import updates_for_epochs
from repro.optim import flatten


# ---------------------------------------------------------------------------
# shared toy problem (same as test_trace_engine)
# ---------------------------------------------------------------------------
KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (6, 3))
X = jax.random.normal(jax.random.PRNGKey(1), (64, 6))
Y = X @ W_TRUE


def _loss(p, b):
    x, y = b
    return jnp.mean((x @ p - y) ** 2)


GRAD_FN = jax.jit(jax.grad(_loss))


def _batch_fn(l, i):
    rng = np.random.default_rng(l * 9973 + i)
    idx = rng.integers(0, 64, size=8)
    return X[idx], Y[idx]


REPLAY_KW = dict(grad_fn=GRAD_FN, init_params=jnp.zeros((6, 3)),
                 batch_fn=_batch_fn)


def _base_run(**kw):
    base = dict(protocol="softsync", n_softsync=2, n_learners=8,
                minibatch=8, base_lr=0.05, lr_policy="staleness_inverse",
                optimizer="momentum", seed=7)
    base.update(kw)
    return RunConfig(**base)


# ---------------------------------------------------------------------------
# config + topology validation
# ---------------------------------------------------------------------------
def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(shards=0)
    with pytest.raises(ValueError):
        Topology(pull_jitter=-1.0)
    with pytest.raises(ValueError):
        Topology(groups=3).validate_for(8)      # 3 ∤ 8
    with pytest.raises(ValueError):
        RunConfig(n_learners=8, groups=3)
    with pytest.raises(ValueError):
        RunConfig(shards=0)
    with pytest.raises(ValueError):
        RunConfig(shard_pull_jitter=-0.1)


def test_rudra_arch_presets():
    assert Topology.for_arch("base", 30).is_trivial(30)
    adv = Topology.for_arch("adv", 30)
    assert adv.shards == 8 and not adv.grouped
    star = Topology.for_arch("adv*", 60, jitter=0.02)
    assert star.shards == 8 and star.group_size(60) == 4
    assert star.n_pushers(60) == 15
    assert not Topology.for_arch("adv*", 1).grouped   # single learner: flat
    with pytest.raises(ValueError):
        Topology.for_arch("adv*", 7)    # no group size in (4, 3, 2) — loud
    with pytest.raises(ValueError):
        Topology.for_arch("mega", 8)


def test_shard_bounds_cover_buffer():
    topo = Topology(shards=4)
    bounds = topo.shard_bounds(10)
    assert bounds[0] == (0, 3) and bounds[-1] == (9, 10)
    assert sum(hi - lo for lo, hi in bounds) == 10
    # S > D: trailing shards own empty (fully padded) slices
    tiny = Topology(shards=8).shard_bounds(5)
    assert sum(hi - lo for lo, hi in tiny) == 5
    assert all(lo <= hi for lo, hi in tiny)


def test_run_config_pusher_accounting():
    run = _base_run(groups=4)                       # λ=8 → gs=2, P=4
    assert run.n_pushers == 4 and run.group_size == 2
    assert run.gradients_per_update == 2            # ⌊P/n⌋ = ⌊4/2⌋
    assert _base_run().n_pushers == 8               # ungrouped: P = λ
    hard = _base_run(protocol="hardsync", groups=4)
    assert hard.gradients_per_update == 4           # hardsync: c = P


def test_updates_for_epochs_group_scaling():
    # every update consumes c·μ·gs samples: grouping divides the updates
    assert updates_for_epochs(1.0, 8, 4, 8_192) == 256
    assert updates_for_epochs(1.0, 8, 4, 8_192, group_size=2) == 128


# ---------------------------------------------------------------------------
# the pinned degeneracy: Rudra-base topology IS the existing path
# ---------------------------------------------------------------------------
def test_trivial_topology_trace_bit_identical():
    """S=1 / groups∈{0, λ} schedule the exact legacy trace: same arrays,
    same rng draw order, no shard matrix."""
    run = _base_run()
    tr0 = schedule(run, 40)
    for groups in (0, 8):                    # 0 = disabled, λ ⇒ gs = 1
        trg = schedule(run.replace(groups=groups), 40)
        np.testing.assert_array_equal(tr0.learner, trg.learner)
        np.testing.assert_array_equal(tr0.pulled_ts, trg.pulled_ts)
        np.testing.assert_array_equal(tr0.mb_index, trg.mb_index)
        np.testing.assert_array_equal(tr0.event_time, trg.event_time)
        np.testing.assert_array_equal(tr0.lrs, trg.lrs)
        assert trg.shard_pulled_ts is None
        assert trg.group_size == 1 and trg.minibatches == tr0.minibatches


def test_trivial_topology_replay_bit_identical():
    """groups=λ replays bit-identical to the existing (ungrouped) engine
    path — same scan program, same inputs, byte-equal parameters."""
    run = _base_run()
    res0 = replay(schedule(run, 30), run, **REPLAY_KW)
    rung = run.replace(groups=8)
    resg = replay(schedule(rung, 30), rung, **REPLAY_KW)
    np.testing.assert_array_equal(np.asarray(res0.params),
                                  np.asarray(resg.params))


def test_trivial_topology_still_matches_legacy_oracle():
    """The acceptance anchor: explicit Rudra-base topology ≡ the legacy
    per-arrival loop (the pre-topology contract of test_trace_engine)."""
    run = _base_run(shards=1, groups=0)
    kw = dict(steps=25, **REPLAY_KW)
    legacy = simulate(run, **kw)
    compiled = replay(schedule(run, 25), run, **REPLAY_KW)
    np.testing.assert_allclose(np.asarray(compiled.params),
                               np.asarray(legacy.params),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# shard partition invariance (the satellite property, deterministic form;
# hypothesis sweep in tests/test_topology_properties.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad"])
@pytest.mark.parametrize("mode", ["combine", "sequential"])
def test_shard_partitioned_apply_equals_unsharded(optimizer, mode):
    rng = np.random.default_rng(3)
    D, c = 23, 3
    spec = optim.UpdateSpec(optimizer=optimizer)
    w = jnp.asarray(rng.normal(size=D), jnp.float32)
    s = (None if optimizer == "sgd"
         else jnp.asarray(rng.random(D), jnp.float32))
    g = jnp.asarray(rng.normal(size=(c, D)), jnp.float32)
    coef = jnp.full((c,), 1.0 / c, jnp.float32)
    lrs = jnp.asarray([0.1, 0.05, 0.2], jnp.float32)
    w_full, s_full = optim.apply_event_flat(spec, w, s, g, coef, lrs, mode)
    for bounds in ([(0, 23)], [(0, 7), (7, 23)], [(0, 1), (1, 22), (22, 23)]):
        parts = [optim.apply_event_flat(
                     spec, w[lo:hi], None if s is None else s[lo:hi],
                     g[:, lo:hi], coef, lrs, mode)
                 for lo, hi in bounds]
        w_cat = jnp.concatenate([p[0] for p in parts])
        np.testing.assert_array_equal(np.asarray(w_cat),
                                      np.asarray(w_full))
        if s is not None:
            s_cat = jnp.concatenate([p[1] for p in parts])
            np.testing.assert_array_equal(np.asarray(s_cat),
                                          np.asarray(s_full))


def test_flat_event_on_shard_slices_matches_flat():
    rng = np.random.default_rng(5)
    D, c, S = 10, 2, 4
    spec = optim.UpdateSpec(optimizer="momentum")
    dp = Topology(shards=S).padded_width(D)
    w = jnp.asarray(rng.normal(size=D), jnp.float32)
    s = jnp.asarray(rng.random(D), jnp.float32)
    g = jnp.asarray(rng.normal(size=(c, D)), jnp.float32)
    coef = jnp.full((c,), 0.5, jnp.float32)
    lrs = jnp.asarray([0.1, 0.3], jnp.float32)
    # the flat event on each shard's (Dp,) slice
    w_rows, s_rows = flatten.shard_pack(w, S, dp), flatten.shard_pack(s, S, dp)
    g_rows = flatten.shard_pack(g, S, dp)                    # (c, S, Dp)
    ws, ss = (jnp.stack(v) for v in zip(*[
        optim.apply_event_flat(spec, w_rows[j], s_rows[j], g_rows[:, j],
                               coef, lrs, "combine") for j in range(S)]))
    w_full, s_full = optim.apply_event_flat(spec, w, s, g, coef, lrs,
                                            "combine")
    np.testing.assert_allclose(np.asarray(ws.reshape(-1)[:D]),
                               np.asarray(w_full), atol=1e-7)
    np.testing.assert_allclose(np.asarray(ss.reshape(-1)[:D]),
                               np.asarray(s_full), atol=1e-7)
    # padding rows stay identically zero through the event
    assert float(jnp.abs(ws.reshape(-1)[D:]).max()) == 0.0


# ---------------------------------------------------------------------------
# sharded replay: consistent reads ≡ unsharded; jittered reads well-formed
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards", [2, 5])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad"])
def test_sharded_replay_matches_unsharded(shards, optimizer):
    """pull_jitter = 0 ⇒ every shard slice is the consistent snapshot, so
    the vmapped per-shard replay must reproduce the flat replay (partition
    invariance end-to-end; fp drift from vmap fusion only)."""
    run = _base_run(optimizer=optimizer)
    runs = run.replace(shards=shards)
    tr0, trs = schedule(run, 25), schedule(runs, 25)
    np.testing.assert_array_equal(
        trs.shard_pulled_ts,
        np.broadcast_to(tr0.pulled_ts[:, :, None],
                        tr0.pulled_ts.shape + (shards,)))
    res0 = replay(tr0, run, **REPLAY_KW)
    ress = replay(trs, runs, **REPLAY_KW)
    np.testing.assert_allclose(np.asarray(ress.params),
                               np.asarray(res0.params),
                               atol=1e-5, rtol=1e-5)


def test_sharded_zero_jitter_consistent_under_tied_clocks():
    """pull_jitter = 0 must mean consistent snapshot reads even when a
    deterministic duration sampler makes updates fire at the exact same
    clock instant as pulls (the searchsorted tie hazard)."""
    run = _base_run(shards=4)
    tr = schedule(run, 30, duration_sampler=lambda rng, mu: 1.0)
    np.testing.assert_array_equal(
        tr.shard_pulled_ts,
        np.broadcast_to(tr.pulled_ts[:, :, None],
                        tr.pulled_ts.shape + (4,)))


def test_sharded_jitter_staleness_semantics():
    run = _base_run(shards=4, shard_pull_jitter=0.5, seed=11)
    tr = schedule(run, 60)
    sig = tr.shard_staleness
    assert sig.shape == (60, tr.c, 4)
    # per-shard reads are never staler than the logical pull, never future
    assert (sig >= 0).all()
    assert (tr.shard_pulled_ts >= tr.pulled_ts[:, :, None]).all()
    # the skew actually bites: some slices picked up later updates
    assert (tr.shard_pulled_ts > tr.pulled_ts[:, :, None]).any()
    # jitter is resolved from an independent rng stream: the arrival
    # schedule is untouched vs the unsharded run
    tr0 = schedule(_base_run(seed=11), 60)
    np.testing.assert_array_equal(tr.pulled_ts, tr0.pulled_ts)
    np.testing.assert_array_equal(tr.event_time, tr0.event_time)
    res = replay(tr, run, **REPLAY_KW)
    assert np.isfinite(np.asarray(res.params)).all()


# ---------------------------------------------------------------------------
# learner groups: aggregation semantics
# ---------------------------------------------------------------------------
def test_grouped_hardsync_equals_ungrouped():
    """mean over G groups of mean over gs members == global mean: grouped
    hardsync must reproduce flat hardsync (fp reassociation only)."""
    run = RunConfig(protocol="hardsync", n_learners=4, minibatch=8,
                    base_lr=0.05, optimizer="momentum", seed=3)
    rung = run.replace(groups=2)
    res0 = replay(schedule(run, 12), run, **REPLAY_KW)
    resg = replay(schedule(rung, 12), rung, **REPLAY_KW)
    np.testing.assert_allclose(np.asarray(resg.params),
                               np.asarray(res0.params),
                               atol=1e-5, rtol=1e-5)


def test_group_push_is_member_max_duration():
    run = RunConfig(protocol="async", n_learners=4, minibatch=8,
                    groups=2, seed=0)
    tr = schedule(run, 3, duration_sampler=lambda rng, mu, l: 1.0 + l)
    # group 0 = {0,1} pushes every max(1,2)=2 s; group 1 = {2,3} every 4 s
    np.testing.assert_allclose(tr.event_time, [2.0, 4.0, 4.0])
    assert tr.minibatches == 3 * 1 * 2          # steps · c · gs


def test_grouped_softsync_replay_learns():
    run = _base_run(groups=2, n_softsync=1, base_lr=0.1)   # P=2, c=2, gs=4
    tr = schedule(run, 150)
    assert tr.c == 2 and tr.group_size == 4
    mem = tr.member_learners()
    assert mem.shape == (150, 2, 4)
    # contiguous blocks: group g = learners [4g, 4g+4)
    assert set(mem[0, 0].tolist()) in ({0, 1, 2, 3}, {4, 5, 6, 7})
    res = replay(tr, run, **REPLAY_KW)
    err = float(jnp.mean((X @ res.params - Y) ** 2))
    assert err < 0.1 * float(jnp.mean(Y ** 2))


def test_grouped_batches_average_members():
    """The staged group minibatches are exactly the members' batch_fn
    outputs (slot-aligned), so the in-scan mean is the Eq.-3 group fold."""
    run = _base_run(groups=4, n_softsync=1, seed=2)        # gs=2
    tr = schedule(run, 6)
    staged = _materialize_batches(tr, _batch_fn)
    mem = tr.member_learners()
    x0 = np.asarray(_batch_fn(int(mem[3, 1, 0]), int(tr.mb_index[3, 1]))[0])
    np.testing.assert_array_equal(np.asarray(staged[0][3, 1, 0]), x0)


def test_sharded_grouped_combined():
    """adv*: shards + groups + skew compose in one replay."""
    run = _base_run(n_learners=8, groups=4, shards=3,
                    shard_pull_jitter=0.3, n_softsync=2)
    tr = schedule(run, 20)
    assert tr.group_size == 2 and tr.shard_pulled_ts.shape[-1] == 3
    res = replay(tr, run, **REPLAY_KW)
    assert np.isfinite(np.asarray(res.params)).all()


def test_per_gradient_lrs_with_topology():
    run = _base_run(groups=4, shards=2, lr_policy="per_gradient")
    tr = schedule(run, 15)
    assert tr.mode == "sequential"
    res = replay(tr, run, **REPLAY_KW)
    assert np.isfinite(np.asarray(res.params)).all()


# ---------------------------------------------------------------------------
# gates: where non-trivial topologies must be refused
# ---------------------------------------------------------------------------
def test_host_ps_and_legacy_engine_reject_topology():
    run = _base_run(shards=2)
    with pytest.raises(ValueError):
        ParameterServerState.from_run(jnp.zeros((3,)), run)
    with pytest.raises(ValueError):
        simulate(run, steps=5, **REPLAY_KW)
    with pytest.raises(ValueError):
        ExperimentSpec(run=run, problem="mlp_teacher", steps=5,
                       engine="legacy")
    with pytest.raises(ValueError):             # adamw has no flat shards
        bad = _base_run(shards=2, optimizer="adamw")
        replay(schedule(bad, 5), bad, **REPLAY_KW)


def test_replay_batch_rejects_topology():
    run = _base_run(shards=2)
    tr = schedule(run, 10)
    with pytest.raises(ValueError):
        replay_batch([tr, tr], [run, run], batch_fns=[_batch_fn, _batch_fn],
                     **{k: v for k, v in REPLAY_KW.items()
                        if k != "batch_fn"})


def test_trace_topology_mismatch_rejected():
    run = _base_run(shards=2)
    tr = schedule(run, 10)
    with pytest.raises(ValueError):
        replay(tr, _base_run(), **REPLAY_KW)


# ---------------------------------------------------------------------------
# experiments surface: sweep fallback + record echo
# ---------------------------------------------------------------------------
def test_run_sweep_topology_falls_back_to_sequential():
    base = ExperimentSpec(
        run=_base_run(n_learners=8, groups=4, shards=2, minibatch=4,
                      optimizer="momentum"),
        problem="mlp_teacher", steps=12)
    sweep = Sweep.over(base, seed=[0, 1])
    batched = run_sweep(sweep)                  # must not try to vmap
    sequential = run_sweep(sweep, batch=False)
    assert len(batched) == 2
    for b, s in zip(batched, sequential):
        assert b.metrics["test_error"] == pytest.approx(
            s.metrics["test_error"], abs=1e-6)


def test_topology_echoed_in_records():
    spec = ExperimentSpec(
        run=RunConfig(protocol="softsync", n_softsync=1, n_learners=8,
                      groups=4, shards=2, shard_pull_jitter=0.1, seed=1),
        steps=50)                               # measure mode
    rec = run_spec(spec).record()
    validate_record(rec)
    assert rec["spec"]["run"]["shards"] == 2
    assert rec["spec"]["run"]["groups"] == 4
    assert rec["runtime"]["minibatches"] == 50 * 8 * 1  # c=P=... gs folded
