"""SPMD distributed replay (DESIGN.md §13): the emulated device mesh,
placement planning, the shard_mapped replay's equivalence pins against
single-device replay, the schedule-cache key audit, and the sharding-policy
PartitionSpec rules.

Device-dependent tests skip below their device count; the `multi-device` CI
lane runs the whole suite under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Tolerance policy (measured on CPU, pinned here):

* what-if spmd replay is **bitwise**;
* staged-gradient spmd paths track single-device replay to ~1 ulp/event
  (measured 6e-8..1.2e-7 after ~24 steps; XLA fuses the combine/update
  chain differently inside the shard_map body, and L > 1 psum partial-sum
  order) — pinned with atol=1e-5.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, RunConfig
from repro.core import engine, replay
from repro.core.engine import replay_batch
from repro.core.trace import (_REPLAY_ONLY_FIELDS, _schedule_key,
                              PlacementPlan, placement_plan, schedule_cached)
from repro.experiments.problems import QuadraticProblem
from repro.launch import mesh as mesh_lib
from repro.membership import MembershipTimeline
from repro.serve.fleet import FleetConfig

DEV = jax.device_count()


# ---------------------------------------------------------------------------
# shared toy problems
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


def _cfg(**kw):
    base = dict(protocol="softsync", n_softsync=4, n_learners=16,
                minibatch=8, base_lr=0.05, lr_policy="staleness_inverse",
                optimizer="momentum", seed=7)
    base.update(kw)
    return RunConfig(**base)


def _replay_pair(cfg, steps=24, **kw):
    """(single, spmd) results for the SAME trace object."""
    trace = schedule_cached(cfg, steps)
    common = dict(grad_fn=GRAD_FN, init_params=jnp.zeros((6, 3)),
                  batch_fn=_batch_fn, **kw)
    single = replay(trace, cfg, **common)
    spmd = replay(trace, cfg, placement="spmd", **common)
    return single, spmd


def _assert_close(a, b, atol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=atol, rtol=0)


# ---------------------------------------------------------------------------
# mesh bring-up: ensure_host_devices / debug meshes (satellite 1)
# ---------------------------------------------------------------------------
def test_ensure_host_devices_validates_n():
    with pytest.raises(ValueError, match="at least 1"):
        mesh_lib.ensure_host_devices(0)


def test_ensure_host_devices_noop_when_satisfied():
    assert mesh_lib.ensure_host_devices(1) == DEV
    assert mesh_lib.ensure_host_devices(DEV) == DEV


def test_ensure_host_devices_clear_error_after_init():
    """jax is live (DEV above) — asking for more devices than exist must
    raise the actionable error, not silently edit a dead env var."""
    before = os.environ.get("XLA_FLAGS")
    with pytest.raises(RuntimeError, match="ensure_host_devices"):
        mesh_lib.ensure_host_devices(4096)
    assert os.environ.get("XLA_FLAGS") == before


@pytest.mark.skipif(DEV >= 4, reason="needs a device-starved host")
def test_make_debug_mesh_names_the_fix():
    """The old failure was XLA's opaque mesh-shape error; now the message
    says how to launch."""
    with pytest.raises(RuntimeError, match="ensure_host_devices"):
        mesh_lib.make_debug_mesh()


@pytest.mark.skipif(DEV < 4, reason="needs >=4 emulated devices")
def test_debug_mesh_axes():
    m = mesh_lib.make_debug_mesh(2, 2)
    assert m.axis_names == ("data", "model")
    assert mesh_lib.data_axes(m) == ("data",)
    assert mesh_lib.n_learners(m) == 2
    assert mesh_lib.n_chips(m) == 4


@pytest.mark.skipif(DEV < 4, reason="needs >=4 emulated devices")
def test_sim_mesh_axes():
    m = mesh_lib.make_sim_mesh(2, 2)
    assert m.axis_names == mesh_lib.SIM_AXES == ("ps", "learner")
    # the sim mesh has no 'data'/'pod' axes: it is not a learner mesh
    assert mesh_lib.data_axes(m) == ()
    assert mesh_lib.n_chips(m) == 4


# ---------------------------------------------------------------------------
# placement planning
# ---------------------------------------------------------------------------
def test_placement_plan_auto_learners():
    cfg = _cfg(shards=4)                      # c = 16/4 = 4 slots
    trace = schedule_cached(cfg, 12)
    plan = placement_plan(trace, cfg, device_count=8)
    assert (plan.shards, plan.learners) == (4, 2)   # largest divisor of 4
    assert plan.devices == 8 and plan.slot_block == 2
    assert placement_plan(trace, cfg, device_count=4).learners == 1
    assert "4ps" in plan.describe()


def test_placement_plan_explicit_learners():
    cfg = _cfg(shards=2, placement="spmd", spmd_learners=2)
    trace = schedule_cached(cfg, 12)
    plan = placement_plan(trace, cfg, device_count=4)
    assert (plan.shards, plan.learners) == (2, 2)
    with pytest.raises(RuntimeError, match="spmd_learners"):
        placement_plan(trace, cfg, device_count=2)  # 2ps×2l needs 4


def test_placement_plan_device_shortfall_names_the_fix():
    cfg = _cfg(shards=4)
    trace = schedule_cached(cfg, 12)
    with pytest.raises(RuntimeError, match="ensure_host_devices"):
        placement_plan(trace, cfg, device_count=2)


def test_spmd_config_validation():
    with pytest.raises(ValueError, match="kernel-supported"):
        _cfg(placement="spmd", optimizer="adamw")
    with pytest.raises(ValueError, match="spmd"):
        _cfg(spmd_learners=2)                 # needs placement="spmd"
    with pytest.raises(ValueError, match="divide"):
        _cfg(placement="spmd", spmd_learners=3)   # c = 4
    with pytest.raises(ValueError, match="placement"):
        _cfg(placement="bogus")


def test_replay_rejects_unknown_placement_and_assembly():
    cfg = _cfg()
    trace = schedule_cached(cfg, 8)
    kw = dict(grad_fn=GRAD_FN, init_params=jnp.zeros((6, 3)),
              batch_fn=_batch_fn)
    with pytest.raises(ValueError, match="placement"):
        replay(trace, cfg, placement="multihost", **kw)


def test_replay_batch_rejects_spmd_lanes():
    cfg = _cfg(placement="spmd")
    trace = schedule_cached(cfg, 8)
    with pytest.raises(ValueError, match="single-placement"):
        replay_batch([trace], [cfg], grad_fn=GRAD_FN,
                     init_params=jnp.zeros((6, 3)), batch_fns=[_batch_fn])


# ---------------------------------------------------------------------------
# equivalence pins: 1×1 mesh (always run — any device count)
# ---------------------------------------------------------------------------
def test_spmd_matches_single_combine_1x1():
    single, spmd = _replay_pair(_cfg())
    _assert_close(spmd.params, single.params)
    assert spmd.updates == single.updates
    assert spmd.simulated_time == pytest.approx(single.simulated_time)


def test_spmd_matches_single_sequential_1x1():
    single, spmd = _replay_pair(_cfg(lr_policy="per_gradient"))
    _assert_close(spmd.params, single.params)


def test_spmd_whatif_bitwise_1x1():
    prob = QuadraticProblem(d=64, seed=3)
    cfg = _cfg()
    trace = schedule_cached(cfg, 24)
    kw = dict(grad_fn=prob.grad_fn, init_params=prob.init,
              batch_fn=prob.batch_fn_for(cfg.minibatch),
              flat_grad=prob.flat_grad)
    single = replay(trace, cfg, **kw)
    spmd = replay(trace, cfg, placement="spmd", **kw)
    np.testing.assert_array_equal(np.asarray(spmd.params["w"]),
                                  np.asarray(single.params["w"]))


def test_spmd_eval_history_1x1():
    eval_fn = lambda p: {"err": float(jnp.mean((X @ p - Y) ** 2))}
    single, spmd = _replay_pair(_cfg(), steps=20, eval_fn=eval_fn,
                                eval_every=5)
    assert len(spmd.history) == len(single.history) == 4
    for a, b in zip(spmd.history, single.history):
        assert a["update"] == b["update"]
        assert a["time"] == pytest.approx(b["time"])
        assert a["err"] == pytest.approx(b["err"], abs=1e-5)


# ---------------------------------------------------------------------------
# equivalence pins: the 8-device emulated cluster (the CI multi-device lane)
# ---------------------------------------------------------------------------
need8 = pytest.mark.skipif(DEV < 8, reason="needs 8 emulated devices "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")


@need8
@pytest.mark.parametrize("shards", [2, 4])
def test_spmd_matches_single_sharded(shards):
    single, spmd = _replay_pair(_cfg(shards=shards))
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_matches_single_explicit_learners():
    # force the full 4ps×2learner mesh (c = 4 → slot_block 2): the psum
    # combine path, not just the L=1 full-width einsum
    single, spmd = _replay_pair(_cfg(shards=4, placement="spmd",
                                     spmd_learners=2))
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_matches_single_groups():
    single, spmd = _replay_pair(_cfg(n_softsync=2, groups=8))
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_matches_single_elastic_masked():
    churn = MembershipTimeline(((2.0, 0, "crash"), (3.5, 0, "join"),
                                (4.0, 1, "leave")))
    cfg = _cfg(n_softsync=2, n_learners=8, shards=2, membership=churn)
    trace = schedule_cached(cfg, 24)
    assert trace.valid is not None            # the masked path actually ran
    single, spmd = _replay_pair(cfg)
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_matches_single_bf16_ring():
    single, spmd = _replay_pair(_cfg(shards=4, ring_dtype="bf16"))
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_matches_single_pallas_ring(monkeypatch):
    monkeypatch.setattr(engine, "_event_body", lambda spec: "pallas")
    single, spmd = _replay_pair(_cfg(shards=4), steps=16)
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_sequential_sharded():
    single, spmd = _replay_pair(_cfg(shards=2,
                                     lr_policy="per_gradient"))
    _assert_close(spmd.params, single.params)


@need8
def test_spmd_whatif_sharded():
    """What-if gradients are shard-local (no collectives), but at S > 1
    the single-device comparison point is the *staged* sharded replay —
    a different gradient code path — so this pin is ~1 ulp (measured
    3e-8), not bitwise; the same-path bitwise pin is the S=1 test above."""
    prob = QuadraticProblem(d=64, seed=3)
    cfg = _cfg(shards=4)
    trace = schedule_cached(cfg, 24)
    kw = dict(grad_fn=prob.grad_fn, init_params=prob.init,
              batch_fn=prob.batch_fn_for(cfg.minibatch),
              flat_grad=prob.flat_grad)
    single = replay(trace, cfg, **kw)
    spmd = replay(trace, cfg, placement="spmd", **kw)
    _assert_close(spmd.params["w"], single.params["w"])


# ---------------------------------------------------------------------------
# schedule_cached key audit (satellite 2)
# ---------------------------------------------------------------------------
# one entry PER RunConfig FIELD: the override dict that flips it to a valid
# non-default value (companion fields satisfy __post_init__ and are applied
# to both sides of the comparison, so only the audited field differs).
_CHURN = MembershipTimeline(((1.0, 0, "leave"),))
_FIELD_FLIPS = {
    "protocol": {"protocol": "async"},
    "n_softsync": {"protocol": "softsync", "n_softsync": 2},
    "n_learners": {"n_learners": 2},
    "minibatch": {"minibatch": 64},
    "base_lr": {"base_lr": 0.01},
    "ref_batch": {"ref_batch": 64},
    "lr_policy": {"lr_policy": "staleness_inverse"},
    "momentum": {"momentum": 0.5},
    "optimizer": {"optimizer": "adagrad"},
    "weight_decay": {"weight_decay": 0.01},
    "warmstart_epochs": {"warmstart_epochs": 1},
    "seed": {"seed": 1},
    "duration_model": {"duration_model": "two_speed"},
    "slow_fraction": {"slow_fraction": 0.5},
    "slow_factor": {"slow_factor": 2.0},
    "pareto_alpha": {"pareto_alpha": 2.0},
    "pareto_scale": {"pareto_scale": 1.0},
    "shards": {"shards": 2},
    "groups": {"n_learners": 4, "groups": 2},
    "shard_pull_jitter": {"shard_pull_jitter": 0.5},
    "ring_dtype": {"ring_dtype": "bf16"},
    "placement": {"placement": "spmd"},
    "spmd_learners": {"n_learners": 2, "placement": "spmd",
                      "spmd_learners": 2},
    "membership": {"n_learners": 4, "membership": _CHURN},
    "backup": {"n_learners": 4, "backup": 1},
    "num_microbatches": {"num_microbatches": 2},
    "remat": {"remat": False},
    "fsdp": {"fsdp": True},
    "use_pallas": {"use_pallas": True},
    "attn_impl": {"attn_impl": "naive"},
    "attn_q_chunk": {"attn_q_chunk": 512},
    "attn_kv_chunk": {"attn_kv_chunk": 512},
    "unroll": {"unroll": True},
    "residual_spec": {"residual_spec": (("data",), None)},
    # schedule-relevant: the serving lane resolves inside schedule() (the
    # ServingTrace rides the arrival trace), so fleets key distinct traces
    "serving": {"serving": FleetConfig(replicas=1)},
}


def test_schedule_cached_field_audit():
    """Every RunConfig field must be triaged: replay-only fields (and ONLY
    those) canonicalize out of the schedule-cache key.  Adding a field
    without classifying it — here and, if replay-only, in
    ``trace._REPLAY_ONLY_FIELDS`` — fails the coverage assert."""
    names = {f.name for f in dataclasses.fields(RunConfig)}
    assert names == set(_FIELD_FLIPS), (
        "new RunConfig field(s) need a flip entry + schedule/replay triage: "
        f"{names ^ set(_FIELD_FLIPS)}")
    assert set(_REPLAY_ONLY_FIELDS) <= names

    for name, flip in _FIELD_FLIPS.items():
        companions = {k: v for k, v in flip.items() if k != name}
        base = RunConfig(**companions)
        flipped = RunConfig(**flip)
        assert getattr(flipped, name) != getattr(base, name), name
        same_key = _schedule_key(flipped) == _schedule_key(base)
        assert same_key == (name in _REPLAY_ONLY_FIELDS), (
            f"{name}: schedule-cache key {'ignores' if same_key else 'keys'}"
            f" this field, but _REPLAY_ONLY_FIELDS says the opposite")


def test_schedule_cached_shares_and_splits_entries():
    """The regression this audit guards: replay-only flips share ONE cached
    trace; membership/backup (schedule-relevant) key distinct traces."""
    schedule_cached.cache_clear()
    base = _cfg()
    t0 = schedule_cached(base, 10)
    assert schedule_cached(base.replace(ring_dtype="bf16"), 10) is t0
    assert schedule_cached(base.replace(placement="spmd"), 10) is t0
    churn = MembershipTimeline(((1.0, 0, "leave"),))
    assert schedule_cached(base.replace(membership=churn), 10) is not t0
    hard = RunConfig(protocol="hardsync", n_learners=4, seed=7)
    assert schedule_cached(hard, 10) is not \
        schedule_cached(hard.replace(backup=1), 10)
    assert schedule_cached.cache_info().currsize == 4


# ---------------------------------------------------------------------------
# sharding-policy PartitionSpecs (satellite 3)
# ---------------------------------------------------------------------------
def _toy_params_shape():
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return {
        "embed": sds(64, 8),                  # (V, M)
        "head": sds(8, 64),                   # (M, V)
        "final_norm": sds(8,),
        "units": {
            "attn": {"w_q": sds(3, 8, 4, 2),  # (U, M, H, dh)
                     "b_q": sds(3, 4, 2)},    # (U, H, dh)
            "mlp": {"w_gate": sds(3, 8, 16),
                    "w_down": sds(3, 16, 8)},
        },
    }


@pytest.mark.skipif(DEV < 4, reason="needs a 2x2 debug mesh")
def test_param_shardings_head_mode_2x2():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import param_shardings
    mesh = mesh_lib.make_debug_mesh(2, 2)
    sh = param_shardings(_toy_params_shape(), mesh, fsdp=False, mode="head")
    assert sh["embed"].spec == P("model", None)
    assert sh["head"].spec == P(None, "model")
    assert sh["final_norm"].spec == P(None)
    assert sh["units"]["attn"]["w_q"].spec == P(None, None, "model", None)
    assert sh["units"]["attn"]["b_q"].spec == P(None, "model", None)
    assert sh["units"]["mlp"]["w_gate"].spec == P(None, None, "model")
    assert sh["units"]["mlp"]["w_down"].spec == P(None, "model", None)
    # the layer-stack axis (dim 0 of units leaves) is never sharded
    for leaf in jax.tree.leaves(sh["units"]):
        assert leaf.spec[0] is None


@pytest.mark.skipif(DEV < 4, reason="needs a 2x2 debug mesh")
def test_param_shardings_fsdp_2x2():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import param_shardings
    mesh = mesh_lib.make_debug_mesh(2, 2)
    sh = param_shardings(_toy_params_shape(), mesh, fsdp=True, mode="head")
    # FSDP shards the largest still-replicated dim over the data axis
    assert sh["units"]["attn"]["w_q"].spec == P(None, "data", "model", None)
    # seq mode + fsdp_wide: weights replicated over model get ZeRO-3
    # sharding over (data, model) jointly
    sh = param_shardings(_toy_params_shape(), mesh, fsdp=True, mode="seq",
                         fsdp_wide=True)
    assert sh["units"]["attn"]["w_q"].spec == \
        P(None, ("data", "model"), None, None)
    # embed already uses "model", so it gets plain (non-wide) data-sharding
    assert sh["embed"].spec == P("model", "data")


@pytest.mark.skipif(DEV < 4, reason="needs a 2x2 debug mesh")
def test_batch_spec_for_2x2():
    from repro.configs import get_config
    from repro.launch.sharding import batch_spec_for
    mesh = mesh_lib.make_debug_mesh(2, 2)
    cfg = get_config("qwen2_1_5b")
    bspec, sspec = batch_spec_for(cfg, mesh, "seq", batch=8, seq=64)
    assert bspec == "data" and sspec == "model"
    bspec, sspec = batch_spec_for(cfg, mesh, "head", batch=3, seq=64)
    assert bspec is None and sspec is None


def test_parallelism_mode_thresholds():
    """head/seq selection is a divisibility rule on the model axis; FSDP
    thresholds depend on the selected mode (5e10 head / 5e9 seq)."""
    from repro.configs import get_config
    from repro.launch.sharding import parallelism_mode
    q2 = get_config("qwen2_1_5b")             # 12 heads
    assert parallelism_mode(q2, 16) == "seq"  # 12 % 16 != 0
    assert parallelism_mode(q2, 2) == "head"  # 12 % 2 == 0
    sc = get_config("starcoder2_7b")          # 36 heads
    assert parallelism_mode(sc, 8) == "seq"
    assert parallelism_mode(sc, 4) == "head"


@pytest.mark.skipif(DEV < 8, reason="needs a 1x8 debug mesh")
def test_needs_fsdp_mode_dependent_threshold():
    from repro.configs import get_config
    from repro.launch.sharding import needs_fsdp, parallelism_mode
    mesh = mesh_lib.make_debug_mesh(1, 8)     # model axis = 8
    sc = get_config("starcoder2_7b")          # seq at ms=8: 7B > 5e9
    assert parallelism_mode(sc, 8) == "seq" and needs_fsdp(sc, mesh)
    q3 = get_config("qwen3_14b")              # head at ms=8: 14B < 5e10
    assert parallelism_mode(q3, 8) == "head" and not needs_fsdp(q3, mesh)
    assert needs_fsdp(get_config("llama3_405b"), mesh)   # 405B > 5e10
