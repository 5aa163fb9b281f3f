"""Unified staleness-aware optimizer subsystem (repro.optim, DESIGN.md §3).

Backend-equivalence sweeps (reference / jit / pallas) across optimizer ×
mode × c with per-gradient staleness coefficients, dtype round-trips (bf16
params, fp32 accumulators), flat-buffer padding at odd sizes, and the two
regression tests from the applyUpdate unification: per-gradient LRs with
momentum (seed bug: silently fell back to plain SGD) and the fused softsync
engine's velocity carry (seed bug: dropped v0_coef)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.config import RunConfig
from repro.core import init_opt_state, make_train_step, simulate
from repro.core.lr_policies import make_lr_policy
from repro.core.protocols import ParameterServerState
from repro.optim import UpdateSpec, apply_update, init_state


def _mixed_tree(key, sizes=((300,), (17, 8), (4, 4, 4)), dtype=jnp.float32):
    ks = jax.random.split(key, len(sizes))
    return {f"p{i}": jax.random.normal(k, s, dtype)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def _grads(key, like, c):
    return [jax.tree.map(
        lambda p, k=k: jax.random.normal(k, p.shape, p.dtype), like)
        for k in jax.random.split(key, c)]


# ---------------------------------------------------------------------------
# backend equivalence: optimizer × mode × c vs the jnp reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad", "adamw"])
@pytest.mark.parametrize("mode", ["combine", "sequential"])
@pytest.mark.parametrize("c", [1, 3, 5])
def test_backend_equivalence(optimizer, mode, c):
    spec = UpdateSpec(optimizer=optimizer)
    params = _mixed_tree(jax.random.PRNGKey(c))
    grads = _grads(jax.random.PRNGKey(100 + c), params, c)
    # non-uniform per-gradient staleness coefficients + per-event LRs
    coef = jnp.asarray([1.0 / (i + 1) for i in range(c)]) / c
    lrs = jnp.asarray([0.1 / max(1.0, float(i)) for i in range(c)])
    outs = {}
    for backend in ("reference", "jit", "pallas"):
        p, s = apply_update(spec, params, init_state(spec, params),
                            grads, coef, lrs, mode=mode, backend=backend)
        # second call exercises state carry (and jit-cache reuse)
        p, s = apply_update(spec, p, s, grads, coef, lrs, mode=mode,
                            backend=backend)
        outs[backend] = (p, s)
    ref_p, ref_s = outs["reference"]
    for backend in ("jit", "pallas"):
        p, s = outs[backend]
        for k in params:
            np.testing.assert_allclose(np.asarray(p[k]),
                                       np.asarray(ref_p[k]), atol=1e-5,
                                       err_msg=f"{backend}:{k}")
        for sk, sv in ref_s.items():
            got = jax.tree.leaves(s[sk])
            want = jax.tree.leaves(sv)
            for a, b in zip(got, want):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg=f"{backend}:{sk}")


def test_adamw_pallas_falls_back_to_jit():
    """adamw has no kernel path; the pallas backend must transparently use
    the pytree path instead of crashing."""
    spec = UpdateSpec(optimizer="adamw")
    assert not spec.kernel_supported
    params = _mixed_tree(jax.random.PRNGKey(0))
    grads = _grads(jax.random.PRNGKey(1), params, 2)
    coef = jnp.asarray([0.5, 0.5])
    lrs = jnp.asarray([0.1, 0.1])
    p1, _ = apply_update(spec, params, init_state(spec, params), grads,
                         coef, lrs, backend="pallas")
    p2, _ = apply_update(spec, params, init_state(spec, params), grads,
                         coef, lrs, backend="jit")
    np.testing.assert_allclose(np.asarray(p1["p0"]), np.asarray(p2["p0"]),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# dtype round-trip: bf16 params, fp32 accumulators
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("optimizer", ["momentum", "adagrad"])
@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_bf16_params_fp32_accumulators(optimizer, backend):
    spec = UpdateSpec(optimizer=optimizer)
    params = _mixed_tree(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    state = init_state(spec, params)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(state))
    assert "master" in state
    grads = _grads(jax.random.PRNGKey(3), params, 3)
    coef = jnp.asarray([0.5, 0.3, 0.2])
    lrs = jnp.full((3,), 0.1)
    before = optim.backends.pallas_dispatches
    p, s = apply_update(spec, params, state, grads, coef, lrs,
                        backend=backend)
    p, s = apply_update(spec, p, s, grads, coef, lrs, backend=backend)
    # the pallas case runs the fused kernel on the master's flat buffer
    assert optim.backends.pallas_dispatches == before + (
        2 if backend == "pallas" else 0)
    # dtypes preserved through the flat-buffer round trip
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(p))
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(s))
    # values track the reference within bf16 resolution (state: fp32-tight
    # modulo the bf16-rounded params feeding event 2)
    rp, rs = apply_update(spec, params, init_state(spec, params), grads,
                          coef, lrs, backend="reference")
    rp, rs = apply_update(spec, rp, rs, grads, coef, lrs,
                          backend="reference")
    np.testing.assert_allclose(
        np.asarray(p["p0"], np.float32), np.asarray(rp["p0"], np.float32),
        atol=3e-2)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(s)[0]), np.asarray(jax.tree.leaves(rs)[0]),
        atol=1e-4)


def test_flat_buffer_padding_odd_sizes():
    """Leaf sizes chosen so the concatenated buffer needs lane + row-block
    padding; the pallas path must still bit-match the reference."""
    spec = UpdateSpec(optimizer="momentum")
    sizes = ((7,), (13, 5), (1,), (3, 3, 3), (127,))
    params = _mixed_tree(jax.random.PRNGKey(4), sizes=sizes)
    grads = _grads(jax.random.PRNGKey(5), params, 4)
    coef = jnp.asarray([0.4, 0.3, 0.2, 0.1])
    lrs = jnp.full((4,), 0.05)
    for mode in ("combine", "sequential"):
        rp, rs = apply_update(spec, params, init_state(spec, params), grads,
                              coef, lrs, mode=mode, backend="reference")
        pp, ps = apply_update(spec, params, init_state(spec, params), grads,
                              coef, lrs, mode=mode, backend="pallas")
        for k in params:
            np.testing.assert_allclose(np.asarray(pp[k]), np.asarray(rp[k]),
                                       atol=1e-6, err_msg=f"{mode}:{k}")
            np.testing.assert_allclose(
                np.asarray(ps["velocity"][k]), np.asarray(rs["velocity"][k]),
                atol=1e-6)


def test_sequential_fold_matches_bruteforce_affine():
    """sequential_fold's full affine form (θ coefficients + v0 carry +
    velocity decay/gain) vs a brute-force momentum unroll."""
    rng = np.random.default_rng(0)
    for c, m in [(1, 0.9), (4, 0.9), (6, 0.5), (3, 0.0)]:
        lrs = rng.uniform(0.01, 0.2, size=c)
        fold = optim.sequential_fold(lrs, m)
        g = rng.normal(size=(c, 5))
        v0 = rng.normal(size=5)
        theta, v = np.zeros(5), v0.copy()
        for j in range(c):
            v = m * v + g[j]
            theta -= lrs[j] * v
        np.testing.assert_allclose(
            theta, -(fold.theta_coef @ g) - fold.v0_coef * v0, atol=1e-12)
        # velocity after the round: v' = m^c·v0 + Σ m^{c−1−i} g_i
        want_v = fold.v_decay * v0 + sum(
            m ** (c - 1 - i) * g[i] for i in range(c))
        np.testing.assert_allclose(v, want_v, atol=1e-12)
        # v_gain is the equal-gradients collapse of the second term
        np.testing.assert_allclose(
            fold.v_gain, sum(m ** (c - 1 - i) for i in range(c)), atol=1e-12)


# ---------------------------------------------------------------------------
# regression: per-gradient LRs + momentum (seed bug: bypassed the optimizer)
# ---------------------------------------------------------------------------
def test_ps_per_gradient_momentum_matches_sequential_events_oracle():
    """footnote 3 with momentum: the PS's fused update must equal applying
    the c gradients one-by-one (v ← m·v + G_i/c ; θ ← θ − α_i·v) with each
    gradient's own modulated LR, in arrival order."""
    base_lr, m, c = 0.2, 0.9, 3
    run = RunConfig(protocol="softsync", n_softsync=2, n_learners=6,
                    base_lr=base_lr, lr_policy="per_gradient",
                    optimizer="momentum", momentum=m)
    policy = make_lr_policy(run)
    params = {"w": jnp.ones((5, 4)), "b": jnp.zeros((7,))}
    ps = ParameterServerState(params, c=c, optimizer="momentum", momentum=m)
    rng = np.random.default_rng(0)
    pushes = []   # (grad, grad_timestamp), staleness varies across updates
    ts_pattern = [[0, 0, 0], [0, 1, 0], [0, 2, 1]]
    for upd, stamps in enumerate(ts_pattern):
        for t in stamps:
            g = jax.tree.map(
                lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
                params)
            pushes.append((g, t))
    for g, t in pushes:
        ps.push_gradient(g, t, policy)
    assert ps.timestamp == len(ts_pattern)

    # oracle: per-event momentum with α_i = α₀ / max(1, σ_i)
    theta = jax.tree.map(lambda p: np.asarray(p, np.float64), params)
    vel = jax.tree.map(lambda p: np.zeros(p.shape), params)
    for upd in range(len(ts_pattern)):
        batch = pushes[upd * c:(upd + 1) * c]
        alphas = policy(upd, [t for _, t in batch])
        for (g, _), a in zip(batch, alphas):
            vel = jax.tree.map(
                lambda v, gg: m * v + np.asarray(gg, np.float64) / c, vel, g)
            theta = jax.tree.map(lambda p, v: p - a * v, theta, vel)
    assert len(set(np.round(
        policy(2, [t for _, t in pushes[6:9]]), 6))) > 1   # LRs really vary
    for k in params:
        np.testing.assert_allclose(np.asarray(ps.params[k]), theta[k],
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(ps.velocity[k]), vel[k],
                                   atol=1e-5)


@pytest.mark.parametrize("optimizer", ["momentum", "adagrad"])
def test_ps_backends_agree(optimizer):
    """The same arrival sequence produces the same weights under every
    optim backend (per-gradient staleness LRs included)."""
    run = RunConfig(protocol="softsync", n_softsync=2, n_learners=4,
                    base_lr=0.1, lr_policy="per_gradient",
                    optimizer=optimizer)
    policy = make_lr_policy(run)
    params = {"w": jnp.ones((9, 3))}
    rng = np.random.default_rng(1)
    grads = [jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params)
        for _ in range(6)]
    results = []
    for backend in ("reference", "jit", "pallas"):
        ps = ParameterServerState(params, c=2, optimizer=optimizer,
                                  backend=backend)
        for i, g in enumerate(grads):
            ps.push_gradient(g, max(0, i // 2 - 1), policy)
        results.append(np.asarray(ps.params["w"]))
    for r in results[1:]:
        np.testing.assert_allclose(r, results[0], atol=1e-5)


# ---------------------------------------------------------------------------
# regression: fused softsync engine velocity carry (seed bug: dropped v0_coef)
# ---------------------------------------------------------------------------
def test_fused_equals_sequential_momentum_multiround():
    """With identical per-group data the group-mean gradients coincide, so
    the fused engine's affine round fold must reproduce the sequential
    engine EXACTLY across rounds.  The seed engine diverged from round 2 on
    (wrong velocity decay, dropped θ carry)."""
    n, mu = 4, 8
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (6, 3))
    Xg = jax.random.normal(jax.random.PRNGKey(1), (mu, 6))
    Yg = Xg @ W
    # groups are interleaved (sample s joins group s % n): repeating each
    # sample n times gives every group the same data
    batch = {"x": jnp.repeat(Xg, n, axis=0), "y": jnp.repeat(Yg, n, axis=0)}

    def loss(p, b, sample_weights=None):
        per = jnp.mean((b["x"] @ p - b["y"]) ** 2, axis=-1)
        if sample_weights is not None:
            per = per * sample_weights
        return jnp.mean(per), {"loss": jnp.mean(per)}

    for lrp in ("const", "per_gradient", "staleness_inverse"):
        run = RunConfig(protocol="softsync", n_softsync=n, n_learners=8,
                        minibatch=mu, base_lr=0.05, lr_policy=lrp,
                        optimizer="momentum", momentum=0.9)
        seq = jax.jit(make_train_step(run, loss, engine="sequential"))
        fus = jax.jit(make_train_step(run, loss, engine="fused"))
        p1 = p2 = jnp.zeros((6, 3))
        o1 = init_opt_state(run, p1)
        o2 = init_opt_state(run, p2)
        for r in range(3):
            p1, o1, _ = seq(p1, o1, batch)
            p2, o2, _ = fus(p2, o2, batch)
            np.testing.assert_allclose(
                np.asarray(p1), np.asarray(p2), atol=1e-5,
                err_msg=f"{lrp} round {r}")
        np.testing.assert_allclose(np.asarray(o1["velocity"]),
                                   np.asarray(o2["velocity"]), atol=1e-5,
                                   err_msg=lrp)


# ---------------------------------------------------------------------------
# the simulator's sgd-mode hot path really fires the fused kernel
# ---------------------------------------------------------------------------
def test_simulator_sgd_hot_path_dispatches_pallas():
    before = optim.backends.pallas_dispatches
    run = RunConfig(protocol="softsync", n_softsync=4, n_learners=4,
                    minibatch=4, base_lr=0.1, lr_policy="staleness_inverse",
                    optimizer="momentum", seed=0)

    def loss(p, b):
        x, y = b
        return jnp.mean((x @ p - y) ** 2)
    grad_fn = jax.jit(jax.grad(loss))
    X = np.asarray(np.random.default_rng(0).normal(size=(64, 6)), np.float32)
    Wt = np.asarray(np.random.default_rng(1).normal(size=(6, 2)), np.float32)

    def batch_fn(l, i):
        idx = np.random.default_rng(l * 997 + i).integers(0, 64, size=4)
        return jnp.asarray(X[idx]), jnp.asarray(X[idx] @ Wt)

    res = simulate(run, steps=10, grad_fn=grad_fn,
                   init_params=jnp.zeros((6, 2)), batch_fn=batch_fn)
    assert res.updates == 10
    assert optim.backends.pallas_dispatches >= before + 10


@pytest.mark.parametrize("apply", ["single", "pallas"])
def test_fp32_master_keeps_sub_ulp_updates(apply):
    """bf16 weights with a float32 master: an update under half a bf16 step
    (at 1.0 the step is 2^-7) accumulates over events in the master, and
    the stored weights are the master rounded; without the master each
    rounding would lose it and the weights would never move.  The same
    through ``apply_single`` and through the fused Pallas kernel."""
    from repro.optim import apply_single
    spec = UpdateSpec(optimizer="sgd")
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = init_state(spec, params)
    assert state["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((4,), -1.0, jnp.float32)}
    lr = 2.0 ** -10                      # an eighth of a bf16 step
    if apply == "single":
        step = jax.jit(lambda p, s: apply_single(spec, p, s, g, lr))
    else:
        def step(p, s):
            return apply_update(spec, p, s, [g], [1.0], [lr],
                                backend="pallas")
    p, s = params, state
    for _ in range(12):
        p, s = step(p, s)
    np.testing.assert_array_equal(s["master"]["w"], 1.0 + 12 * lr)
    np.testing.assert_array_equal(
        p["w"], jnp.asarray(1.0 + 12 * lr, jnp.float32).astype(jnp.bfloat16))
    assert float(p["w"][0]) > 1.0
    # the same events on the stored weights alone: every one is rounded away
    bare, _ = params, None
    for _ in range(12):
        bare, _ = apply_single(spec, bare, {}, g, lr)
    np.testing.assert_array_equal(bare["w"], params["w"])
