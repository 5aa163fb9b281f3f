"""Replay megakernel + compressed weight ring (DESIGN.md §12).

Equivalence contract, pinned here:

* **Event level** (jit vs jit): the Pallas megakernel ``ring_apply`` /
  ``ring_apply_whatif`` (interpret mode on CPU) is BITWISE its fused jnp
  twin — and with an fp32 ring the twin is bitwise the flat
  ``apply_event_flat`` reference.
* **Engine level**: the fused scan body equals the stock pytree body
  bitwise on the trivial topology (the casts are no-ops); the Pallas body
  equals the fused body bitwise for stateless/adagrad cells and to fp32
  accumulation tolerance on momentum cells (XLA forms FMAs differently
  per compiled program at some ring depths — ~1 ulp/event).
* **Sharded**: fused ≡ pallas bitwise; vs the stock sharded body the
  combine einsum is phrased on (S, c, Dp) operands, which XLA lowers with
  different rounding, so agreement is fp32-tolerance, not bitwise.
* **bf16 ring**: the fp32 master chain (bf16 row + error-feedback
  residue) reconstructs the exact fp32 weights per event; end-to-end
  drift vs an fp32 ring stays within the documented tolerance because
  only *gradient evaluation points* are quantized.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RunConfig
from repro import telemetry
from repro.core import engine, replay, schedule
from repro.core.engine import _materialize_batches, replay_batch
from repro.core.trace import schedule_cached
from repro.kernels import replay_ring
from repro.membership import MembershipTimeline
from repro.optim import UpdateSpec
from repro.optim.backends import (apply_event_flat, apply_event_ring,
                                  apply_event_ring_whatif)


def _bw(a, b):
    """Bitwise array equality (NaN-free data)."""
    assert np.array_equal(np.asarray(a), np.asarray(b)), (
        f"max |diff| = {np.max(np.abs(np.asarray(a) - np.asarray(b)))}")


# ---------------------------------------------------------------------------
# shared tiny problem (linear regression, deterministic batches)
# ---------------------------------------------------------------------------
KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (6, 3))
X = jax.random.normal(jax.random.PRNGKey(1), (64, 6))
Y = X @ W_TRUE


def _loss(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] - y) ** 2)


GRAD_FN = jax.jit(jax.grad(_loss))
INIT = {"w": jnp.zeros((6, 3))}


def _batch_fn(l, i):
    rng = np.random.default_rng(l * 9973 + i)
    idx = rng.integers(0, 64, size=8)
    return X[idx], Y[idx]


def _run(cfg, steps=24, **kw):
    trace = schedule(cfg, steps)
    return replay(trace, cfg, grad_fn=GRAD_FN, init_params=INIT,
                  batch_fn=_batch_fn, **kw)


# ---------------------------------------------------------------------------
# event level: megakernel ≡ fused twin ≡ flat reference, bitwise
# ---------------------------------------------------------------------------
def _event_operands(optimizer, ring_dtype, seed=3, K=5, c=4, width=700):
    spec = UpdateSpec(optimizer=optimizer)
    Dp = replay_ring.padded_width(width)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    ring32 = jax.random.normal(ks[0], (K, Dp), jnp.float32)
    s = None if optimizer == "sgd" else jnp.zeros((Dp,))
    g = jax.random.normal(ks[1], (c, Dp)) * 0.1
    coef = jnp.abs(jax.random.normal(ks[2], (c,))) + 0.1
    lrs = jnp.full((c,), 0.05)
    if ring_dtype == "bf16":
        ring = ring32.astype(jnp.bfloat16)
        res = ring32[2] - ring[2].astype(jnp.float32)
    else:
        ring, res = ring32, None
    return spec, ring, ring32, s, res, g, coef, lrs


@pytest.mark.parametrize("ring_dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("mode", ["combine", "sequential"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad"])
def test_event_megakernel_bitwise_vs_twin(optimizer, mode, ring_dtype):
    spec, ring, _, s, res, g, coef, lrs = _event_operands(
        optimizer, ring_dtype)
    idx = jnp.array([2, 3], jnp.int32)

    mega = jax.jit(functools.partial(
        replay_ring.ring_apply, spec=spec, mode=mode, interpret=True))
    twin = jax.jit(functools.partial(
        apply_event_ring, spec, prev=2, slot=3, mode=mode))
    rm, sm, resm = mega(ring, s, res, g, coef, lrs, idx)
    rt, st, rest = twin(ring=ring, s=s, res=res, g=g, coef=coef, lrs=lrs)
    _bw(rm, rt)
    if s is not None:
        _bw(sm, st)
    if res is not None:
        _bw(resm, rest)


@pytest.mark.parametrize("mode", ["combine"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum"])
def test_event_fp32_megakernel_bitwise_vs_flat_reference(optimizer, mode):
    """With an fp32 ring the megakernel event IS the stock chain: gather
    row, ``apply_event_flat``, ``.at[slot].set`` — bitwise in combine mode
    (the engine's mode everywhere).  Sequential mode re-associates the
    per-slot FMA chain differently across the two program phrasings, so
    its bitwise pin lives in the twin test above instead."""
    spec, ring, _, s, res, g, coef, lrs = _event_operands(optimizer, "fp32")
    idx = jnp.array([2, 3], jnp.int32)

    @jax.jit
    def stock(ring, s):
        w, s2 = apply_event_flat(spec, ring[2], s, g, coef, lrs, mode)
        return ring.at[3].set(w), s2

    @jax.jit
    def mega(ring, s):
        r2, s2, _ = replay_ring.ring_apply(ring, s, None, g, coef, lrs,
                                           idx, spec=spec, mode=mode,
                                           interpret=True)
        return r2, s2

    rs, ss = stock(ring, s)
    rm, sm = mega(ring, s)
    _bw(rm, rs)
    if s is not None:
        _bw(sm, ss)


@pytest.mark.parametrize("ring_dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum"])
def test_event_whatif_megakernel_bitwise_vs_twin(optimizer, ring_dtype):
    spec, ring, ring32, s, res, g, coef, lrs = _event_operands(
        optimizer, ring_dtype, c=3)
    Dp = ring.shape[1]
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    a = jnp.abs(jax.random.normal(ks[0], (Dp,))) + 0.5
    wstar = jax.random.normal(ks[1], (Dp,))
    ts = jnp.array([1, 2, 4], jnp.int32)
    idx = jnp.concatenate([jnp.array([2, 3], jnp.int32), ts])

    mega = jax.jit(functools.partial(
        replay_ring.ring_apply_whatif, spec=spec, interpret=True))
    twin = jax.jit(functools.partial(
        apply_event_ring_whatif, spec, ts=ts, prev=2, slot=3))
    rm, sm, resm = mega(ring, s, res, a, wstar, coef, lrs, idx)
    rt, st, rest = twin(ring=ring, s=s, res=res, a=a, wstar=wstar,
                        coef=coef, lrs=lrs)
    _bw(rm, rt)
    if s is not None:
        _bw(sm, st)
    if res is not None:
        _bw(resm, rest)


def test_event_whatif_megakernel_bitwise_across_row_blocks():
    """Three row blocks, pulled rows repeated and the slot row among them:
    the kernel's copies of the ring rows follow the grid."""
    K, c, Dp = 4, 5, 3 * replay_ring.row_block_for(1 << 20) * 128
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    ring32 = jax.random.normal(ks[0], (K, Dp))
    ring = ring32.astype(jnp.bfloat16)
    res = ring32[2] - ring[2].astype(jnp.float32)
    s = 0.1 * jax.random.normal(ks[1], (Dp,))
    a = jnp.abs(jax.random.normal(ks[2], (Dp,))) + 0.5
    wstar = jax.random.normal(ks[3], (Dp,))
    coef = jnp.linspace(0.1, 0.5, c)
    lrs = jnp.full((c,), 0.05)
    ts = jnp.array([3, 3, 1, 2, 2], jnp.int32)
    spec = UpdateSpec(optimizer="momentum")
    mega = jax.jit(functools.partial(
        replay_ring.ring_apply_whatif, spec=spec, interpret=True))
    twin = jax.jit(functools.partial(
        apply_event_ring_whatif, spec, ts=ts, prev=2, slot=3))
    got = mega(ring, s, res, a, wstar, coef, lrs,
               jnp.concatenate([jnp.array([2, 3], jnp.int32), ts]))
    want = twin(ring=ring, s=s, res=res, a=a, wstar=wstar, coef=coef,
                lrs=lrs)
    for g, w in zip(got, want):
        _bw(g, w)


def test_event_bf16_master_chain_exact():
    """bf16 row + error-feedback residue reconstructs the EXACT fp32
    weights the fp32-ring event produced — compression never touches the
    master chain, only where gradients get evaluated."""
    spec, ring_bf, ring32, s, res, g, coef, lrs = _event_operands(
        "momentum", "bf16")
    idx = jnp.array([2, 3], jnp.int32)
    r32, s32, _ = jax.jit(functools.partial(
        replay_ring.ring_apply, spec=spec, interpret=True))(
            ring32, s, None, g, coef, lrs, idx)
    rbf, sbf, resb = jax.jit(functools.partial(
        replay_ring.ring_apply, spec=spec, interpret=True))(
            ring_bf, s, res, g, coef, lrs, idx)
    master = rbf[3].astype(jnp.float32) + resb
    _bw(master, r32[3])


# ---------------------------------------------------------------------------
# error-feedback residue: |res| is bounded by bf16 rounding of the master
# ---------------------------------------------------------------------------
def _residue_bound_holds(seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (512,)) * (
        10.0 ** (seed % 7 - 3))
    q = w.astype(jnp.bfloat16)
    res = np.asarray(w - q.astype(jnp.float32))
    # round-to-nearest bf16: |w - q(w)| <= 2^-8 ulp-scale |w| (+ denormal
    # floor); the EF residue is exactly this quantization error
    bound = np.abs(np.asarray(w)) * 2.0 ** -8 + 1e-38
    return bool(np.all(np.abs(res) <= bound))


@pytest.mark.parametrize("seed", range(12))
def test_ef_residue_bounded(seed):
    assert _residue_bound_holds(seed)


def test_ef_residue_bounded_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=30, derandomize=True)
    @given(st.integers(0, 2 ** 20))
    def prop(seed):
        assert _residue_bound_holds(seed)
    prop()


# ---------------------------------------------------------------------------
# dispatch branch: the CPU fallback and the counters
# ---------------------------------------------------------------------------
def test_dispatch_counters_and_interpret_default():
    spec, ring, _, s, res, g, coef, lrs = _event_operands("sgd", "fp32")
    before = replay_ring.pallas_dispatches
    replay_ring.ring_apply(ring, s, res, g, coef, lrs,
                           jnp.array([2, 3], jnp.int32), spec=spec)
    assert replay_ring.pallas_dispatches == before + 1
    # off-accelerator the kernel auto-selects interpret mode (CPU CI)
    expect = jax.default_backend() != "tpu"
    assert replay_ring.default_interpret() is expect
    assert replay_ring.last_interpret is expect


def test_engine_pallas_path_dispatches_kernel():
    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=4,
                    minibatch=8, base_lr=0.05, optimizer="sgd", seed=3,
                    ring_impl="pallas")
    before = replay_ring.pallas_dispatches
    _run(cfg, steps=6)
    assert replay_ring.pallas_dispatches > before


# ---------------------------------------------------------------------------
# engine level: fused ≡ stock bitwise on the trivial topology
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("protocol,n", [("async", 1), ("softsync", 2),
                                        ("hardsync", 1)])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad"])
def test_engine_fused_bitwise_vs_stock(protocol, n, optimizer):
    kw = dict(protocol=protocol, n_softsync=n, n_learners=8, minibatch=8,
              base_lr=0.05, lr_policy="staleness_inverse",
              optimizer=optimizer, seed=11)
    fused = _run(RunConfig(ring_impl="fused", **kw))
    stock = _run(RunConfig(ring_impl="stock", **kw))
    _bw(fused.params["w"], stock.params["w"])


def test_engine_fused_bitwise_vs_stock_elastic_mask():
    """Masked (elastic) replay: cancelled slots zero out identically in
    both scan bodies."""
    churn = MembershipTimeline(((1.0, 3, "crash"), (2.5, 3, "join"),
                                (4.0, 6, "leave")))
    kw = dict(protocol="softsync", n_softsync=2, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer="momentum", seed=13, membership=churn)
    fused = _run(RunConfig(ring_impl="fused", **kw))
    stock = _run(RunConfig(ring_impl="stock", **kw))
    _bw(fused.params["w"], stock.params["w"])


def test_engine_fused_bitwise_vs_stock_grouped():
    kw = dict(protocol="softsync", n_softsync=2, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer="momentum", seed=5, groups=4)
    fused = _run(RunConfig(ring_impl="fused", **kw))
    stock = _run(RunConfig(ring_impl="stock", **kw))
    _bw(fused.params["w"], stock.params["w"])


# ---------------------------------------------------------------------------
# engine level: pallas vs fused
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_engine_pallas_bitwise_vs_fused(optimizer):
    kw = dict(protocol="softsync", n_softsync=2, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer=optimizer, seed=7)
    pallas = _run(RunConfig(ring_impl="pallas", **kw))
    fused = _run(RunConfig(ring_impl="fused", **kw))
    _bw(pallas.params["w"], fused.params["w"])


def test_engine_pallas_vs_fused_momentum_tolerance():
    """Momentum cells drift ~1 ulp/event between the two compiled
    programs (XLA forms the v-update FMA differently at some ring
    depths); the event-level test above is bitwise, so pin the
    engine-level agreement at fp32 accumulation tolerance."""
    kw = dict(protocol="softsync", n_softsync=4, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer="momentum", seed=7)
    pallas = _run(RunConfig(ring_impl="pallas", **kw))
    fused = _run(RunConfig(ring_impl="fused", **kw))
    np.testing.assert_allclose(np.asarray(pallas.params["w"]),
                               np.asarray(fused.params["w"]),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# sharded topology
# ---------------------------------------------------------------------------
def test_engine_sharded_fused_bitwise_vs_pallas_and_tol_vs_stock():
    kw = dict(protocol="softsync", n_softsync=2, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer="momentum", seed=19, shards=2)
    fused = _run(RunConfig(ring_impl="fused", **kw))
    pallas = _run(RunConfig(ring_impl="pallas", **kw))
    stock = _run(RunConfig(ring_impl="stock", **kw))
    _bw(fused.params["w"], pallas.params["w"])
    # stock shard body phrases the combine einsum on (S, c, Dp) operands —
    # XLA lowers that with different rounding (~1 ulp/event), so the
    # cross-body contract is fp32 tolerance, not bitwise (DESIGN.md §12)
    np.testing.assert_allclose(np.asarray(fused.params["w"]),
                               np.asarray(stock.params["w"]),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# bf16 compressed ring, engine level
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ring_impl", ["fused", "pallas"])
def test_engine_bf16_ring_tolerance(ring_impl):
    """End-to-end bf16-ring drift vs the fp32 ring: gradients get
    evaluated at quantized snapshots, nothing else changes — documented
    tolerance ~1e-3 on O(1) weights over 24 steps."""
    kw = dict(protocol="softsync", n_softsync=2, n_learners=8, minibatch=8,
              base_lr=0.05, optimizer="momentum", seed=23)
    bf = _run(RunConfig(ring_impl=ring_impl, ring_dtype="bf16", **kw))
    fp = _run(RunConfig(ring_impl=ring_impl, ring_dtype="fp32", **kw))
    np.testing.assert_allclose(np.asarray(bf.params["w"]),
                               np.asarray(fp.params["w"]),
                               rtol=0, atol=5e-3)
    drift = np.max(np.abs(np.asarray(bf.params["w"]) -
                          np.asarray(fp.params["w"])))
    assert drift > 0.0          # the ring really was quantized


# ---------------------------------------------------------------------------
# what-if replay (in-kernel closed-form gradients)
# ---------------------------------------------------------------------------
def _whatif_operands(d=600, seed=0):
    i = jnp.arange(d, dtype=jnp.float32)
    a = 0.5 + (i % 100.0) / 100.0
    wstar = jnp.sin(0.01 * i)
    return a, wstar


def _whatif_run(cfg, steps=24, impl=None, d=600, **kw):
    a, wstar = _whatif_operands(d)
    cfg = cfg if impl is None else cfg.replace(ring_impl=impl)
    trace = schedule(cfg, steps)
    init = {"w": jnp.zeros((a.shape[0],), jnp.float32)}
    if cfg.ring_impl == "stock":
        def grad_fn(p, b):
            return {"w": a * (p["w"] - wstar)}
        return replay(trace, cfg, grad_fn=grad_fn, init_params=init,
                      batch_fn=lambda l, i: np.zeros((1,), np.float32))
    return replay(trace, cfg, init_params=init,
                  flat_grad=("quadratic", lambda pos: (a[pos], wstar[pos])),
                  **kw)


def _whatif_cfg(c=4, ring_dtype="fp32", **kw):
    """softsync over 8 learners: c = 8 / n gradients per update (K = 4 at
    c = 4, K = 15 at c = 1)."""
    return RunConfig(protocol="softsync", n_softsync=8 // c, n_learners=8,
                     minibatch=1, base_lr=0.02, optimizer="momentum",
                     ring_dtype=ring_dtype, seed=29, **kw)


@pytest.mark.parametrize("c", [1, 4])
@pytest.mark.parametrize("ring_dtype", ["fp32", "bf16"])
def test_whatif_pallas_bitwise_vs_fused(ring_dtype, c):
    """The Pallas body, which carries the ring in the kernel's tiles and
    updates it in place, is bitwise the fused twin's flat carry."""
    cfg = _whatif_cfg(c, ring_dtype)
    _bw(_whatif_run(cfg, impl="pallas").params["w"],
        _whatif_run(cfg, impl="fused").params["w"])


def test_whatif_pallas_bitwise_vs_fused_across_segments():
    """Segments hand the tiled carry from one scan dispatch to the next,
    and the eval reads the weights out of it in between; two row blocks
    per ring row."""
    cfg = _whatif_cfg(4, "bf16")

    def run(impl):
        return _whatif_run(cfg, impl=impl, d=40_000, eval_every=8,
                           eval_fn=lambda p: {"w": np.asarray(p["w"])})
    pallas, fused = run("pallas"), run("fused")
    assert len(pallas.history) == len(fused.history) == 3
    for hp, hf in zip(pallas.history, fused.history):
        _bw(hp["w"], hf["w"])
    _bw(pallas.params["w"], fused.params["w"])


@pytest.mark.parametrize("protocol,tiled", [("softsync", True),
                                            ("hardsync", False)])
def test_whatif_tiled_carry_counter(protocol, tiled):
    """A what-if scan built with the kernel's tiles counts once; the
    K = 1 fallback (hardsync: no stale pull) runs the flat twin and does
    not count."""
    cfg = RunConfig(protocol=protocol, n_softsync=1, n_learners=4,
                    minibatch=1, base_lr=0.02, optimizer="momentum",
                    seed=29, ring_impl="pallas")
    assert (schedule(cfg, 8).max_staleness + 1 >= 2) is tiled
    engine._make_scan_fn.cache_clear()          # trace the scan afresh
    before = telemetry.counters().get(engine.TILED_CARRY_BUILDS, 0)
    _whatif_run(cfg, steps=8)
    after = telemetry.counters().get(engine.TILED_CARRY_BUILDS, 0)
    assert after - before == int(tiled)


def test_whatif_matches_staged_stock():
    """The in-kernel closed-form gradients equal the staged twin to fp32
    accumulation tolerance (the streamed fori accumulation orders the
    c-sum differently from the einsum)."""
    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                    minibatch=1, base_lr=0.02, optimizer="momentum",
                    seed=29)
    whatif = _whatif_run(cfg, steps=64, impl="fused")
    stock = _whatif_run(cfg, steps=64, impl="stock")
    np.testing.assert_allclose(np.asarray(whatif.params["w"]),
                               np.asarray(stock.params["w"]),
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# staged batches, batched replay, config plumbing
# ---------------------------------------------------------------------------
def test_replay_batches_equals_batch_fn():
    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                    minibatch=8, base_lr=0.05, optimizer="momentum",
                    seed=31)
    trace = schedule(cfg, 16)
    staged = _materialize_batches(trace, _batch_fn)
    via_fn = replay(trace, cfg, grad_fn=GRAD_FN, init_params=INIT,
                    batch_fn=_batch_fn)
    via_staged = replay(trace, cfg, grad_fn=GRAD_FN, init_params=INIT,
                        batches=staged)
    _bw(via_fn.params["w"], via_staged.params["w"])


def test_replay_batch_fused_matches_singles():
    cfgs = [RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                      minibatch=8, base_lr=0.05, optimizer="momentum",
                      seed=s, ring_impl="fused") for s in (41, 43)]
    traces = [schedule(c, 16) for c in cfgs]
    batch = replay_batch(traces, cfgs, grad_fn=GRAD_FN, init_params=INIT,
                         batch_fns=[_batch_fn, _batch_fn])
    singles = [replay(t, c, grad_fn=GRAD_FN, init_params=INIT,
                      batch_fn=_batch_fn) for t, c in zip(traces, cfgs)]
    for b, s in zip(batch, singles):
        np.testing.assert_allclose(np.asarray(b.params["w"]),
                                   np.asarray(s.params["w"]),
                                   rtol=0, atol=1e-6)


def test_replay_batch_rejects_mixed_ring_config():
    cfgs = [RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                      minibatch=8, seed=41, ring_impl="fused"),
            RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                      minibatch=8, seed=43, ring_impl="stock")]
    traces = [schedule(c, 8) for c in cfgs]
    with pytest.raises(ValueError, match="ring"):
        replay_batch(traces, cfgs, grad_fn=GRAD_FN, init_params=INIT,
                     batch_fns=[_batch_fn, _batch_fn])


@pytest.mark.parametrize("bad", [dict(ring_dtype="fp16"),
                                 dict(ring_impl="xla"),
                                 dict(ring_dtype="bf16", ring_impl="stock"),
                                 dict(ring_dtype="bf16", optimizer="adamw")])
def test_ring_config_validation(bad):
    with pytest.raises(ValueError):
        RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                  minibatch=8, **bad)


def test_schedule_cached_identity_and_shape_key():
    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                    minibatch=8, seed=47)
    t1 = schedule_cached(cfg, 16)
    t2 = schedule_cached(cfg, 16)
    assert t1 is t2                       # one trace object per (run, steps)
    assert schedule_cached(cfg, 17) is not t1
    assert schedule_cached(cfg.replace(seed=48), 16) is not t1
    # the cache must agree with a fresh schedule
    fresh = schedule(cfg, 16)
    np.testing.assert_array_equal(t1.pulled_ts, fresh.pulled_ts)
    np.testing.assert_array_equal(t1.learner, fresh.learner)
