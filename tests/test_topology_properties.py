"""Property-based tests (hypothesis) for the topology subsystem: the
shard-partition invariance of the fused event apply over ANY valid shard
boundary (the kernel update is elementwise, so sharding the buffer is pure
layout), and group/pusher accounting invariants of the schedule pass."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro import optim
from repro.config import RunConfig
from repro.core import Topology, schedule
from repro.optim import flatten

SET = dict(deadline=None, max_examples=20, derandomize=True)


def _boundaries(draw, dim):
    """Random ordered cut points → list of [lo, hi) covering [0, dim)."""
    n_cuts = draw(st.integers(0, min(6, dim - 1)))
    cuts = sorted(draw(st.sets(st.integers(1, dim - 1),
                               min_size=n_cuts, max_size=n_cuts)))
    edges = [0] + cuts + [dim]
    return list(zip(edges[:-1], edges[1:]))


@settings(**SET)
@given(st.data(),
       st.sampled_from(["sgd", "momentum", "adagrad"]),
       st.sampled_from(["combine", "sequential"]))
def test_any_shard_boundary_partitions_apply_event(data, optimizer, mode):
    """apply_event_flat over ANY contiguous partition of the flat buffer
    equals the unsharded update exactly (per-element ops are identical)."""
    dim = data.draw(st.integers(2, 40))
    c = data.draw(st.integers(1, 4))
    bounds = _boundaries(data.draw, dim)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    spec = optim.UpdateSpec(optimizer=optimizer,
                            momentum=data.draw(st.floats(0.0, 0.99)))
    w = jnp.asarray(rng.normal(size=dim), jnp.float32)
    s = (None if optimizer == "sgd"
         else jnp.asarray(rng.random(dim), jnp.float32))
    g = jnp.asarray(rng.normal(size=(c, dim)), jnp.float32)
    coef = jnp.full((c,), 1.0 / c, jnp.float32)
    lrs = jnp.asarray(rng.uniform(0.01, 0.5, size=c), jnp.float32)
    w_full, s_full = optim.apply_event_flat(spec, w, s, g, coef, lrs, mode)
    parts = [optim.apply_event_flat(
                 spec, w[lo:hi], None if s is None else s[lo:hi],
                 g[:, lo:hi], coef, lrs, mode)
             for lo, hi in bounds]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p[0]) for p in parts]),
        np.asarray(w_full))
    if s is not None:
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p[1]) for p in parts]),
            np.asarray(s_full))


@settings(**SET)
@given(st.data(), st.sampled_from(["sgd", "momentum", "adagrad"]))
def test_equal_width_shard_pack_roundtrip_and_apply(data, optimizer):
    """shard_pack inverts by flattening its rows and dropping the pad, and
    the flat event applied to each shard's slice reproduces the flat apply
    on the equal-width layout for any (D, S)."""
    dim = data.draw(st.integers(1, 33))
    shards = data.draw(st.integers(1, 8))
    c = data.draw(st.integers(1, 3))
    dp = Topology(shards=shards).padded_width(dim)
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    spec = optim.UpdateSpec(optimizer=optimizer)
    w = jnp.asarray(rng.normal(size=dim), jnp.float32)
    s = (None if optimizer == "sgd"
         else jnp.asarray(rng.random(dim), jnp.float32))
    g = jnp.asarray(rng.normal(size=(c, dim)), jnp.float32)
    coef = jnp.full((c,), 1.0 / c, jnp.float32)
    lrs = jnp.asarray(rng.uniform(0.01, 0.5, size=c), jnp.float32)
    w_rows = flatten.shard_pack(w, shards, dp)
    np.testing.assert_array_equal(np.asarray(w_rows.reshape(-1)[:dim]),
                                  np.asarray(w))
    s_rows = None if s is None else flatten.shard_pack(s, shards, dp)
    g_rows = flatten.shard_pack(g, shards, dp)               # (c, S, Dp)
    ws = jnp.stack([optim.apply_event_flat(
        spec, w_rows[j], None if s_rows is None else s_rows[j],
        g_rows[:, j], coef, lrs, "combine")[0] for j in range(shards)])
    w_full, _ = optim.apply_event_flat(spec, w, s, g, coef, lrs, "combine")
    np.testing.assert_allclose(
        np.asarray(ws.reshape(-1)[:dim]), np.asarray(w_full),
        atol=1e-6, rtol=1e-6)


@settings(deadline=None, max_examples=12, derandomize=True)
@given(st.integers(2, 24), st.data())
def test_grouped_schedule_invariants(lam, data):
    """For any G | λ: P = G pushers, σ ≥ 0, minibatch accounting counts
    every member gradient, and member blocks tile [0, λ)."""
    divisors = [g for g in range(1, lam + 1) if lam % g == 0]
    groups = data.draw(st.sampled_from(divisors))
    n = data.draw(st.integers(1, max(1, groups)))
    run = RunConfig(protocol="softsync", n_softsync=n, n_learners=lam,
                    groups=groups, minibatch=8, seed=lam * 31 + groups)
    tr = schedule(run, 60)
    gs = lam // groups
    assert tr.group_size == gs
    assert tr.c == max(1, groups // n)
    assert tr.minibatches == 60 * tr.c * gs
    assert (tr.staleness >= 0).all()
    assert int(tr.learner.max()) < groups
    mem = tr.member_learners()
    if gs == 1:
        assert mem is None
    else:
        assert mem.shape == (60, tr.c, gs)
        assert set(np.unique(mem)) <= set(range(lam))
