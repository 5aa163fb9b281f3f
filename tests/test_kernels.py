"""Pallas kernel validation: interpret-mode allclose vs pure-jnp oracles,
with hypothesis shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels import ps_update as psu
from repro.kernels import ssm_scan as ssk
from repro.kernels import wkv6 as wk

SET = dict(deadline=None, max_examples=8, derandomize=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
def test_flash_attention_matches_ref(causal, window):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 192, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 192, 4, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 192, 4, 32), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=causal, window=window,
                             blk_q=64, blk_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, want, atol=2e-5)


@settings(**SET)
@given(st.sampled_from([32, 48, 96]), st.sampled_from([1, 2]),
       st.sampled_from([(4, 4), (8, 2), (8, 8)]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_flash_attention_sweep(seq, batch, heads, dtype):
    H, KV = heads
    key = jax.random.PRNGKey(seq * 7 + batch)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (batch, seq, H, 16), dtype)
    k = jax.random.normal(ks[1], (batch, seq, KV, 16), dtype)
    v = jax.random.normal(ks[2], (batch, seq, KV, 16), dtype)
    out = fa.flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                             interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=atol)


def test_flash_attention_unaligned_seq():
    """Sequence not a multiple of the block size (padding path)."""
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 100, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 100, 4, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 100, 4, 16), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                             interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5)


# ---------------------------------------------------------------------------
# ps_update
# ---------------------------------------------------------------------------
@settings(**SET)
@given(st.sampled_from([1000, 4096, 5000]), st.integers(1, 6),
       st.sampled_from([0.0, 0.9]))
def test_ps_update_sweep(D, c, momentum):
    key = jax.random.PRNGKey(D + c)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (D,), jnp.float32)
    v = jax.random.normal(ks[1], (D,), jnp.float32)
    g = jax.random.normal(ks[2], (c, D), jnp.float32)
    coef = jnp.abs(jax.random.normal(ks[3], (c,))) + 0.1
    w2, v2 = psu.ps_update_flat(w, v, g, coef, momentum=momentum, lr=0.05,
                                row_block=8, interpret=True)
    w2r, v2r = ref.ps_update_ref(w, v, g, coef, momentum=momentum, lr=0.05)
    np.testing.assert_allclose(w2, w2r, atol=1e-5)
    np.testing.assert_allclose(v2, v2r, atol=1e-5)


def test_ps_update_tree_matches_sequential_events():
    """The fused kernel reproduces the PS's staleness-weighted sumGradients
    (footnote 3) on a parameter pytree."""
    params = {"a": jnp.ones((300,)), "b": jnp.zeros((17, 8))}
    vel = jax.tree.map(jnp.zeros_like, params)
    grads = [jax.tree.map(lambda p: jnp.full_like(p, float(i + 1)), params)
             for i in range(3)]
    coef = jnp.array([1.0, 0.5, 0.25])
    p2, v2 = psu.ps_update_tree(params, vel, grads, coef, momentum=0.9,
                                lr=0.1, interpret=True)
    want_g = 1 * 1.0 + 2 * 0.5 + 3 * 0.25
    np.testing.assert_allclose(v2["a"], np.full(300, want_g), atol=1e-5)
    np.testing.assert_allclose(p2["a"], np.full(300, 1 - 0.1 * want_g),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------
@settings(**SET)
@given(st.sampled_from([32, 96, 128]), st.sampled_from([8, 16]),
       st.sampled_from([16, 32]))
def test_ssm_scan_sweep(S, N, chunk):
    key = jax.random.PRNGKey(S + N)
    ks = jax.random.split(key, 4)
    Bt, H, P = 2, 3, 8
    x = jax.random.normal(ks[0], (Bt, S, H, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (Bt, S, H))) * 0.1
    Bm = jax.random.normal(ks[2], (Bt, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (Bt, S, N)) * 0.5
    y, st_ = ssk.ssm_scan(x, a, Bm, Cm, chunk=chunk, interpret=True)
    yr, str_ = ref.ssm_ref(x, a, Bm, Cm)
    np.testing.assert_allclose(y, yr, atol=2e-3)
    np.testing.assert_allclose(st_, str_, atol=2e-3)


def test_ssm_chunked_jnp_matches_ref():
    """The XLA-fallback chunked SSD (models.ssm) against the recurrence."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    Bt, S, H, P, N = 2, 100, 3, 8, 16
    x = jax.random.normal(ks[0], (Bt, S, H, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (Bt, S, H))) * 0.1
    Bm = jax.random.normal(ks[2], (Bt, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (Bt, S, N)) * 0.5
    for unroll in (False, True):
        y, st_ = ssd_chunked(x, a, Bm, Cm, chunk=32, unroll=unroll)
        yr, str_ = ref.ssm_ref(x, a, Bm, Cm)
        np.testing.assert_allclose(y, yr, atol=2e-3)
        np.testing.assert_allclose(st_, str_, atol=2e-3)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------
@settings(**SET)
@given(st.sampled_from([16, 48, 64]), st.sampled_from([8, 16]),
       st.sampled_from([8, 16]))
def test_wkv6_sweep(S, P, chunk):
    key = jax.random.PRNGKey(S * 31 + P)
    ks = jax.random.split(key, 5)
    Bt, H = 2, 3
    r = jax.random.normal(ks[0], (Bt, S, H, P)) * 0.5
    k = jax.random.normal(ks[1], (Bt, S, H, P)) * 0.5
    v = jax.random.normal(ks[2], (Bt, S, H, P)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (Bt, S, H, P)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, P)) * 0.3
    y, st_ = wk.wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    yr, str_ = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(y, yr, atol=2e-3)
    np.testing.assert_allclose(st_, str_, atol=2e-3)


def test_wkv_chunked_probe_matches_recurrent():
    """The unrolled chunked WKV (roofline probe path) vs the recurrence."""
    from repro.models.rwkv import wkv_chunked, wkv_recurrent
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    Bt, S, H, P = 2, 70, 2, 8
    r = jax.random.normal(ks[0], (Bt, S, H, P)) * 0.5
    k = jax.random.normal(ks[1], (Bt, S, H, P)) * 0.5
    v = jax.random.normal(ks[2], (Bt, S, H, P)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (Bt, S, H, P)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, P)) * 0.3
    y1, s1 = wkv_chunked(r, k, v, w, u, chunk=16)
    y2, s2 = wkv_recurrent(r, k, v, w, u)
    np.testing.assert_allclose(y1, y2, atol=2e-3)
    np.testing.assert_allclose(s1, s2, atol=2e-3)


# ---------------------------------------------------------------------------
# model-level: chunked attention == naive attention
# ---------------------------------------------------------------------------
def _qkv(S, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (2, S, 8, 16)),
            jax.random.normal(ks[1], (2, S, 2, 16)),
            jax.random.normal(ks[2], (2, S, 2, 16)))


@pytest.mark.parametrize("causal,window,S,q_chunk,kv_chunk,unroll", [
    (True, 0, 80, 32, 32, False),      # Sq not a multiple of the chunk
    (True, 0, 80, 32, 32, True),
    (True, 24, 80, 32, 32, False),     # window: leading blocks skipped
    (True, 24, 80, 32, 32, True),
    (True, 0, 80, 32, 16, False),      # Q and KV chunks of unequal width
    (True, 40, 80, 16, 32, False),
    (False, 0, 80, 32, 32, False),     # non-causal: every block computed
    (False, 0, 80, 32, 32, True),
])
def test_chunked_attention_equals_naive(causal, window, S, q_chunk,
                                        kv_chunk, unroll):
    from repro.models.attention import chunked_attention, naive_attention
    q, k, v = _qkv(S)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_chunk=q_chunk, kv_chunk=kv_chunk, unroll=unroll)
    want = naive_attention(q, k, v, causal=causal, window=window)
    # bf16 probability×value matmul (§Perf A2) widens the tolerance
    np.testing.assert_allclose(out, want, atol=6e-3)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
def test_chunked_attention_gradients_equal_naive(causal, window):
    """q, k and v cotangents through the skipped blocks' scans."""
    from repro.models.attention import chunked_attention, naive_attention
    q, k, v = _qkv(80)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 80, 8, 16))

    def loss(attn, **kw):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=causal, window=window, **kw) * w)
    got = jax.grad(loss(chunked_attention, q_chunk=32, kv_chunk=32),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(naive_attention), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=3e-2)
