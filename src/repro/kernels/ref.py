"""Pure-jnp oracles for every Pallas kernel (no Pallas, no chunking tricks).

Each oracle is the most literal possible implementation of the math — used
by tests (``tests/test_kernels.py``) and the hypothesis shape sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.spec import combine_terms


# ---------------------------------------------------------------------------
# ps_update
# ---------------------------------------------------------------------------
def ps_update_ref(w, v, g, coef, *, momentum: float, lr: float):
    """w/v: (D,); g: (c, D); coef: (c,)."""
    g32, coef = g.astype(jnp.float32), coef.astype(jnp.float32)
    weighted = combine_terms(g.shape[0], lambda i: coef[i] * g32[i])
    v_new = momentum * v.astype(jnp.float32) + weighted
    w_new = w.astype(jnp.float32) - lr * v_new
    return w_new.astype(w.dtype), v_new.astype(v.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention_ref(q, k, v, *, causal: bool, window: int = 0):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) — materialized softmax."""
    from repro.models.attention import naive_attention
    return naive_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# ssm (sequential recurrence — the definitional oracle)
# ---------------------------------------------------------------------------
def ssm_ref(x, a, Bm, Cm):
    """x: (B,S,H,P); a: (B,S,H); Bm/Cm: (B,S,N).
    S_t = exp(a_t)·S_{t-1} + B_t ⊗ x_t ;  y_t = C_t · S_t."""
    Bt, S, H, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    def step(state, t):
        state = (jnp.exp(af[:, t])[..., None, None] * state
                 + jnp.einsum("bn,bhp->bhnp", Bf[:, t], xf[:, t]))
        y = jnp.einsum("bn,bhnp->bhp", Cf[:, t], state)
        return state, y

    state0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    final, ys = jax.lax.scan(step, state0, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final


# ---------------------------------------------------------------------------
# wkv6 (sequential recurrence)
# ---------------------------------------------------------------------------
def wkv6_ref(r, k, v, w, u):
    """r/k/v/w: (B,S,H,P); u: (H,P).  Literal recurrence."""
    from repro.models.rwkv import wkv_recurrent
    return wkv_recurrent(r, k, v, w, u)
