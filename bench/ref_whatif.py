"""The what-if problem's definition and its plain reference replay.

The problem is a diagonal quadratic, gradient g = a ⊙ (w − w*), on D
weights.  Its curvature a ∈ [0.5, 1.5) and target w* ∈ [−1, 1) are closed
forms of a weight's flat position and the mix's problem seed, its start
w₀ of the position and the run's seed, all worked out in uint32
arithmetic, so the program's device and this reference compute the same
float32 values at any position.

The reference replays a column sample of the weights event by event in
float32: event j folds the c gradients taken at the snapshots the trace
says were pulled (each snapshot is the bfloat16 rounding of the master
weights of its time), averages them, and applies momentum SGD to the
float32 master weights.  It keeps every snapshot, not a ring, so a ring
that overwrote a row still needed shows.  ``quant="bf16"`` is the
control: master weights and momentum kept in bfloat16.  ``half=True``
plants a fault: each event averages the first half of its gradients
only.
"""

from __future__ import annotations

import functools

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def salt(seed: int) -> int:
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _mix(x, xp):
    u32 = xp.uint32
    x = x * u32(_M1)
    x = x ^ (x >> u32(16))
    x = x * u32(_M2)
    x = x ^ (x >> u32(13))
    x = x * u32(_M3)
    return x ^ (x >> u32(16))


def _unit(pos, salt_value: int, stream: int, xp):
    """A float32 in [0, 1) from (position, seed, stream), exactly."""
    u32 = xp.uint32
    x = (pos.astype(u32) + xp.asarray(salt_value, dtype=u32)
         + u32((0x632BE5AB * stream) & 0xFFFFFFFF))
    x = _mix(_mix(x, xp) ^ u32(stream + 1), xp)
    return (x >> u32(8)).astype(xp.float32) * xp.float32(2.0 ** -24)


def coeffs_at(pos, salt_value: int, xp):
    """(a, w*) at int positions ``pos``."""
    a = xp.float32(0.5) + _unit(pos, salt_value, 1, xp)
    wstar = xp.float32(2.0) * _unit(pos, salt_value, 2, xp) - xp.float32(1.0)
    return a, wstar


def init_at(pos, salt_value: int, xp):
    """The start weights w₀ at int positions ``pos``."""
    return xp.float32(0.04) * _unit(pos, salt_value, 3, xp) - xp.float32(0.02)


def coeffs_fn(problem_seed: int):
    """``coeffs(pos) -> (a, w*)`` for the program's what-if replay.  The
    problem's seed is the mix's, not the run's: it is a constant of the
    compiled replay, which the cache then holds for every run."""
    import jax.numpy as jnp
    s = salt(problem_seed)
    return lambda pos: coeffs_at(pos, s, jnp)


@functools.lru_cache(maxsize=4)
def _init_fn(d: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda s: init_at(jnp.arange(d, dtype=jnp.uint32), s,
                                     jnp))


def make_init(seed: int, d: int):
    """The (D,) float32 start weights from the run's seed, made on the
    device in one call (the seed is an argument: one program serves
    every seed)."""
    import jax.numpy as jnp
    return _init_fn(d)(jnp.uint32(salt(seed)))


def round_bf16(w):
    """float32 → the nearest-even bfloat16 value, as float32, done on the
    bits (a compiler may drop the rounding of a bare convert pair)."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(w, jnp.uint32)
    lsb = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + lsb) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@functools.lru_cache(maxsize=4)
def _replay(quant: str, half: bool):
    import jax
    import jax.numpy as jnp

    def run(ts, lrs, w0, a, wstar, momentum):
        steps, c = ts.shape
        keep = round_bf16 if quant == "bf16" else (lambda x: x)
        hist = jnp.zeros((steps + 1,) + w0.shape, jnp.float32)
        hist = hist.at[0].set(round_bf16(w0))

        def event(carry, x):
            hist, w, v = carry
            j, t, lr = x
            used = max(1, c // 2) if half else c
            ghat = jnp.zeros_like(w)
            for i in range(used):
                ghat = ghat + jnp.float32(1.0 / used) * (
                    a * (hist[t[i]] - wstar))
            v = keep(momentum * v + ghat)
            w = keep(w - lr * v)
            return (hist.at[j + 1].set(round_bf16(w)), w, v), None

        carry = (hist, keep(w0), jnp.zeros_like(w0))
        xs = (jnp.arange(steps), ts, lrs)
        (_, w, v), _ = jax.lax.scan(event, carry, xs)
        return w, v
    return jax.jit(run)


def replay_columns(ts: np.ndarray, lrs: np.ndarray, cols: np.ndarray,
                   seed: int, problem_seed: int, momentum: float,
                   quant: str = "f32", half: bool = False):
    """Master weights after the trace's events, at columns ``cols``.
    ``ts``: (events, c) pulled timestamps; ``lrs``: (events,)."""
    import jax.numpy as jnp
    pos = jnp.asarray(cols, jnp.uint32)
    a, wstar = coeffs_at(pos, salt(problem_seed), jnp)
    w0 = init_at(pos, salt(seed), jnp)
    w, _ = _replay(quant, half)(jnp.asarray(ts, jnp.int32),
                                jnp.asarray(lrs, jnp.float32), w0, a, wstar,
                                jnp.float32(momentum))
    return np.asarray(w)
