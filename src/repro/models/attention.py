"""Grouped-query attention with RoPE, qk-norm, QKV-bias and sliding window.

Three implementations, selected by ``RunConfig.attn_impl``:

* ``naive``   — materializes the full score matrix; the test oracle.
* ``chunked`` — online-softmax over the live KV chunks of each Q chunk
                (blocks the mask removes are skipped); memory O(Qc·Kc);
                the default for dry-run lowering on CPU and the pure-XLA
                production fallback.
* ``pallas``  — the TPU flash-attention kernel in ``repro.kernels``.

The decode path (single new token against a cache) is a plain einsum — the
score row is (B, H, S) which is small.  Sliding-window models keep a
ring-buffer cache of ``window`` entries instead of the full sequence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.config import ModelConfig, RunConfig
from repro.models.layers import apply_rope, rms_norm

NEG_INF = -1e30

# counters (repro.telemetry), at trace time: the (Q chunk, KV chunk) blocks
# chunked attention computes, and those it skips as fully masked
KV_BLOCKS = "attention.kv_blocks"
KV_BLOCKS_SKIPPED = "attention.kv_blocks_skipped"


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    M, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    s = float(1.0 / np.sqrt(M))
    p = {
        "w_q": jax.random.normal(ks[0], (M, H, Dh), dtype) * s,
        "w_k": jax.random.normal(ks[1], (M, KV, Dh), dtype) * s,
        "w_v": jax.random.normal(ks[2], (M, KV, Dh), dtype) * s,
        "w_o": jax.random.normal(ks[3], (H, Dh, M), dtype) * float(1.0 / np.sqrt(H * Dh)),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((H, Dh), dtype)
        p["b_k"] = jnp.zeros((KV, Dh), dtype)
        p["b_v"] = jnp.zeros((KV, Dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dtype)
        p["k_norm"] = jnp.ones((Dh,), dtype)
    return p


def project_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, M) -> q (B,S,H,Dh), k/v (B,S,KV,Dh), RoPE applied."""
    q = jnp.einsum("bsm,mhd->bshd", x, p["w_q"])
    k = jnp.einsum("bsm,mkd->bskd", x, p["w_k"])
    v = jnp.einsum("bsm,mkd->bskd", x, p["w_v"])
    if cfg.qkv_bias:
        q = q + p["b_q"]
        k = k + p["b_k"]
        v = v + p["b_v"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def output_proj(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshd,hdm->bsm", o, p["w_o"])


# ---------------------------------------------------------------------------
# Score-matrix (naive) implementation — the oracle
# ---------------------------------------------------------------------------
def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, window: int = 0,
                    q_positions: Optional[jax.Array] = None,
                    k_positions: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,Sq,H,Dh) k: (B,Sk,KV,Dh) v: (B,Sk,KV,Dv). Returns
    (B,Sq,H,Dv); the scores are scaled by 1/√Dh."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(Dh)
    qg = q.reshape(B, Sq, KV, G, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qp = (q_positions if q_positions is not None
          else jnp.arange(Sq))[:, None]                       # (Sq, 1)
    kp = (k_positions if k_positions is not None
          else jnp.arange(k.shape[1]))[None, :]               # (1, Sk)
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax implementation
# ---------------------------------------------------------------------------
def kv_block_ranges(Sq: int, Sk: int, q_chunk: int, kv_chunk: int, *,
                    causal: bool, window: int = 0) -> list:
    """The KV chunks each Q chunk must visit: ``[lo, hi)`` per Q chunk.

    Positions are ``arange`` (self-attention), so a block whose every entry
    the causal or window mask removes is known at trace time: KV chunks past
    the Q chunk's last row (causal) or before its first row's window are
    never computed."""
    q_chunk, kv_chunk = min(q_chunk, Sq), min(kv_chunk, Sk)
    nq, nk = -(-Sq // q_chunk), -(-Sk // kv_chunk)
    ranges = []
    for i in range(nq):
        q0 = i * q_chunk
        hi = min(nk, (q0 + q_chunk - 1) // kv_chunk + 1) if causal else nk
        lo = max(0, (q0 - window + 1) // kv_chunk) if window > 0 else 0
        ranges.append((lo, hi))
    return ranges


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: int = 0,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      unroll: bool = False) -> jax.Array:
    """Blockwise attention: per Q chunk, a scan over the KV chunks that
    the mask leaves live (:func:`kv_block_ranges`).

    Equivalent to naive_attention for self-attention with aligned positions;
    v may be narrower than q and k (latent attention: 192 against 128).
    Causal attention computes the blocks on and below the diagonal only
    (10 of 16 at four chunks a side), as a flash kernel's skip does; the
    blocks computed and skipped are counted at trace time.  ``unroll``
    replaces the scans with trace-time python loops over the same blocks
    (roofline cost probes — cost_analysis counts while bodies once).  Each
    KV step is checkpointed, as a flash attention's backward is: the
    backward pass recomputes the step's scores, and the scan saves only its
    (m, l, acc) carries, not S×S scores per head.
    """
    B, Sq, H, Dh = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    pq, pk = nq * q_chunk - Sq, nk * kv_chunk - Sk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp_ = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(Dh)

    qb = qp.reshape(B, nq, q_chunk, KV, G, Dh).transpose(1, 0, 3, 4, 2, 5)
    kb = kp_.reshape(B, nk, kv_chunk, KV, Dh).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, kv_chunk, KV, Dv).transpose(1, 0, 3, 2, 4)
    # qb: (nq, B, KV, G, Qc, Dh); kb/vb: (nk, B, KV, Kc, Dh)
    k_pos_base = jnp.arange(nk) * kv_chunk

    ranges = kv_block_ranges(Sq, Sk, q_chunk, kv_chunk, causal=causal,
                             window=window)
    computed = sum(hi - lo for lo, hi in ranges)
    telemetry.count(KV_BLOCKS, computed)
    telemetry.count(KV_BLOCKS_SKIPPED, nq * nk - computed)

    def one_q_block(qc, q0, lo, hi):
        # qc: (B, KV, G, Qc, Dh); q0, lo, hi: trace-time ints
        qpos = q0 + jnp.arange(q_chunk)                        # (Qc,)

        def kv_step(carry, inp):
            m, l, acc = carry
            kc, vc, k0 = inp                                   # (B,KV,Kc,Dh)
            s = jnp.einsum("bkgqd,bksd->bkgqs",
                           qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            kpos = k0 + jnp.arange(kv_chunk)
            mask = kpos[None, :] < Sk                          # padding mask
            mask = jnp.broadcast_to(mask, (q_chunk, kv_chunk))
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            # bf16 probabilities into the PV matmul (accumulate fp32):
            # halves the dominant score-tensor HBM traffic (§Perf A2)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p.astype(jnp.bfloat16),
                vc.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        kv_step = jax.checkpoint(kv_step, prevent_cse=False)
        carry = (jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
                 jnp.zeros((B, KV, G, q_chunk), jnp.float32),
                 jnp.zeros((B, KV, G, q_chunk, Dv), jnp.float32))
        if unroll:
            for j in range(lo, hi):
                carry, _ = kv_step(carry, (kb[j], vb[j], k_pos_base[j]))
        else:
            carry, _ = jax.lax.scan(kv_step, carry, (
                kb[lo:hi], vb[lo:hi], k_pos_base[lo:hi]))
        _, l, acc = carry
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jnp.stack([one_q_block(qb[i], i * q_chunk, lo, hi)
                     for i, (lo, hi) in enumerate(ranges)])
    # out: (nq, B, KV, G, Qc, Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode step against a cache
# ---------------------------------------------------------------------------
def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     cache_len: jax.Array, *, window: int = 0) -> jax.Array:
    """q: (B, 1, H, Dh); cache_k/v: (B, C, KV, Dh); cache_len: () or (B,).

    Full-attention models: C = max seq, positions [0, cache_len) are valid.
    Sliding-window models: C = window (ring buffer) and all slots < min(len, C)
    are valid (ring order does not matter for attention, which is a set
    operation over (k, v) pairs — RoPE was already applied at insert time).
    Per-sequence ``cache_len`` supports continuous batching.
    """
    B, _, H, Dh = q.shape
    C, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(Dh)
    qg = q.reshape(B, KV, G, Dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32)) * scale
    lens = jnp.broadcast_to(jnp.asarray(cache_len), (B,))
    valid = jnp.arange(C)[None, None, None, :] < lens[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cache_v.astype(jnp.float32))
    return o.reshape(B, 1, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Cache plumbing
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Cache for ONE attention layer.  Sliding-window models only keep the
    window (ring buffer)."""
    C = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), dtype),
    }


def cache_insert(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 position: jax.Array) -> dict:
    """Insert a single (B, 1, KV, Dh) entry at ``position`` (ring if full).

    ``position`` is a scalar (whole batch aligned — the dry-run shapes) or a
    (B,) vector (continuous batching: every sequence at its own depth)."""
    C = cache["k"].shape[1]
    if jnp.ndim(position) == 0:
        slot = position % C
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
        return {"k": k, "v": v}
    slots = position % C                                   # (B,)

    def upd(c_b, n_b, s_b):
        return jax.lax.dynamic_update_slice(c_b, n_b, (s_b, 0, 0))
    k = jax.vmap(upd)(cache["k"], k_new, slots)
    v = jax.vmap(upd)(cache["v"], v_new, slots)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Top-level attention entry points
# ---------------------------------------------------------------------------
def attention_forward(cfg: ModelConfig, run: RunConfig, p: dict,
                      x: jax.Array, positions: jax.Array) -> jax.Array:
    """Self-attention over a full sequence (train / prefill)."""
    q, k, v = project_qkv(cfg, p, x, positions)
    window = cfg.sliding_window
    if run.attn_impl == "naive":
        o = naive_attention(q, k, v, causal=cfg.causal, window=window)
    elif run.attn_impl == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=cfg.causal, window=window)
    else:
        o = chunked_attention(q, k, v, causal=cfg.causal, window=window,
                              q_chunk=run.attn_q_chunk,
                              kv_chunk=run.attn_kv_chunk,
                              unroll=run.unroll)
    return output_proj(p, o)


def attention_decode(cfg: ModelConfig, run: RunConfig, p: dict,
                     x: jax.Array, position: jax.Array,
                     cache: dict) -> Tuple[jax.Array, dict]:
    """One-token decode.  x: (B, 1, M); position: () int32 (aligned batch)
    or (B,) int32 (continuous batching — per-sequence depths)."""
    B = x.shape[0]
    if jnp.ndim(position) == 0:
        pos = jnp.reshape(position, (1, 1))                 # broadcast rope
    else:
        pos = position[:, None]                             # (B, 1)
    q, k, v = project_qkv(cfg, p, x, pos)
    cache = cache_insert(cache, k, v, position)
    C = cache["k"].shape[1]
    cache_len = jnp.minimum(position + 1, C)
    o = decode_attention(q, cache["k"], cache["v"], cache_len,
                         window=cfg.sliding_window)
    return output_proj(p, o), cache
