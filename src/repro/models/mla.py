"""Multi-head latent attention (MLA) as DeepSeek-V2/V3 publish it.

Without a query LoRA (``q_lora_rank`` null, as Moonlight-16B-A3B):

    q              = x W_q                     (H heads of Dn + Dr)
    [c, k_rope]    = x W_kv_a                  (latent R, shared rope key Dr)
    [k_nope, v]    = RMSNorm(c) W_kv_b         (H heads of Dn, and Dv)
    k              = [k_nope, RoPE(k_rope)]    (the rope key shared by heads)
    q              = [q_nope, RoPE(q_rope)]
    o              = softmax(q kᵀ / √(Dn + Dr)) v ;  out = o W_o

RoPE is rotate-half over the Dr rope channels.  DeepSeek's released
weights interleave those channels and permute them on load; with weights
drawn from a seed that permutation is only a relabelling of channels.
The full-sequence forward (training, prefill) only: decoding through a
latent cache is not built, and asking for it raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, RunConfig
from repro.models.attention import chunked_attention, naive_attention
from repro.models.layers import apply_rope, rms_norm


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    M, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, dtype) * float(fan_in ** -0.5)
    return {"w_q": normal(ks[0], (M, H, Dn + Dr), M),
            "w_kv_a": normal(ks[1], (M, R + Dr), M),
            "kv_norm": jnp.ones((R,), dtype),
            "w_kv_b": normal(ks[2], (R, H, Dn + Dv), R),
            "w_o": normal(ks[3], (H, Dv, M), H * Dv)}


def mla_forward(cfg: ModelConfig, run: RunConfig, p: dict, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """x: (B, S, M) → (B, S, M), causal over the whole sequence."""
    R, Dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.named_scope("mla.project"):
        q = jnp.einsum("bsm,mhd->bshd", x, p["w_q"])
        kv_a = jnp.einsum("bsm,mr->bsr", x, p["w_kv_a"])
        c = rms_norm(kv_a[..., :R], p["kv_norm"], cfg.norm_eps)
        kv = jnp.einsum("bsr,rhd->bshd", c, p["w_kv_b"])
        k_rope = apply_rope(kv_a[..., None, R:], positions, cfg.rope_theta)
        q = jnp.concatenate(
            [q[..., :Dn], apply_rope(q[..., Dn:], positions, cfg.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :Dn],
             jnp.broadcast_to(k_rope, kv.shape[:-1] + k_rope.shape[-1:])],
            axis=-1)
        v = kv[..., Dn:]
    with jax.named_scope("mla.attend"):
        if run.attn_impl == "naive":
            o = naive_attention(q, k, v, causal=True)
        else:
            o = chunked_attention(q, k, v, causal=True,
                                  q_chunk=run.attn_q_chunk,
                                  kv_chunk=run.attn_kv_chunk,
                                  unroll=run.unroll)
    with jax.named_scope("mla.project"):
        return jnp.einsum("bshd,hdm->bsm", o, p["w_o"])


def mla_decode(*_args, **_kw):
    raise NotImplementedError(
        "latent attention has no decode path: the latent KV cache is not "
        "built (training and prefill only)")

