"""Block zoo: one forward/decode/init triple per block type.

A model is a repeating *unit* (``ModelConfig.block_pattern``) of these blocks
stacked ``n_units`` times.  All blocks are pre-norm residual.  ``shared``
carries the weight-shared attention block used by zamba2 (BLOCK_SHARED_ATTN);
it is a closure constant under the layer scan, not a scanned parameter.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import config as C
from repro.config import ModelConfig, RunConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import init_rms_norm, init_swiglu, rms_norm, swiglu

ZERO_AUX = {"lb_loss": 0.0, "z_loss": 0.0, "dropped_fraction": 0.0}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_block(block_type: str, key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    M = cfg.d_model
    if block_type == C.BLOCK_ATTN:
        return {"norm1": init_rms_norm(M, dtype),
                "attn": attn.init_attention(ks[0], cfg, dtype),
                "norm2": init_rms_norm(M, dtype),
                "mlp": init_swiglu(ks[1], M, cfg.d_ff, dtype)}
    if block_type == C.BLOCK_MOE:
        return {"norm1": init_rms_norm(M, dtype),
                "attn": attn.init_attention(ks[0], cfg, dtype),
                "norm2": init_rms_norm(M, dtype),
                "moe": moe_mod.init_moe(ks[1], cfg, dtype)}
    if block_type == C.BLOCK_MOE_DENSE_RESIDUAL:
        return {"norm1": init_rms_norm(M, dtype),
                "attn": attn.init_attention(ks[0], cfg, dtype),
                "norm2": init_rms_norm(M, dtype),
                "mlp": init_swiglu(ks[1], M, cfg.d_ff, dtype),
                "moe": moe_mod.init_moe(ks[2], cfg, dtype)}
    if block_type == C.BLOCK_MLA:
        return {"norm1": init_rms_norm(M, dtype),
                "attn": mla_mod.init_mla(ks[0], cfg, dtype),
                "norm2": init_rms_norm(M, dtype),
                "mlp": init_swiglu(ks[1], M, cfg.d_ff, dtype)}
    if block_type == C.BLOCK_MLA_MOE:
        return {"norm1": init_rms_norm(M, dtype),
                "attn": mla_mod.init_mla(ks[0], cfg, dtype),
                "norm2": init_rms_norm(M, dtype),
                "moe": moe_mod.init_moe_dropless(ks[1], cfg, dtype)}
    if block_type == C.BLOCK_MAMBA:
        return {"norm1": init_rms_norm(M, dtype),
                "mamba": ssm_mod.init_mamba(ks[0], cfg, dtype)}
    if block_type == C.BLOCK_RWKV:
        return {"norm1": init_rms_norm(M, dtype),
                "norm2": init_rms_norm(M, dtype),
                "rwkv": rwkv_mod.init_rwkv(ks[0], cfg, dtype)}
    if block_type == C.BLOCK_SHARED_ATTN:
        # per-unit parameters only: the norms.  Attention/MLP weights live in
        # the shared trunk (init_shared_block).
        return {"norm1": init_rms_norm(M, dtype),
                "norm2": init_rms_norm(M, dtype)}
    raise ValueError(block_type)


def init_shared_block(key, cfg: ModelConfig, dtype) -> Optional[dict]:
    if C.BLOCK_SHARED_ATTN not in cfg.block_pattern:
        return None
    k1, k2 = jax.random.split(key)
    return {"attn": attn.init_attention(k1, cfg, dtype),
            "mlp": init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)}


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def block_forward(block_type: str, cfg: ModelConfig, run: RunConfig,
                  p: dict, shared: Optional[dict], x: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, dict]:
    if block_type == C.BLOCK_ATTN:
        h = attn.attention_forward(cfg, run, p["attn"],
                                   rms_norm(x, p["norm1"]["scale"],
                                            cfg.norm_eps), positions)
        x = x + h
        x = x + swiglu(rms_norm(x, p["norm2"]["scale"], cfg.norm_eps),
                       p["mlp"])
        return x, ZERO_AUX
    if block_type == C.BLOCK_MOE:
        h = attn.attention_forward(cfg, run, p["attn"],
                                   rms_norm(x, p["norm1"]["scale"],
                                            cfg.norm_eps), positions)
        x = x + h
        mo, aux = moe_mod.moe_forward(
            cfg, p["moe"], rms_norm(x, p["norm2"]["scale"], cfg.norm_eps))
        return x + mo, aux
    if block_type == C.BLOCK_MOE_DENSE_RESIDUAL:
        h = attn.attention_forward(cfg, run, p["attn"],
                                   rms_norm(x, p["norm1"]["scale"],
                                            cfg.norm_eps), positions)
        x = x + h
        xn = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
        mo, aux = moe_mod.moe_forward(cfg, p["moe"], xn)
        return x + mo + swiglu(xn, p["mlp"]), aux
    if block_type in (C.BLOCK_MLA, C.BLOCK_MLA_MOE):
        x = x + mla_mod.mla_forward(cfg, run, p["attn"],
                                    rms_norm(x, p["norm1"]["scale"],
                                             cfg.norm_eps), positions)
        xn = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
        if block_type == C.BLOCK_MLA:
            return x + swiglu(xn, p["mlp"]), ZERO_AUX
        mo, aux = moe_mod.moe_dropless_forward(cfg, p["moe"], xn)
        return x + mo, aux
    if block_type == C.BLOCK_MAMBA:
        h = ssm_mod.mamba_forward(cfg, p["mamba"],
                                  rms_norm(x, p["norm1"]["scale"],
                                           cfg.norm_eps),
                                  use_pallas=run.use_pallas,
                                  unroll=run.unroll)
        return x + h, ZERO_AUX
    if block_type == C.BLOCK_RWKV:
        h = rwkv_mod.rwkv_forward(cfg, p["rwkv"],
                                  rms_norm(x, p["norm1"]["scale"],
                                           cfg.norm_eps),
                                  use_pallas=run.use_pallas,
                                  unroll=run.unroll)
        x = x + h
        h = rwkv_mod.rwkv_channel_mix(cfg, p["rwkv"],
                                      rms_norm(x, p["norm2"]["scale"],
                                               cfg.norm_eps))
        return x + h, ZERO_AUX
    if block_type == C.BLOCK_SHARED_ATTN:
        h = attn.attention_forward(cfg, run, shared["attn"],
                                   rms_norm(x, p["norm1"]["scale"],
                                            cfg.norm_eps), positions)
        x = x + h
        x = x + swiglu(rms_norm(x, p["norm2"]["scale"], cfg.norm_eps),
                       shared["mlp"])
        return x, ZERO_AUX
    raise ValueError(block_type)


# ---------------------------------------------------------------------------
# caches & decode
# ---------------------------------------------------------------------------
def init_block_cache(block_type: str, cfg: ModelConfig, batch: int,
                     max_len: int, dtype) -> Dict[str, Any]:
    if block_type in (C.BLOCK_ATTN, C.BLOCK_MOE, C.BLOCK_MOE_DENSE_RESIDUAL,
                      C.BLOCK_SHARED_ATTN):
        return attn.init_kv_cache(cfg, batch, max_len, dtype)
    if block_type in (C.BLOCK_MLA, C.BLOCK_MLA_MOE):
        return {}
    if block_type == C.BLOCK_MAMBA:
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if block_type == C.BLOCK_RWKV:
        return rwkv_mod.init_rwkv_cache(cfg, batch, dtype)
    raise ValueError(block_type)


def block_decode(block_type: str, cfg: ModelConfig, run: RunConfig,
                 p: dict, shared: Optional[dict], x: jax.Array,
                 position: jax.Array, cache: dict
                 ) -> Tuple[jax.Array, dict, dict]:
    if block_type == C.BLOCK_ATTN:
        h, cache = attn.attention_decode(
            cfg, run, p["attn"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), position, cache)
        x = x + h
        x = x + swiglu(rms_norm(x, p["norm2"]["scale"], cfg.norm_eps),
                       p["mlp"])
        return x, cache, ZERO_AUX
    if block_type == C.BLOCK_MOE:
        h, cache = attn.attention_decode(
            cfg, run, p["attn"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), position, cache)
        x = x + h
        mo, aux = moe_mod.moe_forward(
            cfg, p["moe"], rms_norm(x, p["norm2"]["scale"], cfg.norm_eps))
        return x + mo, cache, aux
    if block_type == C.BLOCK_MOE_DENSE_RESIDUAL:
        h, cache = attn.attention_decode(
            cfg, run, p["attn"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), position, cache)
        x = x + h
        xn = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
        mo, aux = moe_mod.moe_forward(cfg, p["moe"], xn)
        return x + mo + swiglu(xn, p["mlp"]), cache, aux
    if block_type in (C.BLOCK_MLA, C.BLOCK_MLA_MOE):
        mla_mod.mla_decode()
    if block_type == C.BLOCK_MAMBA:
        h, cache = ssm_mod.mamba_decode(
            cfg, p["mamba"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), cache)
        return x + h, cache, ZERO_AUX
    if block_type == C.BLOCK_RWKV:
        h, cache = rwkv_mod.rwkv_decode_time_mix(
            cfg, p["rwkv"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), cache)
        x = x + h
        h, cache = rwkv_mod.rwkv_decode_channel_mix(
            cfg, p["rwkv"],
            rms_norm(x, p["norm2"]["scale"], cfg.norm_eps), cache)
        return x + h, cache, ZERO_AUX
    if block_type == C.BLOCK_SHARED_ATTN:
        h, cache = attn.attention_decode(
            cfg, run, shared["attn"],
            rms_norm(x, p["norm1"]["scale"], cfg.norm_eps), position, cache)
        x = x + h
        x = x + swiglu(rms_norm(x, p["norm2"]["scale"], cfg.norm_eps),
                       shared["mlp"])
        return x, cache, ZERO_AUX
    raise ValueError(block_type)
