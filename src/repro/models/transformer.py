"""Model spine: scan-over-units language model / encoder.

Parameters for the ``n_units`` repeats of ``block_pattern`` are stacked on a
leading axis and the forward pass is a ``jax.lax.scan`` over that axis, so
HLO size (and compile time) is independent of depth — essential for
llama3-405b's 126 layers on the 512-device dry-run.

Modality frontends are stubs per the assignment: audio models consume
precomputed frame embeddings, VLMs consume precomputed patch embeddings,
each passed through a learned linear projector (the one carve-out to
"implement everything").
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import config as C
from repro.config import ModelConfig, RunConfig
from repro.models import blocks as B
from repro.models.layers import (dtype_of, embed, init_embedding,
                                 init_lm_head, init_rms_norm, lm_head,
                                 rms_norm, softmax_cross_entropy)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_unit(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"block_{i}": B.init_block(bt, ks[i], cfg, dtype)
            for i, bt in enumerate(cfg.block_pattern)}


def init_model(cfg: ModelConfig, key) -> dict:
    dtype = dtype_of(cfg.dtype)
    k_embed, k_units, k_shared, k_head, k_front = jax.random.split(key, 5)
    unit_keys = jax.random.split(k_units, cfg.n_units)
    units = jax.vmap(lambda k: init_unit(k, cfg, dtype))(unit_keys)
    params = {
        "embed": init_embedding(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "units": units,
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }
    if cfg.first_k_dense:
        lead_keys = jax.random.split(jax.random.fold_in(k_units, 1),
                                     cfg.first_k_dense)
        params["lead"] = {f"layer_{i}": B.init_block(cfg.lead_block, k, cfg,
                                                     dtype)
                          for i, k in enumerate(lead_keys)}
    if not cfg.tie_embeddings:
        params["head"] = init_lm_head(k_head, cfg.d_model,
                                      cfg.padded_vocab, dtype)
    shared = B.init_shared_block(k_shared, cfg, dtype)
    if shared is not None:
        params["shared"] = shared
    if cfg.frontend != "none":
        params["frontend_proj"] = (
            jax.random.normal(k_front, (cfg.d_model, cfg.d_model), dtype)
            * float(1.0 / np.sqrt(cfg.d_model)))
    return params


# ---------------------------------------------------------------------------
# input embedding (handles the three modality layouts)
# ---------------------------------------------------------------------------
def embed_inputs(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array]
                 ) -> jax.Array:
    """Returns (B, S, M) input activations."""
    if cfg.frontend == "audio":
        # batch["frames"]: (B, S, M) precomputed frame embeddings (stub)
        return jnp.einsum("bsm,mn->bsn", batch["frames"],
                          params["frontend_proj"])
    if cfg.frontend == "vision":
        # early fusion: projected patches prepended to token embeddings
        patches = jnp.einsum("bpm,mn->bpn", batch["patches"],
                             params["frontend_proj"])
        toks = embed(batch["tokens"], params["embed"])
        return jnp.concatenate([patches, toks], axis=1)
    return embed(batch["tokens"], params["embed"])


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def model_forward(cfg: ModelConfig, run: RunConfig, params: dict,
                  batch: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward.  Returns (logits fp32 (B,S,V), aux).  The
    leading dense layers (``first_k_dense``) run once each before the scan
    over the units, under the same remat policy.  The counts the blocks
    report (aux "counts", summed over a unit's blocks) come back stacked
    per unit."""
    x = embed_inputs(cfg, params, batch)
    S = x.shape[1]
    positions = jnp.arange(S)
    shared = params.get("shared")

    def constrain(x):
        if run.residual_spec is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*run.residual_spec))

    def lead_body(x, layer_params):
        x, _ = B.block_forward(cfg.lead_block, cfg, run, layer_params,
                               shared, constrain(x), positions)
        return x

    def unit_body(carry, unit_params):
        x, lb, zl = carry
        counts = {}
        for i, bt in enumerate(cfg.block_pattern):
            x, aux = B.block_forward(bt, cfg, run, unit_params[f"block_{i}"],
                                     shared, constrain(x), positions)
            lb = lb + aux["lb_loss"]
            zl = zl + aux["z_loss"]
            for name, n in aux.get("counts", {}).items():
                counts[name] = counts[name] + n if name in counts else n
        return (x, lb, zl), counts

    if run.remat:
        lead_body = jax.checkpoint(lead_body, prevent_cse=False)
        unit_body = jax.checkpoint(unit_body, prevent_cse=False)

    for i in range(cfg.first_k_dense):
        x = lead_body(x, params["lead"][f"layer_{i}"])
    carry = (x, jnp.float32(0.0), jnp.float32(0.0))
    if run.unroll:
        # python loop for the roofline cost probes (see RunConfig.unroll)
        per_unit = []
        for u in range(cfg.n_units):
            unit_params = jax.tree.map(lambda a: a[u], params["units"])
            carry, st = unit_body(carry, unit_params)
            per_unit.append(st)
        (x, lb_loss, z_loss) = carry
        counts = jax.tree.map(lambda *a: jnp.stack(a), *per_unit)
    else:
        (x, lb_loss, z_loss), counts = jax.lax.scan(unit_body, carry,
                                                    params["units"])

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head_w = (params["embed"].T if cfg.tie_embeddings else params["head"])
    logits = _mask_padded(cfg, lm_head(x, head_w))
    aux = {"lb_loss": lb_loss, "z_loss": z_loss}
    if counts:
        aux["counts"] = counts
    return logits, aux


def _mask_padded(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Vocab is padded to a multiple of 256 for sharding (config.padded_vocab);
    padded ids get -inf so CE/argmax/sampling never see them."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(valid, logits, -1e30)


def model_loss(cfg: ModelConfig, run: RunConfig, params: dict,
               batch: Dict[str, jax.Array], sample_weights=None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy (+ MoE aux losses).  batch carries labels & loss_mask.
    ``sample_weights`` (B,) — per-sample loss weights used by the fused
    softsync engine (staleness-weighted gradient combination)."""
    logits, aux = model_forward(cfg, run, params, batch)
    mask = batch.get("loss_mask")
    if sample_weights is not None:
        w = sample_weights[:, None]
        mask = w if mask is None else mask * w
    ce = softmax_cross_entropy(logits, batch["labels"], mask)
    loss = ce + aux["lb_loss"] + aux["z_loss"]
    metrics = {"loss": loss, "ce": ce, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------
def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked (n_units leading axis) per-block caches."""
    dtype = dtype_of(cfg.dtype)

    def one_unit(_):
        return {f"block_{i}": B.init_block_cache(bt, cfg, batch, max_len,
                                                 dtype)
                for i, bt in enumerate(cfg.block_pattern)}

    unit_cache = one_unit(None)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_units,) + a.shape),
        unit_cache)


def model_decode_step(cfg: ModelConfig, run: RunConfig, params: dict,
                      token: jax.Array, position: jax.Array, caches: dict
                      ) -> Tuple[jax.Array, dict]:
    """One decode step.  token: (B, 1) int32 (or (B,1,M) embeds for audio —
    not used: encoder-only models have no decode).  position: () int32.
    Returns (logits (B, 1, V) fp32, new caches)."""
    if cfg.first_k_dense:
        raise NotImplementedError("decode through leading dense layers is "
                                  "not built (training and prefill only)")
    x = embed(token, params["embed"])
    shared = params.get("shared")

    def unit_body(x, scanned):
        unit_params, unit_cache = scanned
        new_cache = {}
        for i, bt in enumerate(cfg.block_pattern):
            x, c, _ = B.block_decode(bt, cfg, run,
                                     unit_params[f"block_{i}"], shared, x,
                                     position, unit_cache[f"block_{i}"])
            new_cache[f"block_{i}"] = c
        return x, new_cache

    if run.unroll:
        new_caches = []
        for u in range(cfg.n_units):
            scanned = jax.tree.map(lambda a: a[u], (params["units"], caches))
            x, nc = unit_body(x, scanned)
            new_caches.append(nc)
        new_caches = jax.tree.map(lambda *cs: jnp.stack(cs), *new_caches)
    else:
        x, new_caches = jax.lax.scan(unit_body, x, (params["units"], caches))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head_w = (params["embed"].T if cfg.tie_embeddings else params["head"])
    return _mask_padded(cfg, lm_head(x, head_w)), new_caches


# ---------------------------------------------------------------------------
# convenience: parameter counting on the real pytree
# ---------------------------------------------------------------------------
def count_params(params: dict) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
