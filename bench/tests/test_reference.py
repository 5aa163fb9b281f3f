"""The plain references against the program, on the CPU at a small size.

The decoder reference and the program's model run the same float32
arithmetic on the same weights, so they agree to float32 rounding; the
what-if reference and the program's replay agree to a few float32
roundings of the master weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ref_decoder
import ref_whatif
import tokens as T
import weights as W
from conftest import TINY_CONFIGS, TINY_TRAFFIC


def _program_loss_and_grad_norms(cfg, params, tk, lb):
    from repro.config import RunConfig
    from repro.models import model_loss
    import kinds_train
    mcfg = dataclasses.replace(kinds_train.model_config(cfg),
                               dtype="float32")
    run = RunConfig(attn_impl="naive", remat=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    batch = {"tokens": jnp.asarray(tk), "labels": jnp.asarray(lb),
             "loss_mask": jnp.ones(tk.shape, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: model_loss(mcfg, run, p, batch), has_aux=True)(p32)
    norms = {k: float(jnp.sqrt(jnp.sum(v * v)))
             for k, v in W.flatten(g).items()}
    return float(loss), norms


@pytest.fixture(scope="module")
def kinds_train():
    import sys
    import harness as H
    mod = H.kind_module("train")
    sys.modules["kinds_train"] = mod
    return mod


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_decoder_reference_matches_the_program(name, kinds_train):
    cfg = TINY_CONFIGS[name]
    params = W.make_weights(cfg, 3)
    tk, lb = T.lm_batch(cfg["vocab_size"], 4, 32, seed=3, step=0)
    want_loss, want = _program_loss_and_grad_norms(cfg, params, tk, lb)
    gsq = {}
    _, loss = ref_decoder.Decoder(cfg).round(params, tk, lb, [0.0], gsq=gsq)
    assert loss == pytest.approx(want_loss, rel=1e-5)
    for k, v in gsq.items():
        assert float(v) ** 0.5 == pytest.approx(want[k], rel=1e-3, abs=1e-7)


def test_token_stream_is_the_programs():
    from repro.data.synthetic import lm_token_stream
    b = lm_token_stream(300, 4, 16, seed=2 ** 31 + 9, step=5)
    tk, lb = T.lm_batch(300, 4, 16, seed=2 ** 31 + 9, step=5)
    np.testing.assert_array_equal(b["tokens"], tk)
    np.testing.assert_array_equal(b["labels"], lb)


def test_whatif_reference_matches_the_replay():
    from repro.core import engine, trace as trace_mod
    import harness as H
    kind = H.kind_module("whatif")
    tr = dict(TINY_TRAFFIC["tiny-whatif"])
    seed, d, events = 2 ** 31 + 77, 4096, 96
    run = kind.run_config(tr)
    trace = trace_mod.schedule(run, events)
    got = engine.replay(trace, run,
                        init_params={"w": ref_whatif.make_init(seed, d)},
                        flat_grad=("quadratic", ref_whatif.coeffs_fn(5)))
    w = np.asarray(got.params["w"])
    lrs = np.full((events,), kind.event_lr(tr), np.float32)
    ref = ref_whatif.replay_columns(trace.pulled_ts, lrs, np.arange(d), seed,
                                    5, tr["momentum"])
    assert kind.weight_gap(w, ref) < 1e-5
    # the closed forms are the same numbers on both sides
    a, ws = ref_whatif.coeffs_fn(5)(jnp.arange(8, dtype=jnp.int32))
    a2, ws2 = ref_whatif.coeffs_at(np.arange(8, dtype=np.uint32),
                                   ref_whatif.salt(5), np)
    np.testing.assert_array_equal(np.asarray(a), a2)
    np.testing.assert_array_equal(np.asarray(ws), ws2)
