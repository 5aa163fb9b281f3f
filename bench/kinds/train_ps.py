"""Traffic kind "train_ps": softsync rounds of a DeepSeek-V3-style decoder
(latent attention, a dropless MoE over the experts held here) through the
program's parameter-server step.

Set-up makes the weights from the seed on the device (``ref_mla_moe``'s
layout), builds the program's ``ModelConfig`` from the configuration file,
jits ``core.distributed.make_train_step`` once with the routing-bias rule
(``models.moe.routing_bias_update``) and the weights and optimizer state
donated, and feeds it from the program's pipeline.  Its first rounds are
the warm-up and the rounds the reference checks; the same step, state and
feed then run the window, which counts whole rounds: from the first
dispatch in it to ``block_until_ready`` of the last, the host never more
than one round ahead.  The rounds' routing counts are summed on the device
and added to the program's counters once, after the window.

Besides the dense kind's three gaps, ``bias_gap`` checks the routing-bias
rule, which no gradient carries: the norm of the program's bias minus the
reference's over the reference's, the largest over the checked rounds.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time

import numpy as np

import harness as H
import ref_decoder
import ref_mla_moe as ref
import tokens as T
import weights as W

# rounds the feed is built for; the window stops long before
MAX_ROUNDS = 100_000
# the checked numbers, computed as the dense train kind computes them
_train = H.kind_module("train")


def model_config(cfg: dict):
    from repro.config import ModelConfig
    d = ref.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=d["M"], n_heads=d["H"], n_kv_heads=d["H"], d_ff=d["F"],
        vocab_size=d["V"], block_pattern=("mla_moe",),
        first_k_dense=d["L0"], kv_lora_rank=d["R"],
        qk_nope_head_dim=d["Dn"], qk_rope_head_dim=d["Dr"],
        v_head_dim=d["Dv"], n_experts=d["E"], top_k=d["K"],
        moe_d_ff=d["Fe"], n_shared_experts=cfg["n_shared_experts"],
        experts_held=(d["first"], d["held"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        router_bias_speed=float(cfg["assumed"]["bias_update_speed"]),
        seq_aux_loss=float(cfg["assumed"]["seq_aux_alpha"]),
        router_z_loss=0.0, load_balance_loss=0.0,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def run_config(traffic: dict, seed: int):
    from repro.config import RunConfig
    return RunConfig(protocol=traffic["protocol"],
                     n_softsync=traffic["n_softsync"],
                     n_learners=traffic["n_learners"],
                     minibatch=traffic["seqs_per_learner"],
                     base_lr=traffic["base_lr"],
                     lr_policy=traffic["lr_policy"],
                     optimizer=traffic["optimizer"],
                     momentum=traffic["momentum"], seed=seed,
                     remat=traffic["remat"],
                     num_microbatches=traffic["microbatches"])


class Program:
    """The timed path: one jitted step, its state and its feed."""

    def __init__(self, cell: H.Cell, seed: int):
        import jax
        from repro import optim, telemetry
        from repro.core import distributed
        from repro.data import pipeline
        from repro.models import model_loss, moe

        cfg, tr = cell.config, cell.traffic
        self.mcfg, self.run = model_config(cfg), run_config(tr, seed)
        self.batch = tr["n_learners"] * tr["seqs_per_learner"]
        self.seq = tr["seq_len"]
        mcfg, run = self.mcfg, self.run

        def loss_fn(p, b, sample_weights=None):
            return model_loss(mcfg, run, p, b, sample_weights=sample_weights)

        self.params = ref.make_weights(cfg, seed)
        self.opt = optim.init_state(optim.spec_from_run(run), self.params)
        self.step = jax.jit(distributed.make_train_step(
            run, loss_fn, engine=tr["engine"],
            post_event=moe.routing_bias_update(mcfg)), donate_argnums=(0, 1))
        self.add_counts = jax.jit(telemetry.add_step_counts)
        self.feed = iter(pipeline.PrefetchIterator(
            pipeline.make_batch_fn(mcfg, self.batch, self.seq, seed=seed),
            tr["check_rounds"] + MAX_ROUNDS))

    def round(self, batch):
        self.params, self.opt, m = self.step(self.params, self.opt, batch)
        return m

    def first_rounds(self, cfg: dict, seed: int, rounds: int) -> dict:
        """The checked rounds, through the window's own step and feed; the
        count totals are built for the window's shapes on the way."""
        losses, d1, acc, bias = [], None, None, []
        for r in range(rounds):
            m = self.round(next(self.feed))
            acc = self.add_counts(self.add_counts(acc, m), m)
            losses.append(float(m["loss_round_mean"]))
            bias.append(np.asarray(ref.flatten(self.params)[ref.BIAS]))
            if r == 0:
                d1 = W.diff_norms(self.params, ref.make_weights(cfg, seed))
        d_all = W.diff_norms(self.params, ref.make_weights(cfg, seed))
        return {"losses": losses, "d1": d1, "d_all": d_all, "bias": bias}


def gaps(prog: dict, reference: dict) -> dict:
    """The numbers that decide ``correct``: the dense kind's three and
    ``bias_gap``."""
    out = _train.gaps(prog, reference)
    out["bias_gap"] = max(
        float(np.linalg.norm(p - r) / np.linalg.norm(r))
        for p, r in zip(prog["bias"], reference["bias"]))
    return out


def follow(cfg: dict, traffic: dict, seed: int, rounds: int,
           quant: str = "f32", keep=None, alter_token: bool = False,
           frozen_bias: bool = False) -> dict:
    """The reference's own first ``rounds`` rounds from the seed: the round
    losses, the per-leaf norm of the first round's change, of the change
    after all rounds, and of the first round's gradients (root of the sum
    over events of their squared norms), and the routing bias after each
    round.  ``frozen_bias`` plants a fault: the bias rule never runs."""
    dec = ref.Decoder(cfg, quant)
    if frozen_bias:
        dec._bias_step = lambda bias, loads: bias
    batch = traffic["n_learners"] * traffic["seqs_per_learner"]
    lrs = ref_decoder.event_lrs(traffic)
    params = ref.make_weights(cfg, seed)
    state = dec.init_state(params)
    losses, gsq, d1, bias = [], {}, None, []
    for r in range(rounds):
        tk, lb = T.lm_batch(cfg["vocab_size"], batch, traffic["seq_len"],
                            seed, r)
        if alter_token and r == 0:
            lb = lb.copy()
            lb[0] = (lb[0] + 1) % cfg["vocab_size"]
        losses.append(dec.round(state, tk, lb, lrs, traffic["momentum"],
                                keep=keep, gsq=gsq if r == 0 else None))
        bias.append(np.asarray(state["run"][ref.BIAS]))
        if r == 0:
            d1 = W.diff_norms(ref.stored(state), params)
    d_all = W.diff_norms(ref.stored(state), params)
    return {"losses": losses, "d1": d1, "d_all": d_all, "bias": bias,
            "grad": {k: float(v) ** 0.5 for k, v in gsq.items()}}


def program_reading(cell: H.Cell, seed: int) -> dict:
    """The checked numbers of the program's first rounds on one seed,
    with no window (``calibrate.py``)."""
    rounds = cell.traffic["check_rounds"]
    prog = Program(cell, seed)
    first = prog.first_rounds(cell.config, seed, rounds)
    del prog
    gc.collect()
    return gaps(first, follow(cell.config, cell.traffic, seed, rounds))


def planted_readings(cell: H.Cell, seed: int) -> dict:
    """The checked numbers of the reference put in the program's place:
    computed in float8 (the control), with half of each event's rows left
    out, with one sequence's labels altered, and with the routing bias
    left unchanged."""
    cfg, tr = cell.config, cell.traffic
    rounds = tr["check_rounds"]
    base = follow(cfg, tr, seed, rounds)
    out = {}
    for name, kw in (("control_fp8", {"quant": "fp8"}),
                     ("fault_half_batch", {"keep": "half"}),
                     ("fault_token", {"alter_token": True}),
                     ("fault_bias_frozen", {"frozen_bias": True})):
        out[name] = gaps(follow(cfg, tr, seed, rounds, **kw), base)
    return out


def run(cell: H.Cell, seed: int, seconds: float, devices,
        trace_dir=None) -> H.Outcome:
    import jax
    from repro import telemetry
    from repro.models import moe
    tr = cell.traffic
    spans = H.Spans()
    compiles = H.CompileCounter()

    t0 = time.perf_counter()
    prog = Program(cell, seed)
    first = prog.first_rounds(cell.config, seed, tr["check_rounds"])
    setup_s = time.perf_counter() - t0

    profile = (jax.profiler.trace(trace_dir) if trace_dir
               else contextlib.nullcontext())
    compiles.active = True
    losses, pending, counts = [], None, None
    with H.QuietGC(), profile:
        if trace_dir:
            # the profiler's first capture of the step holds the host for
            # a second or more: take it on a round outside the window
            with spans.span("bench.trace_warm"):
                jax.block_until_ready(prog.round(next(prog.feed)))
        window = spans.span("bench.window").__enter__()
        tw0 = time.perf_counter()
        while True:
            with spans.span("bench.data_next"):
                batch = next(prog.feed)
            with jax.profiler.StepTraceAnnotation("round",
                                                  step_num=len(losses)):
                with spans.span("bench.dispatch"):
                    m = prog.round(batch)
                    counts = prog.add_counts(counts, m)
            if pending is not None:
                with spans.span("bench.block"):
                    pending.block_until_ready()
            pending = m["loss_round_mean"]
            losses.append(pending)
            if time.perf_counter() - tw0 >= seconds:
                break
        with spans.span("bench.block"):
            jax.block_until_ready((prog.params, prog.opt, pending, counts))
        window_s = time.perf_counter() - tw0
        window.__exit__(None, None, None)
    compiles.active = False
    before = telemetry.counters()
    telemetry.record_step_counts(counts)
    held = telemetry.counters()[moe.HELD_SLOTS] - before.get(
        moe.HELD_SLOTS, 0)
    memory_peak = H.peak_bytes(devices)
    failed = sum(1 for x in losses if not math.isfinite(float(x)))
    rounds = len(losses)
    events = rounds * tr["n_softsync"]
    tokens = rounds * prog.batch * prog.seq
    del prog, batch, m, pending, losses, counts
    gc.collect()

    t_ref = time.perf_counter()
    reference = follow(cell.config, tr, seed, tr["check_rounds"])
    ref_s = time.perf_counter() - t_ref
    got = gaps(first, reference)
    checks = [H.Check(k, v, cell.limits[k]) for k, v in got.items()
              if k in cell.limits]
    checks.append(H.Check("window_nonfinite", float(failed), 0.0))
    checks.append(H.Check("window_compiles", float(compiles.count), 0.0))
    # forward, remat's recompute, and the backward's two products
    passes = 4 if tr["remat"] else 3
    return H.Outcome(
        attempted=rounds, failed=failed, setup_s=setup_s,
        end_to_end={"updates_per_s": events / window_s,
                    "peak_hbm_gib": memory_peak / 2 ** 30},
        checks=checks, memory_peak=memory_peak,
        layer_ctx={"window_s": window_s, "tokens": tokens,
                   "flops_per_token": ref.train_flops_per_token(
                       cell.config, tr["seq_len"]),
                   "chips": len(devices),
                   "expert_flops": held * passes
                   * ref.expert_flops_per_slot(cell.config)},
        notes={"rounds": rounds, "events": events, "held_slots": held,
               "reference_s": ref_s,
               "not_compared": {k: v for k, v in got.items()
                                if k not in cell.limits},
               "losses_program": first["losses"],
               "losses_reference": reference["losses"]})
