"""Traffic kind "train": softsync training rounds of a decoder.

Set-up makes the weights from the seed on the device, jits the program's
softsync step once (``core.distributed.make_train_step``, as
``train.loop.train`` does) and feeds it from the program's pipeline
(``data.pipeline.PrefetchIterator`` over ``make_batch_fn``).  Its first
rounds are the warm-up and the rounds the reference checks; the same step,
state and feed then run the window.  The window counts whole rounds: from
the first dispatch in it to ``block_until_ready`` of the last, with the
host never more than one round ahead of the device.
"""

from __future__ import annotations

import contextlib
import gc
import math
import statistics
import time

import harness as H
import ref_decoder
import weights as W
import counts

# rounds the feed is built for; the window stops long before
MAX_ROUNDS = 100_000
# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of the change checks
QUIET_LEAF = 1e-3


def model_config(cfg: dict):
    from repro.config import ModelConfig
    d = W.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=d["L"], d_model=d["M"],
        n_heads=d["H"], n_kv_heads=d["KV"], d_head=d["Dh"], d_ff=d["F"],
        vocab_size=d["V"], block_pattern=("attn",),
        qkv_bias=W.qkv_bias(cfg), qk_norm=W.qk_norm(cfg),
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
                     optimizer=traffic["optimizer"], seed=seed,
                     remat=traffic["remat"])


def leaf_gap(prog: dict, ref: dict, grad: dict) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖| over the larger of the reference's
    norm of that leaf and of the median leaf; leaves whose reference
    gradient is quiet are left out."""
    g_med = statistics.median(grad.values())
    live = [k for k in ref if grad.get(k, 0.0) >= QUIET_LEAF * g_med]
    med = statistics.median(ref[k] for k in live)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in live)


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers that decide ``correct``."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss,
            "update_gap": leaf_gap(prog["d1"], ref["d1"], ref["grad"]),
            "change_gap": leaf_gap(prog["d_all"], ref["d_all"], ref["grad"])}


class Program:
    """The timed path: one jitted step, its state and its feed."""

    def __init__(self, cell: H.Cell, seed: int):
        import jax
        from repro import optim
        from repro.core import distributed
        from repro.data import pipeline
        from repro.models import model_loss

        cfg, tr = cell.config, cell.traffic
        self.mcfg, self.run = model_config(cfg), run_config(tr, seed)
        self.batch = tr["n_learners"] * tr["seqs_per_learner"]
        self.seq = tr["seq_len"]
        mcfg, run = self.mcfg, self.run

        def loss_fn(p, b, sample_weights=None):
            return model_loss(mcfg, run, p, b, sample_weights=sample_weights)

        self.params = W.make_weights(cfg, seed)
        self.opt = optim.init_state(optim.spec_from_run(run), self.params)
        self.step = jax.jit(distributed.make_train_step(
            run, loss_fn, engine=tr["engine"]))
        self.feed = iter(pipeline.PrefetchIterator(
            pipeline.make_batch_fn(mcfg, self.batch, self.seq, seed=seed),
            tr["check_rounds"] + MAX_ROUNDS))

    def round(self, batch):
        self.params, self.opt, m = self.step(self.params, self.opt, batch)
        return m["loss_round_mean"]

    def first_rounds(self, cfg: dict, seed: int, rounds: int) -> dict:
        """The checked rounds, through the window's own step and feed."""
        theta0 = self.params
        losses, d1 = [], None
        for r in range(rounds):
            losses.append(float(self.round(next(self.feed))))
            if r == 0:
                d1 = W.diff_norms(self.params, theta0)
                del theta0
        d_all = W.diff_norms(self.params, W.make_weights(cfg, seed))
        return {"losses": losses, "d1": d1, "d_all": d_all}


def program_reading(cell: H.Cell, seed: int) -> dict:
    """The checked numbers of the program's first rounds on one seed,
    with no window (``calibrate.py``)."""
    rounds = cell.traffic["check_rounds"]
    prog = Program(cell, seed)
    first = prog.first_rounds(cell.config, seed, rounds)
    del prog
    gc.collect()
    return gaps(first, ref_decoder.follow(cell.config, cell.traffic, seed,
                                          rounds))


def planted_readings(cell: H.Cell, seed: int) -> dict:
    """The checked numbers of the reference put in the program's place:
    computed in float8 (the control), with half of each event's batch
    left out, and with one sequence's labels altered."""
    cfg, tr = cell.config, cell.traffic
    rounds = tr["check_rounds"]
    ref = ref_decoder.follow(cfg, tr, seed, rounds)
    out = {}
    for name, kw in (("control_fp8", {"quant": "fp8"}),
                     ("fault_half_batch", {"keep": "half"}),
                     ("fault_token", {"alter_token": True})):
        out[name] = gaps(ref_decoder.follow(cfg, tr, seed, rounds, **kw),
                         ref)
    return out


def run(cell: H.Cell, seed: int, seconds: float, devices,
        trace_dir=None) -> H.Outcome:
    import jax
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
    losses, pending = [], None
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
                    loss = prog.round(batch)
            if pending is not None:
                with spans.span("bench.block"):
                    pending.block_until_ready()
            pending = loss
            losses.append(loss)
            if time.perf_counter() - tw0 >= seconds:
                break
        with spans.span("bench.block"):
            jax.block_until_ready((prog.params, prog.opt, loss))
        window_s = time.perf_counter() - tw0
        window.__exit__(None, None, None)
    compiles.active = False
    memory_peak = H.peak_bytes(devices)
    failed = sum(1 for x in losses if not math.isfinite(float(x)))
    rounds = len(losses)
    tokens = rounds * prog.batch * prog.seq
    del prog, batch, loss, pending, losses
    gc.collect()

    t_ref = time.perf_counter()
    ref = ref_decoder.follow(cell.config, tr, seed, tr["check_rounds"])
    ref_s = time.perf_counter() - t_ref
    got = gaps(first, ref)
    checks = [H.Check(k, v, cell.limits[k]) for k, v in got.items()
              if k in cell.limits]
    checks.append(H.Check("window_nonfinite", float(failed), 0.0))
    checks.append(H.Check("window_compiles", float(compiles.count), 0.0))
    fpt = counts.train_flops_per_token(cell.config, tr["seq_len"])
    return H.Outcome(
        attempted=rounds, failed=failed, setup_s=setup_s,
        end_to_end={"tokens_per_s": tokens / window_s,
                    "peak_hbm_gib": memory_peak / 2 ** 30},
        checks=checks, memory_peak=memory_peak,
        layer_ctx={"window_s": window_s, "tokens": tokens,
                   "flops_per_token": fpt, "chips": len(devices),
                   "input_wait_s": spans.seconds.get("bench.data_next", 0.0)},
        notes={"rounds": rounds, "reference_s": ref_s,
               "not_compared": {k: v for k, v in got.items()
                                if k not in cell.limits},
               "losses_program": first["losses"],
               "losses_reference": ref["losses"],
               "d1_program": first["d1"], "d1_reference": ref["d1"]})
