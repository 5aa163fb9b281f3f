"""Traffic kind "whatif": the what-if replay of a parameter-server shard.

Set-up schedules the arrival trace on the host (``core.trace.schedule``),
makes the start weights on the device from the seed, and warms the
program's replay (``core.engine.replay`` with closed-form gradients, which
runs the Pallas ``replay_ring`` what-if kernel on a TPU) through two
segments.  The window is a second ``replay`` call: its scan runs in
fixed-length segments (``eval_every``), and the program hands the master
weights to the harness's callback after each.  The clock starts when the
first segment is done (the carry is built by then) and the call is
stopped at the first segment end past ``--seconds``; only whole segments
count.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

import counts
import harness as H
import ref_whatif
import weights as W


class _Stop(Exception):
    def __init__(self, w, segments):
        super().__init__("window closed")
        self.w, self.segments = w, segments


def run_config(traffic: dict):
    """The replay's settings.  The arrival schedule's seed is the mix's
    own, not the run's: it sets the ring depth K, and so the work."""
    from repro.config import RunConfig
    return RunConfig(protocol=traffic["protocol"],
                     n_softsync=traffic["n_softsync"],
                     n_learners=traffic["n_learners"], minibatch=1,
                     optimizer="momentum", momentum=traffic["momentum"],
                     base_lr=traffic["base_lr"],
                     lr_policy=traffic["lr_policy"],
                     ring_dtype=traffic["ring_dtype"],
                     seed=traffic["schedule_seed"],
                     duration_model=traffic["duration_model"])


def event_lr(traffic: dict) -> float:
    """The staleness-inverse rate α₀/max(1, n) of n-softsync."""
    if traffic["lr_policy"] != "staleness_inverse":
        raise ValueError("the what-if reference knows staleness_inverse only")
    return float(traffic["base_lr"]) / max(1.0, float(traffic["n_softsync"]))


def weight_gap(w_prog: np.ndarray, w_ref: np.ndarray) -> float:
    """Root-mean-square gap of the sampled master weights over their
    root-mean-square.  Not the worst column: where the program and the
    reference differ by one float32 rounding, a snapshot now and then
    rounds to the neighbouring bfloat16 value on one side only, and that
    column then carries a gap of a bfloat16 step for a while."""
    diff = np.asarray(w_prog, np.float64) - np.asarray(w_ref, np.float64)
    return float(np.sqrt(np.mean(diff ** 2) / np.mean(
        np.asarray(w_ref, np.float64) ** 2)))


def columns(seed: int, d: int, n: int) -> np.ndarray:
    """The n columns the check reads, drawn from the seed (a fixed count,
    so one compiled gather and reference serve every seed)."""
    rng = np.random.default_rng([int(seed), 0x57A7])
    return np.sort(rng.integers(0, d, n))


def width(cfg: dict, traffic: dict) -> int:
    total, shards = W.param_count(cfg), traffic["ps_shards"]
    if total % shards:
        raise ValueError(f"{total} weights do not split into {shards} shards")
    return total // shards


def program_reading(cell: H.Cell, seed: int, seconds: float,
                    devices) -> dict:
    """The checked number of one short run (``calibrate.py``)."""
    out = run(cell, seed, seconds, devices)
    return {c.name: c.value for c in out.checks if c.name == "weight_gap"}


def planted_readings(cell: H.Cell, seed: int, events: int) -> dict:
    """The checked number of the reference put in the program's place
    over ``events`` events: kept in bfloat16 (the control), and averaging
    half of each event's gradients."""
    from repro.core import trace as trace_mod
    tr = cell.traffic
    d = width(cell.config, tr)
    trace = trace_mod.schedule(run_config(tr), events)
    cols = columns(seed, d, tr["check_columns"])
    lrs = np.full((events,), event_lr(tr), np.float32)

    def replay(**kw):
        return ref_whatif.replay_columns(trace.pulled_ts, lrs, cols, seed,
                                         tr["problem_seed"], tr["momentum"],
                                         **kw)
    ref = replay()
    return {"control_bf16": {"weight_gap": weight_gap(replay(quant="bf16"),
                                                      ref)},
            "fault_half_batch": {"weight_gap": weight_gap(
                replay(half=True), ref)}}


def run(cell: H.Cell, seed: int, seconds: float, devices,
        trace_dir=None) -> H.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    from repro.core import trace as trace_mod
    from repro.kernels import replay_ring

    tr = cell.traffic
    spans = H.Spans()
    compiles = H.CompileCounter()
    d = width(cell.config, tr)
    run_cfg = run_config(tr)
    on_tpu = devices[0].platform == "tpu"

    t0 = time.perf_counter()
    with spans.span("bench.schedule"):
        trace = trace_mod.schedule(run_cfg, tr["events"])
    K = trace.max_staleness + 1
    seg = K * -(-tr["segment"] // K)       # ring rows line up per segment
    init = {"w": ref_whatif.make_init(seed, d)}
    flat_grad = ("quadratic", ref_whatif.coeffs_fn(tr["problem_seed"]))

    def replay(callback):
        try:
            engine.replay(trace, run_cfg, init_params=init,
                          flat_grad=flat_grad, eval_fn=callback,
                          eval_every=seg)
        except _Stop as stop:
            # the traceback holds the replay's frames, and with them its
            # carry: drop it, so that the device memory goes now
            stop.__traceback__ = None
            out = stop
        else:
            raise RuntimeError(f"the trace's {tr['events']} events ran "
                               f"out before the window closed")
        gc.collect()
        return out

    warm = {"n": 0}

    def warm_cb(params):
        warm["n"] += 1
        params["w"].block_until_ready()
        if warm["n"] == 2:
            raise _Stop(None, warm["n"])
        return {}

    replay(warm_cb)
    if on_tpu and not (replay_ring.pallas_dispatches > 0
                       and replay_ring.last_interpret is False):
        raise SystemExit("bench: the replay kernel was not compiled for "
                         "the chip")
    setup_s = time.perf_counter() - t0

    profile = (jax.profiler.trace(trace_dir) if trace_dir
               else contextlib.nullcontext())
    state = {"n": 0, "t0": None, "n0": 0, "t1": None, "ann": None}

    def window_cb(params):
        with spans.span("bench.block"):
            params["w"].block_until_ready()
        now = time.perf_counter()
        state["n"] += 1
        if state["t0"] is None:
            state["t0"], state["n0"] = now, state["n"]
            state["ann"] = spans.span("bench.window").__enter__()
            compiles.active = True
        elif now - state["t0"] >= seconds:
            compiles.active = False
            state["ann"].__exit__(None, None, None)
            state["t1"] = now
            raise _Stop(params["w"], state["n"])
        return {}

    with H.QuietGC(), profile, spans.span("bench.replay"):
        stop = replay(window_cb)
    window_s = state["t1"] - state["t0"]
    events = (stop.segments - state["n0"]) * seg
    done = stop.segments * seg
    memory_peak = H.peak_bytes(devices)

    cols = columns(seed, d, tr["check_columns"])
    w_prog = np.asarray(stop.w[jnp.asarray(cols)])
    del stop, init
    gc.collect()

    t_ref = time.perf_counter()
    lrs = np.full((done,), event_lr(tr), np.float32)
    w_ref = ref_whatif.replay_columns(trace.pulled_ts[:done], lrs, cols,
                                      seed, tr["problem_seed"],
                                      tr["momentum"])
    ref_s = time.perf_counter() - t_ref
    gap = weight_gap(w_prog, w_ref)
    checks = [H.Check("weight_gap", gap, cell.limits["weight_gap"]),
              H.Check("window_compiles", float(compiles.count), 0.0)]

    kernel_width = replay_ring.padded_width(d)
    event_bytes = counts.whatif_event_bytes(
        kernel_width, trace.c, 2 if tr["ring_dtype"] == "bf16" else 4,
        stateful=True, residue=tr["ring_dtype"] == "bf16")
    return H.Outcome(
        attempted=events, failed=0, setup_s=setup_s,
        end_to_end={"updates_per_s": events / window_s,
                    "peak_hbm_gib": memory_peak / 2 ** 30},
        checks=checks, memory_peak=memory_peak,
        layer_ctx={"window_s": window_s, "events": events,
                   "event_bytes": event_bytes, "chips": len(devices)},
        notes={"ring_K": K, "segment": seg, "events_replayed": done,
               "c": trace.c, "d": d, "reference_s": ref_s,
               "schedule_s": spans.seconds.get("bench.schedule", 0.0)})
