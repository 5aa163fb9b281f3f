"""Smoke run of the system's main path on a TPU, in one process.

    python chip_smoke.py              # one chip: phases A, A2 and B
    python chip_smoke.py --chips 4    # four chips: phase S only

A   Trace replay of the MLP teacher task through ``repro.experiments.run``
    (softsync λ=16 μ=4, momentum, seed 17 — the bench-guard configuration):
    the Pallas megakernel (``ring_impl="auto"``) against its fused jnp twin
    on the same trace, combine mode on fp32 and bf16 rings plus one
    ``per_gradient`` run (sequential mode).
A2  What-if replay of a D = 1e8 diagonal quadratic on a bf16 ring with
    momentum (the in-kernel-gradient megakernel), Pallas against the twin.
B   Three softsync rounds of qwen2-1.5b at its published widths (random
    weights from a seed, sgd, 8×256 tokens, remat) through
    ``repro.train.loop.train``, the trainer ``repro.launch.train`` drives.
S   The sharded parameter server (``placement="spmd"``) across four chips:
    one D = 2^26 trace under ``placement="single"`` and ``"spmd"``, then a
    what-if replay at qwen2-1.5b's parameter count, state built per shard.

Each phase prints one JSON line.  Its ``smoke_s`` fields are wall-clock
seconds of one cold run, compilation included: smoke timings, not
benchmarks.  The last line is ``{"ok": true, "device": {...}}``.  The script
exits non-zero, with no such line, if JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
REL_TOL = 1e-5          # kernel vs twin, and spmd vs single (tests/test_spmd)


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: {msg}")


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _kernel_count() -> int:
    from repro.kernels import replay_ring
    return replay_ring.pallas_dispatches


def _kernel_ran(before: int, what: str) -> None:
    """The Pallas replay kernel was built since ``before``, compiled for the
    chip (not interpret mode)."""
    from repro.kernels import replay_ring
    if replay_ring.pallas_dispatches <= before:
        _fail(f"{what}: no Pallas replay kernel was built")
    if replay_ring.last_interpret is not False:
        _fail(f"{what}: the Pallas kernel ran in interpret mode")


def _max_abs(tree) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32))))
               for x in jax.tree.leaves(tree))


def _agreement(what: str, got, want) -> dict:
    """Max |got − want| over all parameters, and that over max |want|."""
    import jax
    import jax.numpy as jnp
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    rel = diff / max(_max_abs(want), 1e-30)
    if not rel <= REL_TOL:
        _fail(f"{what}: max |Δw| {diff} is {rel} of max |w| "
              f"(limit {REL_TOL})")
    return {"max_abs_diff": diff, "rel_diff": rel}


def _timed_run(spec):
    from repro.experiments import run
    t0 = time.perf_counter()
    res = run(spec)
    return res, time.perf_counter() - t0


def _peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def phase_a(steps: int = 48) -> None:
    from repro.config import RunConfig
    from repro.experiments import ExperimentSpec

    base = RunConfig(protocol="softsync", n_softsync=1, n_learners=16,
                     minibatch=4, base_lr=0.05,
                     lr_policy="staleness_inverse", optimizer="momentum",
                     seed=17)
    variants = {"combine_fp32": base,
                "combine_bf16": base.replace(ring_dtype="bf16"),
                "sequential_fp32": base.replace(lr_policy="per_gradient")}
    for name, cfg in variants.items():
        before = _kernel_count()
        kernel, t_kernel = _timed_run(ExperimentSpec(
            run=cfg, problem="mlp_teacher", steps=steps))
        _kernel_ran(before, f"A/{name}")
        twin, t_twin = _timed_run(ExperimentSpec(
            run=cfg.replace(ring_impl="fused"), problem="mlp_teacher",
            steps=steps))
        if not math.isfinite(kernel.metrics["test_error"]):
            _fail(f"A/{name}: test error {kernel.metrics['test_error']}")
        _emit({"phase": "A", "variant": name, "updates": steps,
               "mode": "sequential" if "sequential" in name else "combine",
               "ring_K": kernel.staleness["ring_buffer_K"],
               **_agreement(f"A/{name}", kernel.params, twin.params),
               "test_error_pallas": kernel.metrics["test_error"],
               "test_error_fused": twin.metrics["test_error"],
               "smoke_s_pallas": t_kernel, "smoke_s_fused": t_twin})


def phase_a2(d: int = 100_000_000, lam: int = 128, steps: int = 8) -> None:
    import jax

    from repro.config import RunConfig
    from repro.experiments import ExperimentSpec

    cfg = RunConfig(protocol="softsync", n_softsync=1, n_learners=lam,
                    minibatch=1, base_lr=0.01, optimizer="momentum", seed=5,
                    ring_dtype="bf16")
    spec = ExperimentSpec(run=cfg, problem="quadratic_whatif",
                          problem_args=(("d", d),), steps=steps)
    before = _kernel_count()
    kernel, t_kernel = _timed_run(spec)
    _kernel_ran(before, "A2")
    K = kernel.staleness["ring_buffer_K"]
    if K < 2:
        _fail(f"A2: ring K = {K}; the what-if kernel needs K >= 2")
    twin, t_twin = _timed_run(ExperimentSpec(
        run=cfg.replace(ring_impl="fused"), problem="quadratic_whatif",
        problem_args=spec.problem_args, steps=steps))
    loss = kernel.metrics["loss"]
    if not math.isfinite(loss):
        _fail(f"A2: loss is {loss}")
    _emit({"phase": "A2", "d": d, "c": lam, "ring_K": K, "updates": steps,
           "ring_dtype": "bf16",
           **_agreement("A2", kernel.params, twin.params),
           "loss_pallas": loss, "loss_fused": twin.metrics["loss"],
           "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
           "smoke_s_pallas": t_kernel, "smoke_s_fused": t_twin})


def phase_b(arch: str = "qwen2-1.5b", rounds: int = 3, batch: int = 8,
            seq: int = 256) -> None:
    import jax

    from repro.config import RunConfig
    from repro.configs import get_config
    from repro.train.loop import train

    cfg = get_config(arch)
    run = RunConfig(protocol="softsync", n_softsync=4, n_learners=8,
                    minibatch=max(1, batch // 8), base_lr=0.01,
                    lr_policy="staleness_inverse", optimizer="sgd", seed=0,
                    remat=True, attn_q_chunk=seq, attn_kv_chunk=seq)
    t0 = time.perf_counter()
    res = train(cfg, run, steps=rounds, batch=batch, seq=seq, eval_every=1)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in res.history]
    if len(losses) != rounds or not all(math.isfinite(x) for x in losses):
        _fail(f"B: losses {losses}")
    _emit({"phase": "B", "arch": arch,
           "params": int(sum(x.size for x in jax.tree.leaves(res.params))),
           "d_model": cfg.d_model, "vocab": cfg.vocab_size,
           "rounds": rounds, "batch": batch, "seq": seq, "losses": losses,
           "smoke_s_rounds": res.wallclock, "smoke_s_total": wall,
           "peak_bytes_in_use": _peak_bytes(jax.devices()[0])})


def phase_s(chips: int = 4, d_compare: int = 1 << 26,
            arch: str = "qwen2_1_5b", steps: int = 6) -> None:
    import jax

    from repro.config import RunConfig
    from repro.configs import get_config
    from repro.experiments import ExperimentSpec
    from repro.kernels.replay_ring import padded_width

    if jax.device_count() < chips:
        _fail(f"S: needs {chips} chips, found {jax.device_count()}")
    base = RunConfig(protocol="softsync", n_softsync=1, n_learners=chips,
                     minibatch=1, base_lr=0.01, optimizer="momentum", seed=5,
                     shards=chips)
    compare = ExperimentSpec(run=base, problem="quadratic_whatif",
                             problem_args=(("d", d_compare),), steps=steps)
    before = _kernel_count()
    single, t_single = _timed_run(compare)
    _kernel_ran(before, "S/single")
    before = _kernel_count()
    spmd, t_spmd = _timed_run(ExperimentSpec(
        run=base.replace(placement="spmd"), problem="quadratic_whatif",
        problem_args=compare.problem_args, steps=steps))
    _kernel_ran(before, "S/spmd")
    _emit({"phase": "S", "check": "spmd_vs_single", "d": d_compare,
           "shards": chips, "updates": steps,
           **_agreement("S/spmd_vs_single", spmd.params, single.params),
           "loss_single": single.metrics["loss"],
           "loss_spmd": spmd.metrics["loss"],
           "smoke_s_single": t_single, "smoke_s_spmd": t_spmd})

    # full size: per device, a K-row bf16 ring slice, fp32 state and
    # residue rows, fp32 a and w*, and the init block — all of width ~D/S
    d = int(get_config(arch).param_count())
    cfg = base.replace(placement="spmd", ring_dtype="bf16")
    spec = ExperimentSpec(run=cfg, problem="quadratic_whatif",
                          problem_args=(("arch", arch), ("shards", chips)),
                          steps=steps)
    before = _kernel_count()
    res, t_full = _timed_run(spec)
    _kernel_ran(before, "S/full")
    K = res.staleness["ring_buffer_K"]
    wl = padded_width(-(-d // chips))
    reckoned = wl * (2 * K + 4 + 4 + 8) + 4 * (d // chips)
    peaks = [_peak_bytes(dev) for dev in jax.devices()[:chips]]
    if max(peaks) > 1.5 * min(peaks):
        _fail(f"S/full: per-device peaks {peaks} differ by more than 1.5x")
    if not math.isfinite(res.metrics["loss"]):
        _fail(f"S/full: loss is {res.metrics['loss']}")
    _emit({"phase": "S", "check": "full_size_whatif", "arch": arch, "d": d,
           "shards": chips, "ring_K": K, "c": base.n_learners,
           "updates": steps, "ring_dtype": "bf16",
           "reckoned_bytes_per_device": reckoned,
           "peak_bytes_in_use": peaks, "loss": res.metrics["loss"],
           "smoke_s": t_full})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded parameter-server phase")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail(f"no src/repro package next to {__file__}")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found: JAX reports platform "
              f"{devices[0].platform!r}")

    from repro.launch.compile_cache import enable_compile_cache
    _emit({"phase": "setup", "compile_cache": enable_compile_cache(),
           "devices": len(devices), "kind": devices[0].device_kind})
    if args.chips == 4:
        phase_s(chips=4)
    else:
        phase_a()
        phase_a2()
        phase_b()
    _emit({"ok": True, "device": {"platform": devices[0].platform,
                                  "kind": devices[0].device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
