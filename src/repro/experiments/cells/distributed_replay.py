"""Cell ``distributed`` — distributed-replay throughput:
``placement="spmd"`` on an emulated 8-device host (DESIGN.md §13).

The cell replays the calibrated adv workload — the what-if quadratic at
multi-million D under ``duration_model="calibrated:adv:300mb"`` — with the
PS ring sharded over S ∈ {1, 2, 4} "ps" devices, and reports updates/s per
S plus the S=4/S=1 scaling ratio.  The what-if body is the per-shard-
parallel showcase: closed-form gradients are shard-local, so each device
touches only its (K, ⌈D/S⌉) ring slice and per-event work drops ∝ 1/S.
Whether that shows up as *wall-clock* scaling depends on the host actually
having cores for the emulated devices to run on (``cpu_count`` rides in
the results; a 1-core container timeshares all S devices).  A
``placement="single"`` row at S=4 anchors the comparison.

Runs its measurement in a **subprocess** pinned to the CPU platform, so
the 8-device XLA flag applies before jax initializes (the dry-run trick,
``launch/dryrun.py``) — the parent process may already hold a 1-device jax,
or the chip.  The module is its own
subprocess entry point (``python -m repro.experiments.cells.\
distributed_replay --inner <json>``) so the child needs only ``src`` on
PYTHONPATH.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.experiments.registry import (Cell, Claim, emit, register_cell,
                                        repo_root)

DEVICES = 8
SHARDS = (1, 2, 4)
_MARKER = "DISTRIBUTED_REPLAY_RESULT:"


def _inner(payload: dict) -> dict:
    """Runs inside the 8-device subprocess: measure every cell."""
    from repro.launch.mesh import ensure_host_devices
    ensure_host_devices(payload["devices"])
    import jax

    from repro.config import RunConfig
    from repro.core.engine import replay
    from repro.core.trace import schedule_cached
    from repro.experiments.problems import QuadraticProblem

    updates = payload["updates"]
    repeats = payload["repeats"]
    prob = QuadraticProblem(d=payload["d"])

    def measure_one(cfg) -> float:
        trace = schedule_cached(cfg, updates)

        def once():
            res = replay(trace, cfg, grad_fn=prob.grad_fn,
                         init_params=prob.init,
                         batch_fn=prob.batch_fn_for(cfg.minibatch),
                         flat_grad=prob.flat_grad)
            jax.block_until_ready(res.params["w"])
            return res

        once()                                    # compile + warm
        best = min(_timed(once) for _ in range(repeats))
        return updates / best

    rows = {}
    for s in payload["shards"]:
        cfg = RunConfig(protocol="softsync", n_softsync=4, n_learners=16,
                        minibatch=4, base_lr=0.05,
                        lr_policy="staleness_inverse", optimizer="momentum",
                        duration_model="calibrated:adv:300mb", shards=s,
                        placement="spmd", seed=0)
        rows[f"spmd_s{s}"] = measure_one(cfg)
    single = RunConfig(protocol="softsync", n_softsync=4, n_learners=16,
                       minibatch=4, base_lr=0.05,
                       lr_policy="staleness_inverse", optimizer="momentum",
                       duration_model="calibrated:adv:300mb",
                       shards=max(payload["shards"]), seed=0)
    rows["single_s%d" % max(payload["shards"])] = measure_one(single)

    s_lo, s_hi = min(payload["shards"]), max(payload["shards"])
    # per-"ps"-device ring residency: K rows of the ⌈D/S⌉ shard slice —
    # the ∝ 1/S per-device working set that wall-clock scaling rides on
    trace = schedule_cached(
        RunConfig(protocol="softsync", n_softsync=4, n_learners=16,
                  minibatch=4, base_lr=0.05,
                  lr_policy="staleness_inverse", optimizer="momentum",
                  duration_model="calibrated:adv:300mb", seed=0), updates)
    K = trace.max_staleness + 1
    ring_bytes = {f"spmd_s{s}": K * (-(-payload["d"] // s)) * 4
                  for s in payload["shards"]}
    return {
        "platform": jax.default_backend(),
        "devices": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "d": payload["d"],
        "updates": updates,
        "updates_per_s": rows,
        "per_device_ring_bytes": ring_bytes,
        "scaling_s%d_over_s%d" % (s_hi, s_lo):
            rows[f"spmd_s{s_hi}"] / rows[f"spmd_s{s_lo}"],
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure(updates: int = 48, d: int = 2_000_000, repeats: int = 3,
            shards=SHARDS, devices: int = DEVICES) -> dict:
    """Spawn the 8-device subprocess and return its measurement dict."""
    payload = {"devices": devices, "updates": updates, "d": d,
               "repeats": repeats, "shards": list(shards)}
    env = dict(os.environ)
    # the emulated devices are CPU devices: pin the child to the CPU so it
    # never contends for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={devices}"]).strip()
    root = repo_root()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(root, "src"),
                    env.get("PYTHONPATH", "")] if p)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.experiments.cells.distributed_replay",
         "--inner", json.dumps(payload)],
        capture_output=True, text=True, env=env, cwd=root, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"distributed_replay subprocess failed:\n{proc.stdout}\n"
            f"{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARKER):
            return json.loads(line[len(_MARKER):])
    raise RuntimeError(f"no result marker in subprocess output:\n"
                       f"{proc.stdout}\n{proc.stderr}")


def compute(updates: int = 48, d: int = 2_000_000, repeats: int = 3):
    out = measure(updates=updates, d=d, repeats=repeats)
    for key, ups in sorted(out["updates_per_s"].items()):
        emit(f"distributed_replay/{key}", f"{ups:.1f}up/s",
             f"D={d} updates={updates} devices={out['devices']}")
    s_lo, s_hi = min(SHARDS), max(SHARDS)
    ratio_key = "scaling_s%d_over_s%d" % (s_hi, s_lo)
    emit(f"distributed_replay/{ratio_key}", f"{out[ratio_key]:.2f}x",
         f"cpu_count={out['cpu_count']} (wall-clock scaling needs cores "
         f"for the emulated devices)")
    return [], out


if __name__ != "__main__":
    # running as the --inner subprocess entry point re-executes this module
    # under __main__ AFTER the cells package already imported (and
    # registered) it — don't register the cell twice
    register_cell(Cell(
        name="distributed", result="distributed_replay",
        title="SPMD distributed replay on the emulated device mesh",
        compute=compute,
        claims=(
            Claim("emulated_mesh_has_8_devices",
                  lambda d: d["devices"] == DEVICES),
            Claim("all_shard_counts_measured",
                  lambda d: all(f"spmd_s{s}" in d["updates_per_s"]
                                for s in SHARDS)),
        ),
        params={"updates": 48, "d": 2_000_000, "repeats": 3},
        quick_params={"updates": 32, "d": 1_000_000, "repeats": 2}))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", default=None,
                    help="(internal) JSON payload; run the measurement in "
                         "this process and print the marker line")
    args = ap.parse_args()
    if args.inner is None:
        ap.error("--inner payload required (use the campaign CLI to run "
                 "the cell)")
    result = _inner(json.loads(args.inner))
    print(_MARKER + json.dumps(result, default=float))
