"""Cell ``bench_guard`` — CI perf-trajectory guard: tiny-shape engine +
sweep benchmarks vs a checked-in floor (``benchmarks/ci_floor.json``).

    PYTHONPATH=src python -m benchmarks.bench_guard

Runs the ``sim_engine`` single-run cell (legacy vs compiled replay) and the
``sweep_batched_vs_sequential`` cell on a tiny shape (≲1 min), then fails
(exit 1) if any guarded metric regresses more than ``tolerance`` (default
30%) below its floor — the regression gate for the perf the compiled
engine and the batched sweep driver earned (DESIGN.md §4/§5).

Guarded metrics:

* ``compiled_updates_per_s``  — absolute compiled-replay throughput.  The
  floor is deliberately far below the dev-machine measurement (CI runners
  vary ~2-3×); this catches collapse-scale regressions, not noise.
* ``engine_speedup``          — compiled vs legacy on the same trace.
  Machine-relative, so the floor can sit much closer to the measurement.
* ``batched_sweep_speedup``   — one vmapped program vs sequential replays
  for a shape-compatible grid cell.  Also machine-relative.
* ``elastic_schedule_updates_per_s`` — host-side throughput of the
  membership-resolution pass in ``core/trace.schedule`` on a churny
  timeline (crash-restarts + leaves).  Absolute, wide margin like the
  compiled throughput: catches the schedule pass collapsing (e.g. the
  threshold refresh going quadratic), not runner noise.
* ``distributed_replay_updates_per_s`` — ``placement="spmd"`` what-if
  throughput at S=4 on the emulated 8-device host (DESIGN.md §13),
  measured in a subprocess so the device-count flag lands before jax
  initializes.  Absolute with a wide margin: guards the SPMD path
  collapsing (a stray host sync, a collective in the shard-local what-if
  body), not the S=4/S=1 wall-clock ratio — that needs real cores and is
  reported, unguarded, by the ``distributed`` cell.
* ``serving_requests_per_s`` — serving-lane throughput (DESIGN.md §14).
  Absolute, wide margin.

Fresh measurements land in ``benchmarks/results/bench_guard.json`` (the CI
job uploads it as a workflow artifact).  To demonstrate the gate trips:

    PYTHONPATH=src python -m benchmarks.bench_guard --floor-scale 100

multiplies every floor 100× and must exit 1.  ``--write-floor`` rewrites
the floor file from fresh measurements × per-metric safety margins (for
maintainers after an intentional perf change).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from repro.experiments.registry import (Cell, Claim, emit, register_cell,
                                        repo_root)

FLOOR_PATH = os.path.join(repo_root(), "benchmarks", "ci_floor.json")

# floor = measured × margin when --write-floor regenerates the file.
# Absolute throughput gets a wide margin (unknown CI hardware); ratios are
# machine-relative and stay tight.
FLOOR_MARGINS = {
    "compiled_updates_per_s": 0.25,
    "engine_speedup": 0.55,
    "batched_sweep_speedup": 0.55,
    "elastic_schedule_updates_per_s": 0.25,
    # absolute spmd throughput on the emulated mesh: wide margin, same
    # rationale as compiled_updates_per_s (CI hardware + core count vary)
    "distributed_replay_updates_per_s": 0.25,
    # serving-lane throughput (snapshot capture + chunked request eval,
    # DESIGN.md §14): absolute, wide margin like the other throughputs —
    # catches the lane collapsing (a per-request recompile, the snapshot
    # carry forcing a host sync), not runner noise
    "serving_requests_per_s": 0.25,
}


def _bench_elastic_schedule(updates: int = 600, repeats: int = 3) -> dict:
    """Host-side wall clock of ``schedule()`` with a churny membership
    timeline (the membership-resolution pass: event interleaving, dropped
    pushes, λ(t) threshold refreshes, mask assembly).  Deliberately calls
    the UNCACHED ``schedule`` — ``schedule_cached`` would return the same
    trace object after the first repeat and time a dict lookup."""
    import time

    from repro.config import RunConfig
    from repro.core.trace import schedule
    from repro.membership import MembershipTimeline

    churn = MembershipTimeline(tuple(
        [(2.0 + 1.5 * i, i % 12, "crash") for i in range(8)]
        + [(3.0 + 1.5 * i, i % 12, "join") for i in range(8)]
        + [(30.0, 13, "leave"), (45.0, 13, "join")]))
    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=16,
                    minibatch=4, seed=17, membership=churn)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        trace = schedule(cfg, updates)
        best = min(best, time.perf_counter() - t0)
    assert trace.valid is not None          # the elastic path actually ran
    return {"updates": updates, "seconds": best,
            "updates_per_s": updates / best}


def measure() -> dict:
    """The tiny-shape measurement cell (~1 min on a CI runner)."""
    from repro.config import RunConfig
    from repro.experiments.cells.distributed_replay import \
        measure as _measure_dist
    from repro.experiments.cells.sim_engine_bench import (_bench_one,
                                                          _bench_sweep)
    from repro.experiments.cells.train_while_serve import \
        measure as _measure_serve

    cfg = RunConfig(protocol="softsync", n_softsync=1, n_learners=16,
                    minibatch=4, base_lr=0.05,
                    lr_policy="staleness_inverse", optimizer="momentum",
                    seed=17)
    row = _bench_one(cfg, updates=48, repeats=3)
    sweep = _bench_sweep(updates=30, lam=16, seeds=3, repeats=3)
    elastic = _bench_elastic_schedule()
    dist = _measure_dist(updates=32, d=1_000_000, repeats=2, shards=(1, 4))
    serve = _measure_serve(updates=32, requests=512, repeats=2)
    return {
        "metrics": {
            "compiled_updates_per_s": row["compiled_updates_per_s"],
            "engine_speedup": row["speedup"],
            "batched_sweep_speedup": sweep["speedup"],
            "elastic_schedule_updates_per_s": elastic["updates_per_s"],
            "distributed_replay_updates_per_s":
                dist["updates_per_s"]["spmd_s4"],
            "serving_requests_per_s": serve["requests_per_s"],
        },
        "engine_cell": row,
        "sweep_cell": sweep,
        "elastic_schedule_cell": elastic,
        "distributed_replay_cell": dist,
        "serving_cell": serve,
    }


def check(metrics: dict, floor: dict, floor_scale: float = 1.0) -> list:
    """Each guarded metric vs floor·scale·(1 − tolerance); returns rows."""
    tol = float(floor.get("tolerance", 0.30))
    rows = []
    for name, value in metrics.items():
        bound = floor["floors"][name] * floor_scale * (1.0 - tol)
        rows.append({"metric": name, "measured": value,
                     "floor": floor["floors"][name] * floor_scale,
                     "min_allowed": bound, "ok": value >= bound})
    return rows


def compute(floor_scale: float = 1.0, floor_path: str = None):
    measured = measure()
    metrics = measured["metrics"]
    for name, value in metrics.items():
        emit(f"bench_guard/{name}", f"{value:.2f}")
    with open(floor_path or FLOOR_PATH) as f:
        floor = json.load(f)
    rows = check(metrics, floor, floor_scale)
    for r in rows:
        status = "ok" if r["ok"] else "REGRESSED"
        print(f"[bench-guard] {r['metric']}: measured={r['measured']:.2f} "
              f"min_allowed={r['min_allowed']:.2f} -> {status}")
    return [], {"measured": measured, "floor": floor,
                "floor_scale": floor_scale, "checks": rows}


def main(argv=None) -> int:
    """CLI gate (``python -m benchmarks.bench_guard``): run the cell, write
    its envelope, exit 1 on any floor trip."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--floor", default=FLOOR_PATH,
                    help="floor file (default benchmarks/ci_floor.json)")
    ap.add_argument("--floor-scale", type=float, default=1.0,
                    help="multiply floors (e.g. 100 to prove the gate "
                         "trips; see module docstring)")
    ap.add_argument("--write-floor", action="store_true",
                    help="rewrite the floor file from fresh measurements "
                         "x safety margins")
    args = ap.parse_args(argv)

    if args.write_floor:
        measured = measure()
        metrics = measured["metrics"]
        floor = {
            "tolerance": 0.30,
            "floors": {k: round(v * FLOOR_MARGINS[k], 3)
                       for k, v in metrics.items()},
            "note": "bench-guard floors: fail if a metric drops >30% below "
                    "its floor. Absolute throughput floors carry a wide "
                    "margin vs the dev-machine measurement (CI hardware "
                    "varies); speedup ratios are machine-relative. "
                    "Regenerate: python -m benchmarks.bench_guard "
                    "--write-floor",
        }
        with open(args.floor, "w") as f:
            json.dump(floor, f, indent=1)
            f.write("\n")
        print(f"[bench-guard] wrote floors to {args.floor}")

    # only non-default params enter the cell hash: the default invocation
    # stays content-addressed identically across machines (an absolute
    # --floor path would poison the hash)
    params = {"floor_scale": args.floor_scale}
    if args.floor != FLOOR_PATH:
        params["floor_path"] = args.floor

    from repro.experiments.campaign import run_cell
    derived = run_cell("bench_guard", params=params, force=True)
    failed = [r for r in derived["checks"] if not r["ok"]]
    if failed:
        print(f"[bench-guard] FAIL: {len(failed)} metric(s) below the "
              f"floor - see benchmarks/results/bench_guard.json",
              file=sys.stderr)
        return 1
    print("[bench-guard] all perf floors hold")
    return 0


register_cell(Cell(
    name="bench_guard", result="bench_guard",
    title="CI perf floors: engine/sweep/schedule/megakernel/spmd/serving",
    compute=compute, deps=("sim_engine",), skip_quick=True,
    claims=(
        Claim("all_perf_floors_hold",
              lambda d: all(r["ok"] for r in d["checks"]),
              detail=lambda d: " ".join(r["metric"] for r in d["checks"]
                                        if not r["ok"])),
    ),
    params={"floor_scale": 1.0}))
