"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix,
limits and per-layer readers are found by the names in ``BENCHMARK.json``
(see ``bench/harness.py``).  The run loads, warms up, measures for
``--seconds`` seconds, checks what the timed path produced against the
plain reference, and prints one JSON line last on standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The numbers
compared with the reference close standard error, each beside its limit.
It exits non-zero, with no result, when JAX finds no TPU or fewer chips
than the cell needs, or when the program is not beside the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _prepare() -> None:
    """The program beside the benchmark."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program at {src}/repro")
    sys.path.insert(0, src)
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)


def _enable_cache() -> None:
    """JAX's compile cache at a fixed path inside the checkout, whatever
    directory the environment names, so that two checkouts share nothing;
    every program is cached, however quick to compile or small."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer_metrics(cell, ctx: dict) -> dict:
    import harness as H
    out = {}
    for m in cell.per_layer:
        v = H.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = v
    return out


def execute(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """Everything after the look for the chip: the run, its checks and
    its result line."""
    import harness as H
    import peaks as P
    import tracereduce

    kind = H.kind_module(cell.traffic["kind"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        outcome = kind.run(cell, seed, seconds, devices, trace_dir=trace_dir)
        red = tracereduce.reduce_trace(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = {c.name: c.value for c in outcome.checks}["window_compiles"]
    print(json.dumps({"workload": cell.name, "seed": seed,
                      "setup_s": outcome.setup_s, "window_compiles": compiles,
                      **outcome.notes}), flush=True)
    per_layer, extra, breakdown = None, None, None
    if trace:
        ctx = dict(outcome.layer_ctx, reduction=red,
                   peaks=P.peaks(devices[0].device_kind))
        per_layer = per_layer_metrics(cell, ctx)
        extra = {"busy_s": red.busy_s, "window_s": red.window_s}
        breakdown = red.breakdown()
    line = H.result_line(cell, outcome, devices, trace, per_layer, extra,
                         breakdown)
    return line, outcome


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _prepare()
    import harness as H
    cell = H.find_cell(H.load_manifest(), args.workload)
    devices = H.require_chip(cell.chips)
    _enable_cache()
    line, outcome = execute(cell, args.seed, args.seconds, bool(args.trace),
                            devices)
    print(json.dumps(line), flush=True)
    H.print_checks(outcome.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
