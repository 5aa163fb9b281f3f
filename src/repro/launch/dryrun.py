import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and emit roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k [--multi-pod] [--protocol softsync --n 4] \
        [--engine sequential|fused] [--json out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The platform and XLA_FLAGS lines above MUST precede any jax import (device
count locks at first init).  The 512 devices are emulated CPU devices, so
the platform is pinned to the CPU: a dry run never touches an accelerator.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.config import INPUT_SHAPES, validate_pairing
from repro.configs import ARCH_IDS, get_config, long_context_variant
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh, n_chips, n_learners
from repro.launch.specs import (build_lowerable, make_run_config,
                                params_specs)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               protocol: str = "softsync", n_softsync: int = 4,
               engine: str = "sequential", num_microbatches: int = 0,
               attn_q_chunk: int = 1024, attn_kv_chunk: int = 1024,
               seq_par_residual: bool = False, mode_override: str = None,
               verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    skip = validate_pairing(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    run, engine = make_run_config(cfg, shape, mesh, protocol=protocol,
                                  n_softsync=n_softsync, engine=engine,
                                  num_microbatches=num_microbatches,
                                  attn_q_chunk=attn_q_chunk,
                                  attn_kv_chunk=attn_kv_chunk,
                                  seq_par_residual=seq_par_residual,
                                  mode_override=mode_override)
    t0 = time.time()
    with mesh:
        fn, arg_specs = build_lowerable(cfg, shape, mesh, run, engine=engine,
                                        mode_override=mode_override)
        lowered = fn.lower(*arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = rl.normalize_cost_analysis(compiled.cost_analysis())
        hlo = compiled.as_text()

    roof = rl.analyse(arch, shape_name, mesh_name, n_chips(mesh),
                      cost, hlo, rl.model_flops(cfg, shape))
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok",
        "protocol": run.protocol, "n_softsync": run.n_softsync,
        "engine": engine, "num_microbatches": run.num_microbatches,
        "fsdp": run.fsdp,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": int(getattr(mem, "temp_size_in_bytes", 0)
                                + getattr(mem, "argument_size_in_bytes", 0)
                                + getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        **{k: v for k, v in roof.row().items()
           if k not in ("arch", "shape", "mesh")},
        "coll_breakdown": {k: v for k, v in roof.coll_breakdown.items()
                           if v > 0},
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] OK "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"t_comp {roof.t_compute*1e3:.1f}ms "
              f"t_mem {roof.t_memory*1e3:.1f}ms "
              f"t_coll {roof.t_collective*1e3:.1f}ms "
              f"-> {roof.dominant}-bound | useful {roof.useful_flops_ratio:.2f} "
              f"| {result['bytes_per_device']/2**30:.1f} GiB/dev")
        sys.stdout.flush()
    return result


def _probe_costs(cfg, shape, mesh, run, engine, mode_override=None):
    """Lower one fully-unrolled cost probe; return (flops, bytes, coll)."""
    with mesh:
        fn, arg_specs = build_lowerable(cfg, shape, mesh, run, engine=engine,
                                        mode_override=mode_override)
        lowered = fn.lower(*arg_specs)
        compiled = lowered.compile()
        cost = rl.normalize_cost_analysis(compiled.cost_analysis())
        coll = rl.collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll["total"], coll)


def _grad_allreduce_bytes(cfg, mesh, fsdp: bool) -> float:
    """Analytic per-device wire bytes of ONE gradient all-reduce over the λ
    learner groups (ring, bf16 grads) — used to correct the sequential
    softsync engine's (G−1) extra reduces that the hardsync probe lacks."""
    pspecs = params_specs(cfg, mesh, fsdp)
    lam = n_learners(mesh)
    total_local = 0
    for leaf in jax.tree.leaves(pspecs):
        shard = leaf.sharding.shard_shape(leaf.shape)
        total_local += int(np.prod(shard)) * 2        # bf16
    return 2.0 * total_local * (lam - 1) / lam


def probe_roofline(arch: str, shape_name: str, *, multi_pod: bool = False,
                   protocol: str = "softsync", n_softsync: int = 4,
                   engine: str = "sequential",
                   attn_q_chunk: int = 1024, attn_kv_chunk: int = 1024,
                   seq_par_residual: bool = False, mode_override: str = None,
                   verbose: bool = True) -> dict:
    """Trip-count-correct roofline: lower unrolled probes at n_units ∈ {1, 2}
    (python loops; cost_analysis counts lax.scan bodies only ONCE — see
    EXPERIMENTS.md §Methodology), then
        total = probe1 + (U − 1) · (probe2 − probe1).
    Probes run hardsync / microbatch=1 (FLOP/byte-equivalent: both are linear
    batch splits); sequential softsync adds (G−1) gradient all-reduces which
    are corrected analytically.
    """
    cfg_full = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.name == "long_500k":
        cfg_full = long_context_variant(cfg_full)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    skip = validate_pairing(cfg_full, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    costs = {}
    for u in (1, 2):
        cfg_u = dataclasses.replace(cfg_full, n_units=u)
        run, eng = make_run_config(cfg_u, shape, mesh, protocol="hardsync",
                                   engine="sequential", num_microbatches=1,
                                   attn_q_chunk=attn_q_chunk,
                                   attn_kv_chunk=attn_kv_chunk,
                                   seq_par_residual=seq_par_residual,
                                   mode_override=mode_override)
        run = run.replace(unroll=True)
        costs[u] = _probe_costs(cfg_u, shape, mesh, run, eng,
                                mode_override=mode_override)
    U = cfg_full.n_units
    f1, b1, c1, bk1 = costs[1]
    f2, b2, c2, bk2 = costs[2]
    flops = f1 + (U - 1) * (f2 - f1)
    hbytes = b1 + (U - 1) * (b2 - b1)
    coll = c1 + (U - 1) * (c2 - c1)
    # per-kind extrapolation: fixed part (embed/head/loss) + U × per-unit
    breakdown = {k: bk1.get(k, 0.0) + (U - 1) * (bk2.get(k, 0.0)
                                                 - bk1.get(k, 0.0))
                 for k in (set(bk1) | set(bk2)) - {"total"}}
    breakdown = {k: v for k, v in breakdown.items() if v > 0}
    coll_per_unit = c2 - c1
    coll_fixed = c1 - coll_per_unit

    # sequential-softsync collective correction: (G−1) extra grad reduces
    G = n_softsync if (protocol in ("softsync", "async")
                       and shape.kind == "train") else 1
    from repro.launch import sharding as _shd
    ar_grad = _grad_allreduce_bytes(cfg_full, mesh,
                                    _shd.needs_fsdp(cfg_full, mesh))
    coll_corrected = coll + (G - 1) * ar_grad if G > 1 else coll

    roof = rl.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=n_chips(mesh),
        hlo_flops=flops, hlo_bytes=hbytes, coll_bytes=coll_corrected,
        model_flops=rl.model_flops(cfg_full, shape),
        coll_breakdown=breakdown)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "status": "ok", "kind": "probe",
              "protocol": protocol, "n_softsync": G,
              "probe_seconds": round(time.time() - t0, 1),
              "ar_grad_bytes": ar_grad,
              "coll_fixed_bytes": coll_fixed,
              "coll_per_unit_bytes": coll_per_unit,
              **{k: v for k, v in roof.row().items()
                 if k not in ("arch", "shape", "mesh")},
              "coll_breakdown": breakdown}
    if verbose:
        print(f"[probe {arch} × {shape_name} × {mesh_name}] "
              f"t_comp {roof.t_compute*1e3:.1f}ms "
              f"t_mem {roof.t_memory*1e3:.1f}ms "
              f"t_coll {roof.t_collective*1e3:.1f}ms "
              f"-> {roof.dominant}-bound | useful {roof.useful_flops_ratio:.3f}"
              f" | {result['probe_seconds']}s")
        sys.stdout.flush()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--protocol", default="softsync",
                    choices=["hardsync", "softsync", "async"])
    ap.add_argument("--n", type=int, default=4, dest="n_softsync")
    ap.add_argument("--engine", default="sequential",
                    choices=["sequential", "fused"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--kv-chunk", type=int, default=1024)
    ap.add_argument("--mode", default="main", choices=["main", "probe"])
    ap.add_argument("--seq-par-residual", action="store_true")
    ap.add_argument("--force-mode", default=None, choices=["head", "seq"])
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    pairs = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        pairs = [(args.arch, args.shape)]

    results = []
    for a, s in pairs:
        try:
            if args.mode == "probe":
                results.append(probe_roofline(
                    a, s, multi_pod=args.multi_pod, protocol=args.protocol,
                    n_softsync=args.n_softsync, engine=args.engine,
                    attn_q_chunk=args.q_chunk, attn_kv_chunk=args.kv_chunk,
                    seq_par_residual=args.seq_par_residual,
                    mode_override=args.force_mode))
                continue
            results.append(dryrun_one(
                a, s, multi_pod=args.multi_pod, protocol=args.protocol,
                n_softsync=args.n_softsync, engine=args.engine,
                num_microbatches=args.microbatches,
                attn_q_chunk=args.q_chunk, attn_kv_chunk=args.kv_chunk,
                seq_par_residual=args.seq_par_residual,
                mode_override=args.force_mode))
        except Exception as e:
            traceback.print_exc()
            results.append({"arch": a, "shape": s,
                            "mesh": "2x16x16" if args.multi_pod else "16x16",
                            "status": "error", "error": repr(e)})
            sys.stdout.flush()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skip")
    err = sum(1 for r in results if r["status"] == "error")
    print(f"\n== dry-run summary: {ok} ok / {sk} skip / {err} error ==")
    return 1 if err else 0


if __name__ == "__main__":
    sys.exit(main())
