"""Cell ``ring`` — max feasible model size for trace-driven replay: stock
path vs the megakernel + what-if ring (DESIGN.md §12).

Part 1 — analytic: bytes/param of the replay working set, calibrated
against measured peak RSS (see ``measured_bytes_per_param`` in the
results).  Replaying a trace against a *real* model backward — the only
pre-megakernel option — materializes the (c, D) pulled-weight and (c, D)
per-slot gradient matrices every event on top of the undonated
double-buffered (K, D) ring:

    stock   ~ (2·K + 2·c) · 4          bytes/param
              [measured 987 at K=3, c=128 vs model 1048]

The what-if megakernel path carries only the donated ring (+ optimizer
state, + the bf16 error-feedback residue — ``roofline.ring_bytes``) and
streams the closed-form gradients in O(D):

    what-if ~ ring_bytes/param + ~16   bytes/param
              [measured 32.7 at K=3, fp32, sgd vs model 28]

At the Table-3 winner shape (1-softsync, c = λ) the gap is c-dominated:
10-100× more feasible parameters under the same memory budget, which is
what opens ``configs/`` big-model shapes to staleness what-if studies.

Part 2 — empirical: ``RLIMIT_AS``-capped subprocesses replay the same
trace shape (softsync n=1, λ=128, 8 updates) under the same 2.5 GiB
address-space cap.  A replay with a real MLP backward dies at
D₀ ≈ 10 M params (its (c, D) matrices alone need ~10 GB); the what-if megakernel on the bf16 error-feedback
ring replays 10·D₀ = 100 M.  ``skip_quick``: the capped subprocesses take
minutes.
"""

from __future__ import annotations

import os
import subprocess
import sys

from repro.experiments.registry import (Cell, derived_claims, emit,
                                        register_cell, repo_root)

# the empirical cell: 1-softsync lam=128 (c = 128), 8 updates, sgd.
# D0: MLP hidden=232558 -> D = 43*232558 + 10 = 10_000_004 ~ 10M params.
# The what-if lane replays 10*D0 sized to its kernel tile (a pad_flat
# no-op: the padded-aux copies of a / w* never materialize) on the bf16
# error-feedback ring.
_CAP_BYTES = 5 << 29            # 2.5 GiB address-space cap
_D0 = 10_000_004
_HIDDEN0 = 232_558              # the real-backward lane sized to D0
_D_WHATIF = 100_007_936         # replay_ring.padded_width(10 * _D0)
_LAM = 128
_STEPS = 8

_CHILD = """
import resource
cap = int({cap})
resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
from repro.config import RunConfig
from repro.experiments import ExperimentSpec
from repro.experiments import run as run_spec

cfg = RunConfig(protocol="softsync", n_softsync=1, n_learners={lam},
                minibatch=1, base_lr=0.01, optimizer="sgd", seed=5,
                ring_dtype={ring_dtype!r})
spec = ExperimentSpec(run=cfg, problem={problem!r},
                      problem_args={pargs!r}, steps={steps})
res = run_spec(spec)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
print("FEASIBLE", sorted(res.metrics)[0], f"peak_bytes={{peak}}")
"""


def _try_replay(label: str, d: int, problem: str, pargs: tuple,
                ring_dtype: str = "fp32", cap: int = _CAP_BYTES) -> dict:
    """Run one capped replay in a subprocess; MemoryError / bad-alloc
    aborts count as infeasible (the allocator may kill the process
    outright rather than raise, so any nonzero exit is a fail)."""
    code = _CHILD.format(cap=cap, lam=_LAM, problem=problem,
                         pargs=pargs, steps=_STEPS, ring_dtype=ring_dtype)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # RLIMIT_AS caps host memory only
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo_root(), "src"),
                    env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    ok = proc.returncode == 0 and "FEASIBLE" in proc.stdout
    return {"label": label, "platform": "cpu", "d": d, "problem": problem,
            "ring_dtype": ring_dtype, "cap_bytes": cap, "feasible": ok,
            "detail": (proc.stdout.strip() if ok else
                       (proc.stderr.strip().splitlines() or ["killed"])[-1]
                       [:200])}


def _stock_bytes_per_param(K: int, c: int) -> float:
    """Working-set bytes/param of the stock real-backward path: undonated
    (K, D) fp32 ring (2x across scan dispatches) + the per-event (c, D)
    pulled-weight and gradient fp32 matrices (live together through the
    vmapped backward).  Validated: 987 measured at K=3, c=128."""
    return 2.0 * K * 4 + 2.0 * c * 4


def _whatif_bytes_per_param(K: int, ring_dtype: str, optimizer: str) -> float:
    """Working-set bytes/param of the what-if megakernel path: the donated
    ring carry (+ state/residue, roofline.ring_bytes) plus the O(D)
    streaming set — a, w*, the accumulator, and one pulled row.
    Validated: 32.7 measured at K=3, fp32, sgd."""
    from repro.launch.roofline import ring_bytes
    carry = ring_bytes(K, 1 << 20, ring_dtype, optimizer)["bytes_per_param"]
    return carry + 4.0 * 4


def compute(**params):
    out = {}

    # ---- analytic: configs/ architectures under a 64 GB budget ------------
    # (one fat host or accelerator-pool node; the smallest configs/ arch is
    # 1.26 B params, so a 32 GB laptop budget unlocks nothing either way)
    budget = 64 << 30
    K, c = 3, _LAM          # 1-softsync lam=128: sigma <= 2n -> K = 3
    stock_bpp = _stock_bytes_per_param(K, c)
    rows = {}
    from repro.configs import ARCH_IDS, get_config
    for arch in ARCH_IDS:
        n = int(get_config(arch).param_count())
        for dtype in ("fp32", "bf16"):
            bpp = _whatif_bytes_per_param(K, dtype, "momentum")
            rows[f"{arch}_{dtype}"] = {
                "params": n,
                "whatif_bytes_per_param": bpp,
                "whatif_gb": n * bpp / 2**30,
                "stock_gb": n * stock_bpp / 2**30,
                "whatif_fits_budget": n * bpp <= budget,
                "stock_fits_budget": n * stock_bpp <= budget,
            }
    out["configs_table"] = rows
    out["analytic"] = {
        "K": K, "c": c, "budget_gb": budget / 2**30,
        "stock_bytes_per_param": stock_bpp,
        "whatif_fp32_bytes_per_param": _whatif_bytes_per_param(
            K, "fp32", "momentum"),
        "whatif_bf16_bytes_per_param": _whatif_bytes_per_param(
            K, "bf16", "momentum"),
        "max_feasible_d_stock": int(budget / stock_bpp),
        "max_feasible_d_whatif_fp32": int(
            budget / _whatif_bytes_per_param(K, "fp32", "momentum")),
        "max_feasible_d_whatif_bf16": int(
            budget / _whatif_bytes_per_param(K, "bf16", "momentum")),
    }
    out["measured_bytes_per_param"] = {
        # peak-RSS calibration points behind the models above (dev box,
        # CPU XLA; softsync n=1 lam=128, 8 updates).  "capped" = under the
        # RLIMIT_AS cap, where the allocator reuses aggressively.
        "stock_mlp_backward_d4m_uncapped": 987.0,
        "whatif_fp32_sgd_d40m_uncapped": 32.7,
        "whatif_bf16_sgd_d100m_capped": 20.0,
    }
    gain = (out["analytic"]["max_feasible_d_whatif_bf16"]
            / out["analytic"]["max_feasible_d_stock"])
    out["analytic"]["feasible_d_gain_bf16"] = gain
    emit("ring_feasibility/analytic/max_feasible_D",
         f"stock={out['analytic']['max_feasible_d_stock']:.2e} "
         f"whatif_bf16={out['analytic']['max_feasible_d_whatif_bf16']:.2e}",
         f"gain={gain:.1f}x at K={K} c={c} under "
         f"{budget >> 30}GB")
    fits = [a for a in ARCH_IDS
            if rows[f"{a}_bf16"]["whatif_fits_budget"]
            and not rows[f"{a}_bf16"]["stock_fits_budget"]]
    emit("ring_feasibility/analytic/configs_unlocked",
         len(fits), ",".join(fits))

    # ---- empirical: RLIMIT_AS-capped subprocess replays -------------------
    # staged path = real MLP backward at D0 (the only pre-megakernel way
    # to replay a trace); new path = what-if megakernel on the closed-form
    # quadratic at 10*D0, same trace shape and cap.
    trials = [
        _try_replay("real_backward_D0", _D0, "mlp_teacher",
                    (("hidden", _HIDDEN0),)),
        _try_replay("whatif_megakernel_10xD0", _D_WHATIF,
                    "quadratic_whatif", (("d", _D_WHATIF),),
                    ring_dtype="bf16"),
    ]
    out["rlimit_demo"] = {
        "cap_gb": _CAP_BYTES / 2**30, "lam": _LAM, "steps": _STEPS,
        "trials": trials,
        "demonstrated_gain": (">=10x" if (not trials[0]["feasible"]
                                          and trials[1]["feasible"])
                              else "NOT demonstrated"),
    }
    for t in trials:
        emit(f"ring_feasibility/rlimit/{t['label']}",
             "feasible" if t["feasible"] else "OOM",
             f"d={t['d']:.0e} cap={_CAP_BYTES / 2**30:.1f}GB")
    emit("ring_feasibility/rlimit/gain",
         out["rlimit_demo"]["demonstrated_gain"],
         f"real backward dies at D0={_D0:.0e}; what-if replays 10*D0")

    out["claims"] = {
        "whatif_extends_feasible_d": gain > 10.0,
        "rlimit_gain_demonstrated":
            out["rlimit_demo"]["demonstrated_gain"] == ">=10x",
    }
    return [], out


register_cell(Cell(
    name="ring", result="ring_feasibility",
    title="Ring feasibility: stock vs what-if megakernel model-size limits",
    compute=compute, skip_quick=True,
    claims=derived_claims("whatif_extends_feasible_d",
                          "rlimit_gain_demonstrated")))
