"""Cell ``sim_engine`` — simulator engine throughput: legacy loop vs
compiled replay vs the batched sweep driver (DESIGN.md §4/§5).

Part 1 — per-run engines on the MLP stand-in at λ ∈ {8, 32, 128}, μ = 4
(the paper's small-minibatch sweet spot, Table 3), via the experiment
surface with ``engine="legacy"`` vs the default compiled trace/replay:

* ``1-softsync`` (c = λ) — the paper's Table-3 winner and the shape where
  the legacy loop hurts most: λ un-jitted ``grad_fn`` dispatches plus one
  host→device optimizer round-trip per update.
* ``(λ/4)-softsync`` (c = 4) — staleness-heavy: the replay ring buffer K
  grows to ~2n while per-update work stays fixed.
* ``λ-softsync`` (c = 1, Eq.-5 degenerate ≈ async) — maximal staleness:
  the ring buffer runs at its full K ≈ 2λ bound and the legacy loop pays
  one complete dispatch round-trip per single-gradient update.

Part 2 — the sweep headline: a 4-LR × 5-seed grid cell replayed as ONE
vmapped device program with one vectorized staging pass
(``run_sweep``/``core.engine.replay_batch``) vs the same grid executed as
sequential per-spec replays (``run_sweep(batch=False)``).

Timing protocol: per configuration both paths are warmed (jit + scan
compiles excluded — the sweep regime: one compile, many replays), then
timed best-of-N end-to-end through the public API on identical
RunConfig/seed grids (identical traces).  ``max_param_drift`` cross-checks
result equivalence on the benchmarked runs themselves.

Wall-clock throughput is machine-dependent, so the cell re-times on every
execution; only the drift/equivalence numbers are claim-checked.  The
``bench_guard`` cell consumes the throughput rows against its CI floors.
"""

from __future__ import annotations

import time

from repro.experiments.registry import Cell, Claim, emit, register_cell

LAMBDAS = (8, 32, 128)
MU = 4
MLP_D = 2762                    # mlp_teacher flat parameter count


def _wait(res):
    import jax.numpy as jnp
    jnp.asarray(res.params["w1"]).block_until_ready()
    return res


def _best_of(fn, repeats: int = 5):
    # min over repeats: discards scheduler noise on a shared CPU
    times, res = [], None
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = fn()
        times.append(time.perf_counter() - t0)
    return min(times), res


def _bench_one(cfg, updates: int, warm_updates: int = 4,
               repeats: int = 5) -> dict:
    import jax.numpy as jnp

    from repro.experiments import ExperimentSpec
    from repro.experiments import run as run_spec
    from repro.launch.roofline import ring_bytes

    spec = ExperimentSpec(run=cfg, problem="mlp_teacher", steps=updates)
    legacy_spec = spec.replace(engine="legacy")

    _wait(run_spec(legacy_spec.replace(steps=warm_updates)))  # legacy warmup
    t_legacy, legacy = _best_of(lambda: _wait(run_spec(legacy_spec)), repeats)

    t0 = time.perf_counter()
    _wait(run_spec(spec))                                   # scan compile
    t_compile = time.perf_counter() - t0
    t_replay, compiled = _best_of(lambda: _wait(run_spec(spec)), repeats)

    drift = float(jnp.max(jnp.abs(
        jnp.asarray(legacy.params["w2"]) -
        jnp.asarray(compiled.params["w2"]))))
    K = compiled.staleness["ring_buffer_K"]
    return {
        "lambda": cfg.n_learners,
        "n_softsync": cfg.n_softsync,
        "c": cfg.gradients_per_update,
        "ring_buffer_K": K,
        "updates": updates,
        "legacy_updates_per_s": updates / t_legacy,
        "compiled_updates_per_s": updates / t_replay,
        "speedup": t_legacy / t_replay,
        "compile_s": t_compile,
        "max_param_drift": drift,
        "ring_bytes_total": ring_bytes(
            K, MLP_D, cfg.ring_dtype, cfg.optimizer)["total_bytes"],
    }


def _bench_sweep(updates: int = 60, lam: int = 32, mu: int = 1,
                 seeds: int = 5, repeats: int = 3) -> dict:
    """The batched-replay headline: 4 LRs × ``seeds`` seeds at 1-softsync
    (c = λ — the Table-3 winner shape) in the small-μ regime where per-slot
    staging dominates the hand-wired pipeline.  All grid points share one
    trace shape, so the whole cell is ONE vmapped scan."""
    import jax.numpy as jnp

    from repro.config import RunConfig
    from repro.experiments import ExperimentSpec, Sweep, run_sweep

    base = ExperimentSpec(
        run=RunConfig(protocol="softsync", n_softsync=1, n_learners=lam,
                      minibatch=mu, base_lr=0.05,
                      lr_policy="staleness_inverse", optimizer="momentum",
                      seed=17),
        problem="mlp_teacher", steps=updates)
    sweep = Sweep.over(base, base_lr=[0.02, 0.05, 0.1, 0.2],
                       seed=range(seeds))

    def _wait_all(results):
        for r in results:
            jnp.asarray(r.params["w1"]).block_until_ready()
        return results

    _wait_all(run_sweep(sweep))                             # warm both paths
    _wait_all(run_sweep(sweep, batch=False))
    t_batch, batched = _best_of(lambda: _wait_all(run_sweep(sweep)), repeats)
    t_seq, seq = _best_of(
        lambda: _wait_all(run_sweep(sweep, batch=False)), repeats)
    drift = max(
        float(jnp.max(jnp.abs(jnp.asarray(a.params["w2"]) -
                              jnp.asarray(b.params["w2"]))))
        for a, b in zip(batched, seq))
    return {
        "grid": f"4xlr * {seeds}xseed",
        "runs": 4 * seeds,
        "protocol_shape": f"1-softsync lam={lam} c={lam} mu={mu}",
        "updates_per_run": updates,
        "sequential_s": t_seq,
        "batched_s": t_batch,
        "speedup": t_seq / t_batch,
        "max_param_drift": drift,
    }


def _bench_megakernel(updates: int = 96, lam: int = 32,
                      repeats: int = 5) -> dict:
    """The megakernel scan body (its jnp twin off the TPU) on an fp32 and
    on the bf16 compressed ring (same event count, half the ring bytes,
    error-feedback residue carried; DESIGN.md §12), both through the
    driver's cached-trace + staged-minibatch path.  The bf16 row's drift
    is against the fp32 row."""
    import jax.numpy as jnp

    from repro.config import RunConfig
    from repro.experiments import ExperimentSpec
    from repro.experiments import run as run_spec
    from repro.launch.roofline import ring_bytes

    def cell(**kw):
        cfg = RunConfig(protocol="softsync", n_softsync=1, n_learners=lam,
                        minibatch=MU, base_lr=0.05,
                        lr_policy="staleness_inverse", optimizer="momentum",
                        seed=17, **kw)
        return ExperimentSpec(run=cfg, problem="mlp_teacher", steps=updates)

    rows = {}
    ref = None
    for label, kw in (("megakernel", {}),
                      ("megakernel_bf16", {"ring_dtype": "bf16"})):
        spec = cell(**kw)
        _wait(run_spec(spec))                               # compile + warm
        t, res = _best_of(lambda s=spec: _wait(run_spec(s)), repeats)
        K = res.staleness["ring_buffer_K"]
        rows[label] = {
            "updates_per_s": updates / t,
            "seconds": t,
            "ring_bytes_total": ring_bytes(
                K, MLP_D, spec.run.ring_dtype,
                spec.run.optimizer)["total_bytes"],
            "max_param_drift": (0.0 if ref is None else float(jnp.max(
                jnp.abs(jnp.asarray(ref.params["w2"]) -
                        jnp.asarray(res.params["w2"]))))),
        }
        if ref is None:
            ref = res
    out = {
        "protocol_shape": f"1-softsync lam={lam} c={lam} mu={MU}",
        "updates": updates,
        **{f"{k}_{m}": v for k, row in rows.items() for m, v in row.items()},
        "bf16_ring_bytes_saved": (rows["megakernel"]["ring_bytes_total"]
                                  - rows["megakernel_bf16"]
                                  ["ring_bytes_total"]),
    }
    return out


def _bench_whatif(updates: int = 96, d: int = 1_000_000,
                  repeats: int = 3) -> dict:
    """The what-if replay (in-kernel closed-form gradients, no staged
    data) vs the staged-gradient path (the problem's ``grad_fn`` on staged
    dummy batches) on the same quadratic problem and trace.  Wall clock is ~parity (same FLOPs either way on CPU); the
    win is PEAK MEMORY — no (c, D) pulled/gradient matrices, a donated
    ring carry — which is what runs at ``configs/`` big-model D (the
    ``ring`` feasibility cell's limit study)."""
    import jax.numpy as jnp

    from repro.config import RunConfig
    from repro.core.engine import replay
    from repro.core.trace import schedule_cached
    from repro.experiments import ExperimentSpec
    from repro.experiments import run as run_spec
    from repro.experiments.problems import get_problem
    from repro.launch.roofline import ring_bytes

    cfg = RunConfig(protocol="softsync", n_softsync=2, n_learners=8,
                    minibatch=1, base_lr=0.02, optimizer="momentum", seed=11)
    args = (("d", d),)
    whatif = ExperimentSpec(run=cfg, problem="quadratic_whatif",
                            problem_args=args, steps=updates)
    problem = get_problem("quadratic_whatif", args)
    trace = schedule_cached(cfg, updates)

    def staged():
        return replay(trace, cfg, grad_fn=problem.grad_fn,
                      init_params=problem.init,
                      batch_fn=problem.batch_fn_for(cfg.minibatch))

    def wait_q(res):
        jnp.asarray(res.params["w"]).block_until_ready()
        return res

    wait_q(run_spec(whatif))
    t_whatif, rw = _best_of(lambda: wait_q(run_spec(whatif)), repeats)
    wait_q(staged())
    t_staged, rs = _best_of(lambda: wait_q(staged()), repeats)
    K = rw.staleness["ring_buffer_K"]
    drift = float(jnp.max(jnp.abs(jnp.asarray(rw.params["w"]) -
                                  jnp.asarray(rs.params["w"]))))
    return {
        "d": d, "updates": updates, "ring_buffer_K": K,
        "whatif_updates_per_s": updates / t_whatif,
        "staged_updates_per_s": updates / t_staged,
        "vs_staged_ratio": t_staged / t_whatif,
        "max_param_drift": drift,
        "ring_bytes_total": ring_bytes(
            K, d, cfg.ring_dtype, cfg.optimizer)["total_bytes"],
    }


def compute(updates: int = 480):
    from repro.config import RunConfig

    out = {}
    for lam in LAMBDAS:
        for label, n in [("softsync_1", 1), ("softsync_quarter", lam // 4),
                         ("softsync_lambda", lam)]:
            cfg = RunConfig(protocol="softsync", n_softsync=n,
                            n_learners=lam, minibatch=MU, base_lr=0.05,
                            lr_policy="staleness_inverse",
                            optimizer="momentum", seed=17)
            row = _bench_one(cfg, updates)
            out[f"{label}_lambda_{lam}"] = row
            emit(f"sim_engine/{label}/lambda={lam}/updates_per_s",
                 f"legacy={row['legacy_updates_per_s']:.1f} "
                 f"compiled={row['compiled_updates_per_s']:.1f}",
                 f"speedup={row['speedup']:.1f}x c={row['c']} "
                 f"K={row['ring_buffer_K']} "
                 f"drift={row['max_param_drift']:.1e}")
    # scale the sweep cell's per-run budget with the engine rows' budget so
    # --quick stays quick
    sweep_row = _bench_sweep(updates=max(10, updates // 8))
    out["sweep_batched_vs_sequential"] = sweep_row
    emit("sim_engine/sweep_batched/4lr_x_5seed",
         f"sequential={sweep_row['sequential_s']:.2f}s "
         f"batched={sweep_row['batched_s']:.2f}s",
         f"speedup={sweep_row['speedup']:.1f}x "
         f"drift={sweep_row['max_param_drift']:.1e}")
    mk_row = _bench_megakernel(updates=max(24, updates // 5))
    out["megakernel"] = mk_row
    emit("sim_engine/megakernel",
         f"{mk_row['megakernel_updates_per_s']:.1f}up/s",
         f"ring_bytes={mk_row['megakernel_ring_bytes_total']}")
    emit("sim_engine/megakernel_bf16_ring",
         f"{mk_row['megakernel_bf16_updates_per_s']:.1f}up/s",
         f"ring_bytes={mk_row['megakernel_bf16_ring_bytes_total']} "
         f"(saves {mk_row['bf16_ring_bytes_saved']}) "
         f"drift={mk_row['megakernel_bf16_max_param_drift']:.1e}")
    whatif_row = _bench_whatif(updates=max(24, updates // 5))
    out["whatif_quadratic"] = whatif_row
    emit("sim_engine/whatif_quadratic",
         f"{whatif_row['whatif_updates_per_s']:.1f}up/s at "
         f"D={whatif_row['d']}",
         f"staged={whatif_row['staged_updates_per_s']:.1f}up/s "
         f"ratio={whatif_row['vs_staged_ratio']:.2f}x "
         f"ring={whatif_row['ring_bytes_total']/1e6:.0f}MB")
    return [], out


register_cell(Cell(
    name="sim_engine", result="sim_engine_bench",
    title="Engine throughput: legacy vs compiled vs batched sweep",
    compute=compute,
    claims=(
        Claim("engine_rows_drift_small",
              lambda d: all(v["max_param_drift"] < 1e-3
                            for k, v in d.items()
                            if k.startswith("softsync_"))),
        Claim("sweep_drift_small",
              lambda d: (d["sweep_batched_vs_sequential"]["max_param_drift"]
                         < 1e-3)),
        Claim("megakernel_bf16_drift_small",
              lambda d: (d["megakernel"]["megakernel_bf16_max_param_drift"]
                         < 1e-3)),
        Claim("whatif_drift_small",
              lambda d: d["whatif_quadratic"]["max_param_drift"] < 1e-3),
    ),
    params={"updates": 480}, quick_params={"updates": 40}))
