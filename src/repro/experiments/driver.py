"""The one experiment driver: ``run(spec) -> RunResult`` (DESIGN.md §5).

``run`` executes a declarative :class:`ExperimentSpec` end-to-end —
resolve problem and budget, schedule the arrival trace, replay it on the
compiled engine (or the legacy per-arrival oracle, or measure-only), and
fold trace + metrics into a :class:`RunResult` record.

``run_sweep`` executes a grid.  Its performance headline: grid points
whose traces are **shape-compatible** (same steps and c — e.g. a 5-seed ×
4-LR cell at fixed protocol shape) and share problem/optimizer/μ are
replayed as ONE vmapped device program (``core.engine.replay_batch``)
instead of sequential replays; everything else falls back to per-spec
:func:`run` semantics.  Results always come back in spec order and are
identical to sequential execution (``tests/test_experiments.py``).

``execute`` is the raw-callable escape hatch for callers with a
hand-written ``grad_fn``/``batch_fn`` instead of a registered problem
(the pre-PR-3 ``simulate_compiled`` / ``simulate_measure`` shims over it
are gone — this and the spec surface are the only entry points).
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from repro.config import RunConfig
from repro.core.engine import replay, replay_batch
from repro.core.simulator import SimResult, simulate
from repro.core.trace import ArrivalTrace, schedule, schedule_cached
from repro.experiments.result import RunResult
from repro.experiments.spec import ExperimentSpec
from repro.experiments.sweep import Sweep
from repro.optim import spec_from_run


def execute(run_cfg: RunConfig, *,
            steps: int,
            grad_fn: Optional[Callable] = None,
            init_params=None,
            batch_fn: Optional[Callable] = None,
            eval_fn: Optional[Callable] = None,
            eval_every: int = 0,
            duration_sampler: Optional[Callable] = None,
            engine: str = "compiled",
            serve_batches=None,
            serve_eval_fn: Optional[Callable] = None) -> SimResult:
    """Run one simulation from raw callables (no problem registry).

    ``engine``: "compiled" (schedule + lax.scan replay; measure-only when
    ``grad_fn`` is None), "measure" (schedule pass only), or "legacy" (the
    per-arrival oracle loop in ``core/simulator.py``).
    """
    if engine == "legacy":
        return simulate(run_cfg, steps=steps, grad_fn=grad_fn,
                        init_params=init_params, batch_fn=batch_fn,
                        eval_fn=eval_fn, eval_every=eval_every,
                        duration_sampler=duration_sampler)
    if engine not in ("compiled", "measure"):
        raise ValueError(f"unknown engine {engine!r}")
    trace = schedule(run_cfg, steps, duration_sampler=duration_sampler)
    if grad_fn is None or engine == "measure":
        return SimResult(trace.clock_log(), trace.steps,
                         trace.simulated_time, trace.minibatches)
    return replay(trace, run_cfg, grad_fn=grad_fn, init_params=init_params,
                  batch_fn=batch_fn, eval_fn=eval_fn, eval_every=eval_every,
                  serve_batches=serve_batches, serve_eval_fn=serve_eval_fn)


# ---------------------------------------------------------------------------
# spec → RunResult
# ---------------------------------------------------------------------------
_SERIES_HEAD = 50


def _staleness_stats(trace: ArrivalTrace, run_cfg: RunConfig) -> Dict:
    """The Fig.-4 statistics block of every record, off the trace."""
    log = trace.clock_log()
    vals = log.all_staleness_values()
    expected = run_cfg.expected_staleness
    return {
        "mean": log.mean_staleness(),
        "min": float(vals.min()) if len(vals) else 0.0,
        "max": float(vals.max()) if len(vals) else 0.0,
        "expected": expected,
        "frac_exceeding_2n": log.fraction_exceeding(2 * max(1.0, expected)),
        "ring_buffer_K": trace.max_staleness + 1,
        "histogram": log.staleness_histogram().tolist(),
        "series_head": log.average_staleness_series()[:_SERIES_HEAD].tolist(),
    }


def _result(spec: ExperimentSpec, trace: ArrivalTrace,
            sim: Optional[SimResult], problem,
            replay_path: str = "sequential") -> RunResult:
    metrics: Dict = {}
    curve: List[Dict] = []
    params = None
    if sim is not None and sim.params is not None:
        params = sim.params
        metrics = dict(problem.eval_fn(params))
        curve = list(sim.history or [])
    runtime = {"simulated_time": trace.simulated_time,
               "updates": trace.steps,
               "minibatches": trace.minibatches,
               # which execution path produced this record: "batched"
               # (one vmapped program over a sweep cell), "sequential"
               # (per-spec compiled replay), "legacy", or "measure" —
               # the sweep fast path is a ~3.6× cliff, so the record
               # says which side of it this run landed on
               "replay_path": replay_path}
    if sim is not None and sim.serving is not None:
        # serving lane (DESIGN.md §14): headline numbers into metrics so
        # sweep tables pick them up, the full summary into runtime
        summary = sim.serving.summary()
        metrics["serving_accuracy"] = summary["accuracy"]
        metrics["serving_staleness_mean"] = summary["staleness_mean"]
        metrics["serving_latency_p99_s"] = summary["latency_p99_s"]
        runtime["serving"] = summary
    return RunResult(
        spec=spec.echo(),
        metrics=metrics,
        curve=curve,
        runtime=runtime,
        staleness=_staleness_stats(trace, spec.run),
        params=params,
        trace=trace,
    )


# staged-minibatch memo: repeated replays of the same (problem, trace, μ)
# grid point — benchmark loops, sweep repeats over cached traces — reuse
# the staged (steps, c, …) pytree instead of re-hashing the whole trace.
# Keys are object ids, so entries keep strong refs and re-check identity
# (an id can be recycled after gc); the bound keeps params-sized pytrees
# from accumulating in long-lived processes.
_STAGED_CACHE: Dict = {}
_STAGED_CACHE_MAX = 8


def _staged_cached(problem, trace, mu: int, build: Callable):
    key = (id(problem), id(trace), mu)
    hit = _STAGED_CACHE.get(key)
    if hit is not None and hit[0] is problem and hit[1] is trace:
        return hit[2]
    staged = build()
    if staged is not None:
        if len(_STAGED_CACHE) >= _STAGED_CACHE_MAX:
            _STAGED_CACHE.pop(next(iter(_STAGED_CACHE)))
        _STAGED_CACHE[key] = (problem, trace, staged)
    return staged


class _Job:
    """One grid point, scheduled: everything replay needs, plus its slot."""

    def __init__(self, index: int, spec: ExperimentSpec):
        self.index = index
        self.spec = spec
        self.engine = spec.resolved_engine()
        self.steps = spec.resolved_steps()
        self.problem = spec.resolve_problem()
        sampler = spec.duration_sampler()
        # built-in duration models are pure in (run, steps): share one
        # trace object across repeated replays of the same grid point
        # (and let the staged-batches cache key on its identity)
        self.trace = (schedule_cached(spec.run, self.steps)
                      if sampler is None
                      else schedule(spec.run, self.steps,
                                    duration_sampler=sampler))

    @property
    def batch_fn(self):
        return self.problem.batch_fn_for(self.spec.run.minibatch)

    def staged_batches(self):
        """The whole trace's minibatches via the problem's vectorized
        staging hook (None if the problem only offers per-slot batch_fn) —
        one hash/gather pass instead of a steps×c Python loop, feeding the
        batched replay's stacked (B, steps, c, …) inputs.  With learner
        groups the slot counters expand to the (steps, c, gs) member
        matrices (every member of a slot shares its push counter)."""
        stage = getattr(self.problem, "stage_minibatches", None)
        if stage is None:
            return None

        def build():
            members = self.trace.member_learners()
            if members is None:
                return stage(self.trace.learner, self.trace.mb_index,
                             self.spec.run.minibatch)
            mb = np.broadcast_to(self.trace.mb_index[:, :, None],
                                 members.shape)
            return stage(members, mb, self.spec.run.minibatch)

        return _staged_cached(self.problem, self.trace,
                              self.spec.run.minibatch, build)

    def batch_exclusion(self) -> Optional[str]:
        """Why this compiled grid point can never join a vmapped batch
        group — the ~3.6× sweep cliff ``run_sweep`` warns about — or None
        when it is batch-eligible (measure/legacy jobs are also None: they
        have no compiled fast path to fall off)."""
        if self.engine != "compiled" or self.problem is None:
            return None
        opt = spec_from_run(self.spec.run)
        if not opt.kernel_supported:
            return (f"optimizer {opt.optimizer!r} has no flat lane layout")
        if not self.trace.topology.is_trivial(self.spec.run.n_learners):
            # covers elastic grouped traces too: member_valid masks only
            # arise with group_size > 1, which is already non-trivial
            return (f"non-trivial topology (shards="
                    f"{self.spec.run.shards}, groups={self.spec.run.groups})")
        if self.spec.run.placement != "single":
            return (f"placement={self.spec.run.placement!r} replays on its "
                    f"own device mesh (no lane axis)")
        if self.trace.serving is not None:
            return ("serving lane (run.serving) adds a snapshot carry and "
                    "a post-scan request evaluation — no vmapped lane "
                    "layout")
        return None

    def batch_key(self):
        """Grid points with equal keys replay as one vmapped program:
        same problem (⇒ same grad_fn/init/batch shapes), same trace shape
        (steps, c), same optimizer event, same μ, eval schedule, and
        elasticity (masked elastic lanes batch together — the per-event
        coefficients are lane data).  Sharded/grouped topologies replay
        per-spec (no vmapped lane layout), so they never join a group."""
        if (self.engine != "compiled" or self.problem is None
                or self.batch_exclusion() is not None):
            return None
        opt = spec_from_run(self.spec.run)
        return (id(self.problem), self.steps, self.trace.c, self.trace.mode,
                opt, self.spec.run.minibatch, self.spec.eval_every,
                self.trace.valid is not None,
                # lanes must agree on ring storage: a bf16 lane's carry
                # has a different dtype + residue layout, and replay_batch
                # rejects mixed groups
                self.spec.run.ring_dtype)

    def run_single(self) -> RunResult:
        if self.engine == "measure":
            return _result(self.spec, self.trace, None, None,
                           replay_path="measure")
        if self.engine == "legacy":
            sim = simulate(self.spec.run, steps=self.steps,
                           grad_fn=self.problem.grad_fn,
                           init_params=self.problem.init,
                           batch_fn=self.batch_fn,
                           eval_fn=self.problem.eval_fn,
                           eval_every=self.spec.eval_every,
                           duration_sampler=self.spec.duration_sampler())
            return _result(self.spec, self.trace, sim, self.problem,
                           replay_path="legacy")
        # prefer whole-trace staged minibatches (one vectorized hash +
        # one device transfer per leaf) over the per-slot batch_fn loop —
        # the loop dominated sequential-replay wall clock before PR 6 —
        # and hand the problem's closed-form gradient (if any) to the
        # what-if replay path
        staged = self.staged_batches()
        serve_kw = {}
        if self.trace.serving is not None:
            stage_requests = getattr(self.problem, "stage_requests", None)
            request_metric = getattr(self.problem, "request_metric", None)
            if stage_requests is None or request_metric is None:
                raise ValueError(
                    f"run.serving is set but problem {self.spec.problem!r} "
                    f"has no serving hooks — implement "
                    f"stage_requests(serving_trace, fleet, seed) and "
                    f"request_metric(params, request_batch) (see "
                    f"MLPProblem), or drop serving from the RunConfig")
            serve_kw = {
                "serve_batches": stage_requests(self.trace.serving,
                                                self.spec.run.serving,
                                                seed=self.spec.run.seed),
                "serve_eval_fn": request_metric,
            }
        sim = replay(self.trace, self.spec.run,
                     grad_fn=self.problem.grad_fn,
                     init_params=self.problem.init,
                     batch_fn=None if staged is not None else self.batch_fn,
                     batches=staged,
                     eval_fn=self.problem.eval_fn,
                     eval_every=self.spec.eval_every,
                     flat_grad=getattr(self.problem, "flat_grad", None),
                     **serve_kw)
        return _result(self.spec, self.trace, sim, self.problem,
                       replay_path="sequential")


def run(spec: ExperimentSpec) -> RunResult:
    """Execute one ExperimentSpec.  THE public entry point."""
    return _Job(0, spec).run_single()


def run_sweep(sweep: Union[Sweep, Sequence[ExperimentSpec]], *,
              batch: bool = True) -> List[RunResult]:
    """Execute a grid of specs; results in spec order.

    ``batch=True`` (default) replays shape-compatible compiled grid points
    as one vmapped program per group; ``batch=False`` forces sequential
    per-spec execution (the equivalence oracle in tests/benchmarks).

    Falling off the batched fast path is a ~3.6× per-spec cliff, so it is
    never silent: compiled grid points that can't batch (non-kernel
    optimizer, non-trivial topology — which includes elastic grouped
    traces) raise ONE RuntimeWarning per sweep naming the reasons, and
    every RunResult
    records the path that produced it in ``runtime["replay_path"]``
    ("batched" | "sequential" | "legacy" | "measure").
    """
    specs = list(sweep)
    jobs = [_Job(i, s) for i, s in enumerate(specs)]
    results: List[Optional[RunResult]] = [None] * len(jobs)

    groups: Dict = {}
    if batch:
        reasons: Dict[str, int] = {}
        for job in jobs:
            why = job.batch_exclusion()
            if why is not None:
                reasons[why] = reasons.get(why, 0) + 1
            key = job.batch_key()
            if key is not None:
                groups.setdefault(key, []).append(job)
        if reasons:
            detail = "; ".join(f"{n} spec(s): {why}"
                               for why, n in sorted(reasons.items()))
            warnings.warn(
                f"run_sweep: {sum(reasons.values())} of {len(jobs)} "
                f"spec(s) fall back from the batched (vmapped) sweep path "
                f"to sequential per-spec replay — {detail}. Sequential "
                f"replay is ~3.6x slower per spec; see "
                f"runtime['replay_path'] on each RunResult.",
                RuntimeWarning, stacklevel=2)

    done = set()
    for key, members in groups.items():
        if len(members) < 2:
            continue
        staged = [j.staged_batches() for j in members]
        if any(s is None for s in staged):
            staged = None
        sims = replay_batch(
            [j.trace for j in members],
            [j.spec.run for j in members],
            grad_fn=members[0].problem.grad_fn,
            init_params=members[0].problem.init,
            batch_fns=(None if staged else [j.batch_fn for j in members]),
            batches=staged,
            eval_fn=members[0].problem.eval_fn,
            eval_every=members[0].spec.eval_every)
        for job, sim in zip(members, sims):
            results[job.index] = _result(job.spec, job.trace, sim,
                                         job.problem, replay_path="batched")
            done.add(job.index)

    for job in jobs:
        if job.index not in done:
            results[job.index] = job.run_single()
    return results
