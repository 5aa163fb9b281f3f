"""The program's own spans and counters.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` (a
``StepTraceAnnotation`` when ``step`` is given): when a profiler trace is
being taken it lands on the host plane, on the same clock as the device
operations; otherwise it costs about a microsecond.  Spans mark coarse
host phases (a replay segment, not an event).

``count(name, n)`` adds to an in-process integer counter and
``counters()`` reads them all; a caller measures a stretch of work by the
difference of two readings.  ``mark(name, value)`` sets a counter outright,
for readings that record the latest state rather than a total.  A jitted
step reports device-side counts in its metrics under ``"counts"`` and
``"maxima"``, keyed by counter name; ``add_step_counts`` keeps their totals
on the device and ``record_step_counts`` adds them to the counters.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional

import jax
import jax.numpy as jnp

_counts: Dict[str, int] = collections.Counter()


def span(name: str, step: Optional[int] = None):
    """A host span named ``name``; a step span numbered ``step`` if given."""
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=int(step))


def count(name: str, n: int = 1) -> None:
    _counts[name] += int(n)


def mark(name: str, value: int) -> None:
    _counts[name] = int(value)


def counters() -> Dict[str, int]:
    """A snapshot of every counter."""
    return dict(_counts)


def add_step_counts(acc, metrics):
    """Running totals, on the device, of the counters a jitted step
    reports in its metrics: each leaf of ``metrics["counts"]`` summed
    whole, each of ``metrics["maxima"]`` maxed, under the counter named by
    its key.  No host sync; :func:`record_step_counts` reads them once."""
    now = {"counts": {k: jnp.sum(v) for k, v in
                      metrics.get("counts", {}).items()},
           "maxima": {k: jnp.max(v) for k, v in
                      metrics.get("maxima", {}).items()}}
    if acc is None:
        return now
    return {"counts": {k: acc["counts"][k] + v
                       for k, v in now["counts"].items()},
            "maxima": {k: jnp.maximum(acc["maxima"][k], v)
                       for k, v in now["maxima"].items()}}


def record_step_counts(acc) -> None:
    """Adds totals of :func:`add_step_counts` to the counters (one host
    read); a maximum is kept where it is larger than the counter's."""
    if acc is None:
        return
    acc = jax.device_get(acc)
    for k, v in acc["counts"].items():
        count(k, int(v))
    for k, v in acc["maxima"].items():
        mark(k, max(_counts.get(k, 0), int(v)))

