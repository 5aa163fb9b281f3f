"""The program's own spans and counters.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` (a
``StepTraceAnnotation`` when ``step`` is given): when a profiler trace is
being taken it lands on the host plane, on the same clock as the device
operations; otherwise it costs about a microsecond.  Spans mark coarse
host phases (a replay segment, not an event).

``count(name, n)`` adds to an in-process integer counter and
``counters()`` reads them all; a caller measures a stretch of work by the
difference of two readings.  ``mark(name, value)`` sets a counter outright,
for readings that record the latest state rather than a total.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional

import jax

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
