"""From a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes (``/device:TPU:<n>``) carry one event per XLA operation on
their ``XLA Ops`` line; host planes carry the harness's
``TraceAnnotation`` spans (names starting ``bench.``) on the thread that
ran them.  Both are on one clock.

Within the ``bench.window`` span, a device is busy while any operation
runs on it: busy time is the union of its operation intervals, and each
gap in that union is an idle stretch, labelled by the harness span that
overlaps it most (what the host was doing while the device waited).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# an operation that only holds others (a loop, a branch, a call): busy,
# but its time is counted under the operations it runs
CONTAINER = re.compile(r"\s(while|conditional|call)\(")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    window_s: float                      # length of the traced window
    busy_s: float                        # union of op intervals, mean/device
    devices: int
    op_seconds: Dict[str, float]         # per op name, mean over devices
    gaps: List[Tuple[str, float]]        # idle stretches, longest first
    spans: Dict[str, float]              # host seconds per harness span
    op_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, pattern: str) -> float:
        """Device seconds of the operations whose HLO text matches."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items()
                   if rx.search(self.op_text.get(n, n)))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]]) -> Reduction:
    """The reduction on plain events: per device ``(name, start_ns,
    end_ns)`` operations, and host ``(name, start_ns, end_ns)`` spans."""
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    if not device_ops:
        raise ValueError("the trace holds no device operations")
    busy_total = 0.0
    op_ns: Dict[str, float] = {}
    op_text: Dict[str, str] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in sorted(device_ops):
        clipped = []
        for text, a, b in device_ops[dev]:
            iv = _clip((a, b), w0, w1)
            if iv is None:
                continue
            clipped.append(iv)
            if CONTAINER.search(text):
                continue          # a loop's time is its body operations'
            name = text.split(" = ", 1)[0]
            op_text[name] = text
            op_ns[name] = op_ns.get(name, 0.0) + iv[1] - iv[0]
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(device_ops)
    inner = [(n, a, b) for n, a, b in host_spans
             if n != WINDOW_SPAN and _clip((a, b), w0, w1)]
    labelled = []
    for g in gaps:
        best = max(inner, key=lambda s: _overlap(g, (s[1], s[2])),
                   default=None)
        name = (best[0] if best is not None
                and _overlap(g, (best[1], best[2])) > 0 else "none")
        labelled.append((name, (g[1] - g[0]) * 1e-9 / n_dev))
    labelled.sort(key=lambda x: -x[1])
    spans: Dict[str, float] = {}
    for n, a, b in inner:
        iv = _clip((a, b), w0, w1)
        spans[n] = spans.get(n, 0.0) + (iv[1] - iv[0]) * 1e-9
    return Reduction(window_s=(w1 - w0) * 1e-9,
                     busy_s=busy_total * 1e-9 / n_dev, devices=n_dev,
                     op_seconds={k: v * 1e-9 / n_dev
                                 for k, v in op_ns.items()},
                     gaps=labelled, spans=spans, op_text=op_text)


def read_events(xplane_path: str):
    """``(device_ops, host_spans)`` of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            device_ops[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX)]
    device_ops = {k: v for k, v in device_ops.items() if v}
    return device_ops, host_spans


def reduce_trace(trace_dir: str) -> Reduction:
    return reduce_events(*read_events(find_xplane(trace_dir)))
