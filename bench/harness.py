"""The benchmark's shared machinery: the manifest, a cell's files, the chip
check, compile counting, host spans and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found here by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, as run, with their source
    bench/traffic/<traffic>.json    the mix; its "kind" names its runner
    bench/kinds/<kind>.py           one runner per kind of traffic
    bench/limits/<workload>.json    the limits that decide ``correct``
    bench/metrics/<metric>.py       one reader per per-layer metric
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# manifest and a cell's files
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    workload: dict            # the BENCHMARK.json entry
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json
    limits: dict              # bench/limits/<workload>.json
    end_to_end: List[dict]    # the end-to-end metrics this cell reports
    per_layer: List[dict]     # the per-layer metrics this cell reports

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell_name: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(manifest: dict, name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise SystemExit(f"bench: no workload {name!r}; known: "
                         f"{sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[wl["config"]]
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(
        workload=wl,
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        wl["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise SystemExit(f"bench: cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str, bench_dir: str = BENCH_DIR):
    return _load_module(os.path.join(bench_dir, "kinds", kind + ".py"),
                        f"bench_kind_{kind}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(ctx) -> float | None`` of ``bench/metrics/<name>.py``."""
    mod = _load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------
def require_chip(chips: int):
    """The devices a cell runs on; exits non-zero without a TPU or with
    fewer chips than the cell asks for.  There is no fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX reports platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count, as the CPU's does not)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_record(devices, memory_peak: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


# ---------------------------------------------------------------------------
# compilations and host spans
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts XLA backend compilations while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0

        def listen(event, duration, **_):
            if self.active and event == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


class QuietGC:
    """Python's cycle collector off for the window, with everything made
    before it frozen out of later collections: a collection of the
    interpreter's whole heap holds the host for up to a second, at a
    moment no run can choose."""

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.disable()
        return self

    def __exit__(self, *exc):
        gc.enable()
        gc.unfreeze()
        return False


class Spans:
    """Host spans of the harness: each is a ``TraceAnnotation`` in the
    profiler's trace (when one is taken) and a sum of host seconds."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        import jax
        self._owner, self._name = owner, name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        s = self._owner.seconds
        s[self._name] = s.get(self._name, 0.0) + dt
        return False


# ---------------------------------------------------------------------------
# the outcome of one run, and its printed form
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]     # besides setup_s
    checks: List[Check]
    memory_peak: int
    layer_ctx: dict                  # what the per-layer readers read
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(cell: Cell, outcome: Outcome, devices, trace: bool,
                per_layer: Optional[Dict[str, float]] = None,
                device_extra: Optional[dict] = None,
                breakdown: Optional[dict] = None) -> dict:
    """The last line of standard output, keys as the contract names them;
    the compared numbers come last, under ``checks``."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = (per_layer or {}).get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = device_record(devices, outcome.memory_peak)
    device.update(device_extra or {})
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def print_checks(checks: List[Check]) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error."""
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
