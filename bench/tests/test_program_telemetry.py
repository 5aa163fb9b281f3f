"""The readers of the program's own names and counters: the pulled-row
counters behind ``pull_distinct_share.whatif`` against an independent
count from the arrival schedule, and the named kernel and scopes in a
trace excerpt recorded on a TPU v5e."""

import collections
import json
import os
import sys

import jax
import numpy as np
import pytest

import harness as H
import run as R
import tracereduce as TR
from kinds import whatif as KW

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_excerpt_scoped.json")
METRIC = "pull_distinct_share.whatif"


def _distinct_rows(pulled_ts, K: int) -> np.ndarray:
    """Per event, the distinct ring rows among its pulled indices."""
    return np.array([len({int(t) % K for t in ev}) for ev in pulled_ts])


def _tiny_schedule(cell):
    from repro.core import trace as trace_mod
    trace = trace_mod.schedule(KW.run_config(cell.traffic),
                               cell.traffic["events"])
    return trace, trace.max_staleness + 1


@pytest.fixture
def fresh_counters(monkeypatch):
    from repro import telemetry
    counts = collections.Counter()
    monkeypatch.setattr(telemetry, "_counts", counts)
    return counts


def test_share_is_rows_over_slots(fresh_counters):
    read = H.metric_reader(METRIC)
    assert read({}) is None
    fresh_counters.update({"replay_ring.pull_slots": 300,
                           "replay_ring.pull_rows": 23})
    assert read({}) == pytest.approx(100.0 * 23 / 300)


def test_absent_without_the_program_counters(monkeypatch):
    """A program without ``repro.telemetry``, as before it had one."""
    import repro
    monkeypatch.delattr(repro, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert H.metric_reader(METRIC)({}) is None


def test_run_share_matches_the_schedule(fresh_counters, tiny_cell):
    """Over a tiny run: the warm-up's two segments and the window call's
    segments, counted again here from ``pulled_ts``."""
    cell = tiny_cell("tiny.whatif")
    line, out = R.execute(cell, 2 ** 31 + 29, 0.3, False,
                          jax.devices()[:1])
    assert line["correct"], line["checks"]
    trace, K = _tiny_schedule(cell)
    seg, done = out.notes["segment"], out.notes["events_replayed"]
    rows = _distinct_rows(trace.pulled_ts, K)
    replayed = np.concatenate([rows[:2 * seg], rows[:done]])
    assert fresh_counters["replay_ring.pull_slots"] == trace.c * len(replayed)
    assert fresh_counters["replay_ring.pull_rows"] == replayed.sum()
    assert H.metric_reader(METRIC)({}) == pytest.approx(
        100.0 * replayed.sum() / (trace.c * len(replayed)))


def _excerpt():
    with open(DATA) as f:
        return json.load(f)


def test_excerpt_names_the_kernel_and_the_relayout():
    d = _excerpt()
    ops = {int(k): [tuple(e) for e in v] for k, v in d["device_ops"].items()}
    r = TR.reduce_events(ops, [tuple(s) for s in d["host_spans"]])
    kernel = [n for n in r.op_seconds if n.startswith("%replay_ring_whatif")]
    assert len(kernel) == 1
    # the roofline reader, which matches the custom call, times the named
    # kernel and nothing else
    ctx = {"reduction": r, "event_bytes": 1000.0, "events": 36,
           "peaks": {"hbm_bytes_per_s": 1e9}}
    assert H.metric_reader("replay_ring_whatif_roofline")(ctx) == \
        pytest.approx(100.0 * 36e3 / (r.op_seconds[kernel[0]] * 1e9))
    scopes = d["op_names"].values()
    assert any(s.endswith("/replay_ring_whatif/pallas_call") for s in scopes)
    for scope in ("replay_ring.to_tiles", "replay_ring.from_tiles"):
        assert any("/engine.replay.scan/while/body/" in s
                   and f"/{scope}/" in s for s in scopes), scope


def test_excerpt_counters_match_the_schedule(tiny_cell):
    """The counters the chip run took at the window's edges equal a
    count over the window's events of the same tiny schedule."""
    d = _excerpt()
    trace, K = _tiny_schedule(tiny_cell("tiny.whatif"))
    lo, hi = d["window_events"]
    rows = _distinct_rows(trace.pulled_ts[lo:hi], K)
    assert d["counters"] == {"pull_slots": trace.c * (hi - lo),
                             "pull_rows": int(rows.sum())}
