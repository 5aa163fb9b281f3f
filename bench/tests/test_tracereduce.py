"""The reduction from a profiler trace to busy time, per-op time and
labelled idle gaps, on a stretch of a trace recorded on a TPU v5e and on
hand-made events."""

import json
import os

import numpy as np
import pytest

import tracereduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_excerpt.json")


def _load():
    with open(DATA) as f:
        d = json.load(f)
    ops = {int(k): [tuple(e) for e in v] for k, v in d["device_ops"].items()}
    return ops, [tuple(s) for s in d["host_spans"]]


def test_hand_made_events():
    ops = {0: [("%a", 10.0, 30.0), ("%b", 20.0, 40.0),
               ("%w = while(x)", 50.0, 70.0), ("%c", 55.0, 60.0),
               ("%d", 90.0, 120.0)]}
    spans = [(TR.WINDOW_SPAN, 0.0, 100.0), ("bench.block", 0.0, 9.0),
             ("bench.dispatch", 40.0, 50.0), ("bench.data_next", 70.0, 85.0)]
    r = TR.reduce_events(ops, spans)
    assert r.window_s == pytest.approx(100e-9)
    # busy: [10, 40] ∪ [50, 70] ∪ [90, 100]
    assert r.busy_s == pytest.approx(60e-9)
    assert r.idle_share == pytest.approx(0.4)
    # the loop's own interval is busy, but its time is its body's
    assert "%w" not in r.op_seconds
    assert r.op_seconds["%d"] == pytest.approx(10e-9)
    # gaps [0, 10], [40, 50], [70, 90], each named by the span that
    # overlaps it most
    got = [(n, round(s * 1e9, 6)) for n, s in r.gaps]
    assert got == [("bench.data_next", 20.0), ("bench.block", 10.0),
                   ("bench.dispatch", 10.0)]


def test_recorded_trace_against_a_timeline():
    ops, spans = _load()
    r = TR.reduce_events(ops, spans)
    (w0, w1), = [(a, b) for n, a, b in spans if n == TR.WINDOW_SPAN]
    # an independent count: a 1 ns timeline of the window
    busy = np.zeros(int(w1 - w0), bool)
    for _, a, b in ops[0]:
        lo, hi = int(max(a, w0) - w0), int(min(b, w1) - w0)
        if hi > lo:
            busy[lo:hi] = True
    assert r.busy_s == pytest.approx(busy.sum() * 1e-9, rel=1e-3, abs=2e-9)
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert sum(s for _, s in r.gaps) + r.busy_s == pytest.approx(r.window_s)
    assert r.gaps == sorted(r.gaps, key=lambda g: -g[1])
    # the Pallas kernel is an anonymous tpu_custom_call
    assert r.seconds_matching('custom_call_target="tpu_custom_call"') > 0
    bd = r.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce_events({0: [("%a", 0.0, 1.0)]}, [])
