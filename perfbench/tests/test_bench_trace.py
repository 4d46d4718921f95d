"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (two j30 solves, module events only; CPU only here)."""

from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from perfbench import manifest
from perfbench.trace import (HOST_PLANE, MODULE_LINE, SOLVE_SPAN,
                             WINDOW_MARK, Slices, Trace)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
S = 1e9   # ns per second


def _ev(plane, line, name, start_s, dur_s):
    return [plane, line, name, start_s * S, dur_s * S]


def _synthetic():
    dev = "/device:TPU:0"
    return [
        _ev(HOST_PLANE, "python3", WINDOW_MARK, 0.0, 0.0),
        _ev(HOST_PLANE, "python3", SOLVE_SPAN, 0.0, 5.0),
        _ev(HOST_PLANE, "python3", "PjitFunction(fixpoint)", 0.1, 0.3),
        _ev(HOST_PLANE, "python3", "np.asarray(jax.Array)", 2.2, 0.5),
        _ev(HOST_PLANE, "other", "noise", -1.0, 9.0),
        _ev(dev, MODULE_LINE, "jit_fixpoint(1)", 0.5, 1.0),
        _ev(dev, MODULE_LINE, "jit_fixpoint(1)", 1.0, 1.0),
        _ev(dev, MODULE_LINE, "jit__unknown(7)", 3.0, 1.0),
        _ev(dev, "XLA Ops", "%fusion", 3.0, 0.5),
        _ev("/device:TPU:1", MODULE_LINE, "jit__unknown(7)", 3.0, 2.0),
    ]


def test_busy_idle_and_breakdown_on_hand_made_events():
    t = Trace.from_events(_synthetic(), 1)
    assert t.window_s() == pytest.approx(5.0)
    assert t.busy_s() == pytest.approx(2.5)         # [0.5, 2) and [3, 4)
    assert t.idle_share() == pytest.approx(50.0)
    b = t.breakdown()
    assert b["device_ops"] == [["jit_fixpoint", pytest.approx(2.0)],
                               ["jit__unknown", pytest.approx(1.0)]]
    idle = dict((k, v) for k, v in b["idle_gaps"])
    # gaps [0, 0.5), [2, 3), [4, 5): the dispatch covers the first, the
    # host read most of the second, plain Python the third
    assert idle == {"PjitFunction(fixpoint)": pytest.approx(0.5),
                    "np.asarray(jax.Array)": pytest.approx(1.0),
                    "python": pytest.approx(1.0)}


def test_two_chips_average_busy_and_take_the_busiest_idle():
    t = Trace.from_events(_synthetic(), 2)
    assert t.busy_s() == pytest.approx((2.5 + 2.0) / 2)
    assert t.idle_share() == pytest.approx(50.0)


def test_a_slice_cut_inside_a_solve_ends_at_its_last_event():
    ev = [e for e in _synthetic() if e[2] != SOLVE_SPAN]
    t = Trace.from_events(ev, 1)
    assert t.window_s() == pytest.approx(4.0)       # the last module ends
    assert t.busy_s() == pytest.approx(2.5)


def test_slices_add_up():
    first = Trace.from_events(_synthetic(), 1)
    second = Trace.from_events(_synthetic(), 2)
    both = Slices([first, second])
    assert both.window_s() == pytest.approx(10.0)
    assert both.busy_s() == pytest.approx(2.5 + 2.25)
    assert both.idle_share() == pytest.approx(50.0)
    dev = dict((k, v) for k, v in both.breakdown()["device_ops"])
    assert dev == {"jit_fixpoint": pytest.approx(4.0),
                   "jit__unknown": pytest.approx(2.0)}
    assert sum(v for _, v in both.breakdown()["idle_gaps"]) \
        == pytest.approx(5.0)


def test_missing_planes_raise():
    with pytest.raises(ValueError):
        Trace.from_events(_synthetic(), 3)
    unmarked = [e for e in _synthetic()
                if e[2] not in (SOLVE_SPAN, WINDOW_MARK)]
    with pytest.raises(ValueError):
        Trace.from_events(unmarked, 1)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "j30_two_solves.json.gz"), "rt") as f:
        return json.load(f)


def _union_length(intervals, lo, hi):
    pts = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                 if min(e, hi) > max(s, lo))
    total, end = 0.0, lo
    for s, e in pts:
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_recorded_trace(recorded):
    ev = recorded["events"]
    t = Trace.from_events(ev, 1)
    mods = [(e[3] / S, (e[3] + e[4]) / S) for e in ev
            if e[0] == "/device:TPU:0" and e[1] == MODULE_LINE]
    solves = [(e[3] / S, (e[3] + e[4]) / S) for e in ev
              if e[2] == SOLVE_SPAN]
    assert len(solves) == 2
    assert t.start == pytest.approx(min(s for s, _ in solves))
    assert t.busy_s() == pytest.approx(
        _union_length(mods, t.start, t.end))
    assert 0.0 < t.busy_s() < t.window_s()
    assert 0.0 < t.idle_share() < 100.0
    names = [n for n, _ in t.breakdown()["device_ops"]]
    assert "jit_fixpoint" in names and "jit__unknown" in names
    assert sum(v for _, v in t.breakdown()["idle_gaps"]) \
        == pytest.approx(t.window_s() - t.busy_s())


def test_idle_reader_on_the_recorded_trace(recorded):
    t = Trace.from_events(recorded["events"], 1)
    run = types.SimpleNamespace(trace=Slices([t]))
    bench = manifest.Benchmark()
    assert bench.metric_reader("idle_share.prove")(run) == t.idle_share()
