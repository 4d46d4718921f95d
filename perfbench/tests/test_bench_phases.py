"""The readers of what the program reports about itself
(`perfbench.phases`): on hand-made results and events, on a program that
reports none of it, and on a trace recorded on a TPU v5e with the
program's own spans (two j30 proofs; CPU only here)."""

from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from perfbench import manifest, phases
from perfbench.trace import (HOST_PLANE, MODULE_LINE, SOLVE_SPAN,
                             WINDOW_MARK, Slices, Trace)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
S = 1e9   # ns per second
NEW = ("decompose_s.prove", "decompose_dispatches.prove",
       "chunk_superstep_ms.prove", "lockstep_waste.prove",
       "decompose_idle_share.prove")


def _result(**kw):
    base = dict(n_supersteps=10, n_sweeps=300, n_sweep_rounds=10,
                n_lanes=64, decompose_s=1.5, n_decompose_dispatches=511,
                search_s=0.05)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _run(results, disturbed, trace=None):
    return types.SimpleNamespace(
        answers=[types.SimpleNamespace(result=r) for r in results],
        spans=types.SimpleNamespace(solves=[
            types.SimpleNamespace(disturbed=d) for d in disturbed]),
        trace=trace)


def test_result_readers_skip_disturbed_solves():
    run = _run([_result(decompose_s=9.0, n_supersteps=1),
                _result(), _result(decompose_s=2.5, search_s=0.15,
                                   n_decompose_dispatches=601,
                                   n_sweeps=340, n_sweep_rounds=20,
                                   n_supersteps=30)],
               [True, False, False])
    assert phases.decompose_s(run) == pytest.approx(2.0)
    assert phases.decompose_dispatches(run) == pytest.approx(556.0)
    assert phases.chunk_superstep_ms(run) == pytest.approx(
        1e3 * 0.2 / 40)
    assert phases.lockstep_waste(run) == pytest.approx(
        100.0 * (1.0 - 640 / (64 * 30)))


RESULT_READERS = (phases.decompose_s, phases.decompose_dispatches,
                  phases.chunk_superstep_ms, phases.lockstep_waste)


def test_a_program_that_reports_nothing_reads_none():
    old = types.SimpleNamespace(n_supersteps=10, n_sweeps=300)
    run = _run([old, old], [False, False])
    for read in RESULT_READERS:
        assert read(run) is None
    # every solve disturbed: nothing to read
    assert phases.decompose_s(_run([_result()], [True])) is None


@pytest.mark.parametrize("field", ["decompose_s", "n_decompose_dispatches",
                                   "search_s", "n_sweep_rounds"])
def test_a_program_that_stops_reporting_a_field_fails_the_run(field):
    run = _run([_result(), _result(**{field: None})], [False, False])
    with pytest.raises(ValueError, match=field):
        for read in RESULT_READERS:
            read(run)
    # a disturbed solve is not read
    run = _run([_result(**{field: None}), _result()], [True, False])
    for read in RESULT_READERS:
        assert read(run) is not None


def _ev(plane, line, name, start_s, dur_s):
    return [plane, line, name, start_s * S, dur_s * S]


def test_decompose_idle_share_on_hand_made_events():
    dev = "/device:TPU:0"
    ev = [
        _ev(HOST_PLANE, "python3", WINDOW_MARK, 0.0, 0.0),
        _ev(HOST_PLANE, "python3", SOLVE_SPAN, 0.0, 10.0),
        _ev(HOST_PLANE, "python3", phases.DECOMPOSE_SPAN, 1.0, 5.0),
        _ev(HOST_PLANE, "python3", "repro.eps.dispatch", 1.0, 1.0),
        _ev(dev, MODULE_LINE, "jit_fixpoint(1)", 2.0, 1.0),
        _ev(dev, MODULE_LINE, "jit_run_chunk(2)", 7.0, 2.0),
    ]
    t = Trace.from_events(ev, 1)
    run = _run([_result()], [False], Slices([t, t]))
    # idle [0, 2) [3, 7) [9, 10) = 7 s; inside [1, 6): [1, 2) and [3, 6)
    assert phases.decompose_idle_share(run) == pytest.approx(400.0 / 7)
    # a slice that ends inside a decomposition holds its closed
    # dispatch spans but not the open decomposition span
    cut = Slices([Trace.from_events(
        [e for e in ev if e[2] != phases.DECOMPOSE_SPAN], 1)])
    # idle [0, 2) [3, 7) [9, 10) = 7 s; inside [1, 2)
    assert phases.decompose_idle_share(_run([_result()], [False], cut)) \
        == pytest.approx(100.0 / 7)
    bare = Slices([Trace.from_events(
        [e for e in ev if not e[2].startswith("repro.")], 1)])
    # a program from before the spans reads nothing; one that reports
    # decompose_s and records no span fails the run
    old = types.SimpleNamespace(n_supersteps=10, n_sweeps=300)
    assert phases.decompose_idle_share(_run([old], [False], bare)) is None
    with pytest.raises(ValueError, match="eps.decompose"):
        phases.decompose_idle_share(_run([_result()], [False], bare))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "j30_program_spans.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_readers_on_the_recorded_trace(recorded):
    t = Trace.from_events(recorded["events"], 1)
    results = [_result(**r) for r in recorded["results"]]
    run = _run(results, [False] * len(results), Slices([t]))
    names = {n for _, _, n in t.host}
    program = {n for n in names if n.startswith("repro.")}
    assert program == {"repro.solve.pool", "repro.eps.decompose",
                       "repro.eps.dispatch", "repro.solve.chunk",
                       "repro.solve.poll"}
    assert "jit_run_chunk" in dict(t.breakdown()["device_ops"])
    bench = manifest.Benchmark()
    values = {m: bench.metric_reader(m)(run) for m in NEW}
    assert all(v is not None for v in values.values()), values
    assert 0.0 < values["decompose_idle_share.prove"] <= 100.0
    assert 0.0 <= values["lockstep_waste.prove"] < 100.0
    # every proof of the recorded pair splits to the EPS target: two
    # children per split plus the root
    assert values["decompose_dispatches.prove"] >= 511
    dispatches = [iv for iv in t.host if iv[2] == "repro.eps.dispatch"]
    assert len(dispatches) == sum(r.n_decompose_dispatches
                                  for r in results)
