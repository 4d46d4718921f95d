"""The benchmark's spans around the program's layers (CPU only)."""

from __future__ import annotations

import types

import numpy as np
import pytest

from perfbench.families import rcpsp
from perfbench.spans import Spans, pre_search_s, superstep_ms

TINY = {"jobs": 8, "resources": 2, "start_jobs": 2, "finish_jobs": 2,
        "max_predecessors": 3, "max_successors": 3,
        "duration_range": [1, 10], "demand_range": [1, 10],
        "last_finish_window": [33, 64]}


def _solver_and_model():
    from repro.solver import Solver

    inst = rcpsp.generate(TINY, {"nc": 1.2, "rf": 0.5, "rs": 0.7},
                          np.random.default_rng([3, 0]))
    cm, _ = rcpsp.build(inst)
    solver = Solver(n_lanes=8, eps_target=16, chunk=32)
    solver.solve(cm)                                 # compile first
    return solver, cm


def _spans(slices, calls):
    return Spans(slices, lambda k: calls.append(f"start {k}"),
                 lambda: calls.append("stop"))


def test_spans_time_the_layers_and_cut_the_slice_once():
    from repro.core import api, eps

    solver, cm = _solver_and_model()
    fixpoint, runner_call = eps.fixpoint, api.CompiledRunner.__call__

    calls = []
    spans = _spans([{"at": "window", "seconds": 0.0}], calls)
    spans.start()
    answers = []
    for _ in range(3):
        spans.begin_solve()
        res = solver.solve(cm)
        spans.end_solve()
        answers.append(types.SimpleNamespace(result=res))
    spans.close()

    assert calls == ["start 0", "stop"]
    assert spans.done()
    assert [s.disturbed for s in spans.solves] == [True, False, False]
    assert eps.fixpoint is fixpoint
    assert api.CompiledRunner.__call__ is runner_call
    for s in spans.solves:
        assert s.t_call < s.t_first_runner < s.t_done
        assert 0.0 < s.runner_s <= s.t_done - s.t_first_runner
    run = types.SimpleNamespace(spans=spans, answers=answers)
    kept = spans.solves[1:]
    assert pre_search_s(run) == sum(
        s.t_first_runner - s.t_call for s in kept) / 2
    steps = sum(a.result.n_supersteps for a in answers[1:])
    assert superstep_ms(run) == 1e3 * sum(s.runner_s for s in kept) / steps


def test_a_window_shorter_than_the_slice_disturbs_nothing():
    calls = []
    spans = _spans([{"at": "window", "seconds": 3600.0}], calls)
    spans._install = lambda: None
    spans.start()
    spans.begin_solve()
    spans.end_solve()
    spans.close()
    assert calls == ["start 0", "stop"]
    assert spans.solves[0].disturbed       # the slice was still open
    assert spans.done()


def test_unknown_slice_kind_raises():
    with pytest.raises(ValueError):
        _spans([{"at": "search", "seconds": 1}], [])
