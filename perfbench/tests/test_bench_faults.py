"""A whole run of the harness on the CPU, at a size a test run holds,
with the chip check skipped: sound runs come out correct, and the
control and each planted fault of the timed path come out not correct.
"""

from __future__ import annotations

import dataclasses
import json
import os

import pytest

from perfbench import manifest, run

J30_TINY = {
    "name": "rcpsp-tiny", "family": "rcpsp", "dtype": "int32",
    "instance_seed": 2,
    "generator": {
        "jobs": 8, "resources": 2, "start_jobs": 2, "finish_jobs": 2,
        "max_predecessors": 3, "max_successors": 3,
        "duration_range": [1, 10], "demand_range": [1, 10],
        "last_finish_window": [33, 64]},
    "grid": [{"nc": 1.2, "rf": 0.5, "rs": 0.7}, {"nc": 1.5, "rf": 1.0, "rs": 0.7}],
}
TA_TINY = {
    "name": "jobshop-tiny", "family": "jobshop", "dtype": "int32",
    "instance_seed": 1,
    "generator": {"jobs": 4, "machines": 3, "duration_range": [1, 99],
                  "last_finish_window": [545, 576]},
    "grid": [{}],
}
SOLVE = {"preset": "prove", "overrides": {"n_lanes": 8, "eps_target": 16,
                                          "chunk": 32, "timeout_s": 4}}
PROVE = {"replay": 2, "mode": "prove",
         "metric": "proof_s", "solve": SOLVE,
         "limits": {"errors": 0, "unsolved": 0, "infeasible": 0,
                    "objective_mismatch": 0, "not_optimal": 0,
                    "wrong_optimum": 0}}
ANYTIME = {"replay": 2, "mode": "anytime",
           "metric": "anytime_s",
           "solve": {"preset": "prove", "overrides": dict(
               SOLVE["overrides"], max_supersteps=64)},
           "limits": {"errors": 0, "unsolved": 0, "infeasible": 0,
                      "objective_mismatch": 0}}


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    """A checkout with two tiny cells, and the persistent compilation
    cache left off (it is process-wide and would outlive the test)."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    for sub, name, body in (("configs", "rcpsp-tiny", J30_TINY),
                            ("configs", "jobshop-tiny", TA_TINY),
                            ("traffic", "prove-tiny", PROVE),
                            ("traffic", "anytime-tiny", ANYTIME)):
        d = tmp_path / "perfbench" / sub
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{name}.json").write_text(json.dumps(body))
    spec = {
        "configs": [
            {"name": "rcpsp-tiny", "file": "perfbench/configs/rcpsp-tiny.json"},
            {"name": "jobshop-tiny",
             "file": "perfbench/configs/jobshop-tiny.json"}],
        "workloads": [
            {"name": "prove", "config": "rcpsp-tiny", "traffic": "prove-tiny",
             "chips": 1},
            {"name": "anytime", "config": "jobshop-tiny",
             "traffic": "anytime-tiny", "chips": 1}],
        "end_to_end": [
            {"name": "proof_s", "unit": "s", "workloads": ["prove"]},
            {"name": "anytime_s", "unit": "s", "workloads": ["anytime"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return manifest.Benchmark(str(tmp_path))


def _run(bench, cell, seed=11, **kw):
    return run.run_cell(bench, cell, seed, 0.0, False, require_tpu=False,
                        **kw)


@pytest.mark.parametrize("cell", ["prove", "anytime"])
def test_sound_run_is_correct(bench, cell):
    out = _run(bench, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", f"{cell.replace('prove', 'proof')}_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ["prove", "anytime"])
def test_control_is_not_correct(bench, cell):
    """The control: the program given every Cumulative capacity one
    higher.  It has to reach the comparison and fail it there."""
    out = _run(bench, cell, control=True)
    assert out["attempted"] >= 1
    assert not out["correct"], out["checks"]
    assert out["checks"]["infeasible"]["value"] >= 1, out["checks"]


def _frozen_step(cm, subs_lb, subs_ub, opts, st, gbest, pool_head):
    return st, pool_head


def _altered(derive):
    def wrapped(*a, **k):
        res = derive(*a, **k)
        if res.objective is not None:
            res = dataclasses.replace(res, objective=res.objective - 1)
        return res
    return wrapped


def _half_pool(decompose):
    def wrapped(cm, target, opts=None):
        lb, ub = decompose(cm, target, opts)
        half = lb.shape[0] // 2
        return lb[half:], ub[half:]
    return wrapped


FAULTS = {
    # the superstep returns the lane state it was given
    "state_unchanged": ("repro.core.search", "lanes_step",
                        lambda orig: _frozen_step),
    # an answer altered where it is produced
    "answer_altered": ("repro.core.api", "derive_result", _altered),
    # half of the batch of subproblems left out
    "half_pool": ("repro.core.eps", "decompose", _half_pool),
}
CASES = [("prove", "state_unchanged"), ("prove", "answer_altered"),
         ("prove", "half_pool"), ("anytime", "state_unchanged"),
         ("anytime", "answer_altered")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_not_correct(bench, monkeypatch, cell, fault):
    import importlib
    mod_name, attr, make = FAULTS[fault]
    mod = importlib.import_module(mod_name)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    out = _run(bench, cell, seed=12)
    assert not out["correct"], (fault, out["checks"])
