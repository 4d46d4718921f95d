"""The benchmark's instance generators and plain references (CPU only)."""

from __future__ import annotations

import itertools
import json
import os

import numpy as np
import pytest

from perfbench.families import jobshop as js
from perfbench.families import rcpsp
from perfbench.reference import jobshop as js_ref
from perfbench.reference import rcpsp as rcpsp_ref

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


J30 = _config("rcpsp-j30")
TA = _config("jobshop-ta15x15")
SEEDS = [0, 1, 2**31 + 12345, 987654321987]


def _j30(seed, slot):
    grid = J30["grid"]
    return rcpsp.generate(J30["generator"], grid[slot % len(grid)],
                          np.random.default_rng([seed, slot]))


def _reach(n, arcs):
    r = np.eye(n, dtype=bool)
    for i, j in sorted(arcs, reverse=True):
        r[i] |= r[j]
    return r


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("slot", range(8))
def test_j30_follows_progen_parameters(seed, slot):
    p = J30["generator"]
    inst = _j30(seed, slot)
    n, k = p["jobs"], p["resources"]
    gp = J30["grid"][slot]
    # network: arc count from NC over n + 2 nodes, dummy arcs excluded
    assert len(inst.arcs) == int(np.floor(gp["nc"] * (n + 2) + 0.5)) \
        - p["start_jobs"] - p["finish_jobs"]
    assert len(set(inst.arcs)) == len(inst.arcs)
    assert all(i < j for i, j in inst.arcs)
    preds = np.bincount([j for _, j in inst.arcs], minlength=n)
    succs = np.bincount([i for i, _ in inst.arcs], minlength=n)
    assert preds.max() <= p["max_predecessors"]
    assert succs.max() <= p["max_successors"]
    assert (preds[:p["start_jobs"]] == 0).all()
    assert (preds[p["start_jobs"]:] >= 1).all()
    assert (succs[n - p["finish_jobs"]:] == 0).all()
    assert (succs[:n - p["finish_jobs"]] >= 1).all()
    # non-redundant: no arc is implied by a longer path
    for i, j in inst.arcs:
        rest = [a for a in inst.arcs if a != (i, j)]
        assert not _reach(n, rest)[i, j], (i, j)
    # durations, demands, RF
    d = inst.durations
    assert d.min() >= p["duration_range"][0]
    assert d.max() <= p["duration_range"][1]
    lo, hi = p["last_finish_window"]
    assert lo <= d.sum() + d.max() + 2 <= hi
    used = inst.usage > 0
    assert (used.sum(axis=0) == int(np.floor(gp["rf"] * k + 0.5))).all()
    counts = used.sum(axis=1)
    assert counts.max() - counts.min() <= 1
    assert inst.usage[used].min() >= p["demand_range"][0]
    assert inst.usage[used].max() <= p["demand_range"][1]
    # RS: capacity between the largest single demand and the ESS peak
    assert inst.rs == gp["rs"]
    es, _ = rcpsp_ref.longest_paths(d, inst.arcs)
    t = np.arange(int((es + d).max()))
    run = (es[None, :] <= t[:, None]) & (t[:, None] < (es + d)[None, :])
    peak = (run[None] * inst.usage[:, None, :]).sum(axis=2).max(axis=1)
    kmin = inst.usage.max(axis=1)
    want = kmin + np.floor(inst.rs * (peak - kmin) + 0.5).astype(int)
    assert (inst.capacity == want).all()
    if inst.rs == 1.0:
        assert rcpsp_ref.check(inst, es)[0]


def test_j30_replay_set_keeps_binding_resources():
    """The committed replay set is the first draw of every slot, so the
    resources lengthen some optima beyond the critical path, and there
    the integer program, not a priority rule, finds the optimum."""
    seed = J30["instance_seed"]
    binding = []
    for slot in range(len(J30["grid"])):
        inst = _j30(seed, slot)
        es, tail = rcpsp_ref.longest_paths(inst.durations, inst.arcs)
        cpm = int((es + inst.durations).max())
        rule = int((rcpsp_ref.serial_schedule(inst, -tail)
                    + inst.durations).max())
        opt = rcpsp_ref.optimum(inst)[0]
        assert cpm <= opt <= rule
        binding.append(opt > cpm)
    assert any(binding)


def test_j30_one_seed_one_instance():
    a, b, c = _j30(77, 1), _j30(77, 1), _j30(78, 1)
    for f in ("durations", "usage", "capacity"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert a.arcs == b.arcs and a.rs == b.rs
    assert not (np.array_equal(a.durations, c.durations) and a.arcs == c.arcs)


def test_j30_grid_covers_every_rf_at_both_resource_strengths():
    pts = {(g["rf"], g["rs"]) for g in J30["grid"]}
    assert pts == {(rf, rs) for rf in (0.25, 0.5, 0.75, 1.0)
                   for rs in (0.7, 1.0)}


@pytest.mark.parametrize("seed", SEEDS)
def test_taillard_follows_source(seed):
    p = TA["generator"]
    inst = js.generate(p, {}, np.random.default_rng([seed, 0]))
    assert inst.durations.shape == (p["jobs"], p["machines"])
    assert inst.durations.min() >= p["duration_range"][0]
    assert inst.durations.max() <= p["duration_range"][1]
    lo, hi = p["last_finish_window"]
    assert lo <= inst.durations.sum() + inst.durations.max() + 2 <= hi
    for row in inst.machines:
        assert sorted(row) == list(range(p["machines"]))


def test_taillard_one_seed_one_instance():
    p = TA["generator"]
    a = js.generate(p, {}, np.random.default_rng([5, 0]))
    b = js.generate(p, {}, np.random.default_rng([5, 0]))
    c = js.generate(p, {}, np.random.default_rng([6, 0]))
    assert np.array_equal(a.durations, b.durations)
    assert np.array_equal(a.machines, b.machines)
    assert not np.array_equal(a.durations, c.durations)
    # processing times spread over the whole range, about uniformly
    d = np.concatenate([js.generate(p, {}, np.random.default_rng([s, 0]))
                        .durations.ravel() for s in range(8)])
    assert abs(d.mean() - 50) < 3


def test_every_grid_point_compiles_to_one_shape():
    from repro.core.api import shape_signature
    for slot in range(len(J30["grid"])):
        sigs = {shape_signature(rcpsp.build(_j30(s, slot))[0])
                for s in (3, 4, 5)}
        assert len(sigs) == 1
    sigs = {shape_signature(js.build(js.generate(
        TA["generator"], {}, np.random.default_rng([s, 0])))[0])
        for s in (3, 4)}
    assert len(sigs) == 1


# -- plain references ------------------------------------------------------

def _tiny(seed):
    rng = np.random.default_rng(seed)
    n = 4
    arcs = [(0, 2), (1, 3)]
    d = rng.integers(1, 4, size=n)
    usage = rng.integers(0, 4, size=(2, n))
    usage[0, usage.sum(axis=0) == 0] = 1
    cap = usage.max(axis=1)                   # tight: resources bind
    return rcpsp.Instance(durations=d, arcs=arcs, usage=usage, capacity=cap,
                          nc=0.0, rf=0.0, rs=0.0)


def _brute_optimum(inst):
    h = int(inst.durations.sum())
    best = None
    for starts in itertools.product(range(h + 1), repeat=inst.n_jobs):
        ok, mk = rcpsp_ref.check(inst, starts)
        if ok and (best is None or mk < best):
            best = mk
    return best


@pytest.mark.parametrize("seed", [1, 4, 5])
def test_rcpsp_reference_optimum_matches_brute_force(seed):
    inst = _tiny(seed)
    mk, starts = rcpsp_ref.optimum(inst)
    assert rcpsp_ref.check(inst, starts) == (True, mk)
    assert mk == _brute_optimum(inst)
    es, _ = rcpsp_ref.longest_paths(inst.durations, inst.arcs)
    cpm = int((es + inst.durations).max())
    assert mk > cpm                # the integer program, not the bound, decides


def test_rcpsp_checker_rejects_broken_schedules():
    inst = rcpsp.Instance(durations=np.array([2, 3]), arcs=[(0, 1)],
                          usage=np.array([[2, 2]]), capacity=np.array([3]),
                          nc=0, rf=0, rs=0)
    assert rcpsp_ref.check(inst, [0, 2]) == (True, 5)
    assert not rcpsp_ref.check(inst, [0, 1])[0]          # precedence
    free = rcpsp.Instance(durations=np.array([2, 3]), arcs=[],
                          usage=np.array([[2, 2]]), capacity=np.array([3]),
                          nc=0, rf=0, rs=0)
    assert not rcpsp_ref.check(free, [0, 1])[0]          # capacity
    assert rcpsp_ref.check(free, [0, 2]) == (True, 5)
    assert not rcpsp_ref.check(free, [-1, 2])[0]


def test_jobshop_checker_rejects_broken_schedules():
    inst = js.Instance(machines=np.array([[0, 1], [1, 0]]),
                       durations=np.array([[2, 3], [2, 1]]))
    assert js_ref.check(inst, [0, 2, 0, 5]) == (True, 6)
    assert not js_ref.check(inst, [0, 1, 0, 5])[0]      # job order
    assert not js_ref.check(inst, [0, 2, 1, 5])[0]      # machine 1 overlap
    assert not js_ref.check(inst, [0, 2, 0])[0]         # wrong length
