"""The span recorder (`repro.obs`) and what the solver records: the span
tree, the decomposition's dispatch count, the lockstep sweep rounds on
every backend, the named chunk runner and the device scopes, and results
that do not depend on recording."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, solver
from repro.core import api, eps, search as S
from repro.core.backend import get_backend
from repro.core.models import rcpsp


@pytest.fixture(scope="module")
def cm():
    inst = rcpsp.generate(5, n_resources=2, seed=3, edge_prob=0.3)
    return rcpsp.build_model(inst)[0].compile()


def _solver():
    return solver.Solver(solver.SolveConfig.preset(
        "prove", n_lanes=4, eps_target=8, chunk=16, max_depth=128))


def _by_id(rec):
    return {s.span_id: s for s in rec.spans}


# -- the recorder ---------------------------------------------------------

def test_span_tree_parents_solve_ids_and_self_time():
    with obs.Recorder() as rec:
        for _ in range(2):
            with obs.solve():
                with obs.span("a"):
                    time.sleep(0.002)
                    with obs.span("b"):
                        time.sleep(0.003)
                with obs.span("c"):
                    pass
    names = [s.name for s in rec.spans]
    assert names == [obs.SOLVE, "a", "b", "c"] * 2
    ids = _by_id(rec)
    for root in rec.named(obs.SOLVE):
        assert root.parent_id is None
        kids = rec.children(root)
        assert [k.name for k in kids] == ["a", "c"]
        a = kids[0]
        (b,) = rec.children(a)
        assert rec.self_ns(a) == a.duration_ns - b.duration_ns
        assert rec.self_ns(b) == b.duration_ns > 0
        assert rec.self_ns(root) == root.duration_ns - sum(
            k.duration_ns for k in kids)
    assert len({s.solve_id for s in rec.named(obs.SOLVE)}) == 2
    for s in rec.spans:
        top = s
        while top.parent_id is not None:
            top = ids[top.parent_id]
        assert top.name == obs.SOLVE and s.solve_id == top.solve_id
        assert s.start_ns <= s.end_ns


def test_nothing_is_recorded_without_an_open_recorder():
    idle = obs.Recorder()
    with obs.solve():
        with obs.span("a"):
            pass
    assert idle.spans == [] and obs._open is None
    with obs.Recorder():
        with pytest.raises(RuntimeError):
            obs.Recorder().__enter__()
    assert obs._open is None


def test_spans_reach_a_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.solve():
            with obs.span("obs.test.outer"):
                jnp.ones(4).block_until_ready()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert "obs.test.outer" in names
    # the root is kept by a recorder only: over a whole solve it would
    # cover every idle gap of the trace
    assert obs.SOLVE not in names


# -- what the solver records ------------------------------------------------

def test_solver_span_tree(cm):
    sv = _solver()
    sv.solve(cm)                                   # compile outside
    with obs.Recorder() as rec:
        res = [sv.solve(cm) for _ in range(2)]
    roots = rec.named(obs.SOLVE)
    assert len(roots) == 2
    assert len({r.solve_id for r in roots}) == 2
    ids = _by_id(rec)
    parent = {"repro.solve.pool": obs.SOLVE,
              "repro.eps.decompose": "repro.solve.pool",
              "repro.eps.dispatch": "repro.eps.decompose",
              "repro.solve.chunk": obs.SOLVE, "repro.solve.poll": obs.SOLVE}
    for s in rec.spans:
        assert s.name.startswith(obs.PREFIX), s.name
        if s.name != obs.SOLVE:
            assert ids[s.parent_id].name == parent[s.name], s.name
    for root, r in zip(roots, res):
        under = [s for s in rec.spans if s.solve_id == root.solve_id]
        (dec,) = [s for s in under if s.name == "repro.eps.decompose"]
        chunks = [s for s in under if s.name == "repro.solve.chunk"]
        dispatches = [s for s in under if s.name == "repro.eps.dispatch"]
        # the root's fixpoint and the split loop, each with its read-back
        assert chunks and len(dispatches) == r.n_decompose_dispatches == 2
        (pool,) = [s for s in under if s.name == "repro.solve.pool"]
        assert r.decompose_s * 1e9 >= pool.duration_ns >= dec.duration_ns
        assert r.search_s * 1e9 >= sum(c.duration_ns for c in chunks)
        assert r.decompose_s + r.search_s <= r.wall_s


def test_decomposition_dispatch_counter_counts_fixpoint_calls(
        cm, monkeypatch):
    calls = []
    real = eps.fixpoint

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(eps, "fixpoint", counted)
    stats = {}
    with obs.Recorder() as rec:
        eps.decompose(cm, 8, stats=stats)
    (dec,) = rec.named("repro.eps.decompose")
    # one fixpoint call for the root, then the split loop's one dispatch
    # however many splits it makes
    assert len(calls) == 1 and stats["dispatches"] == 2
    assert stats["splits"] == 7
    dispatches = rec.named("repro.eps.dispatch")
    assert len(dispatches) == stats["dispatches"]
    assert all(d.parent_id == dec.span_id for d in dispatches)
    # a second decomposition counts afresh into the same dict
    eps.decompose(cm, 8, stats=stats)
    assert stats["dispatches"] == 2 and len(calls) == 2


def test_chunk_runner_calls_are_search_only(cm, monkeypatch):
    """The decomposition's split loop is compiled apart from the chunk
    runners: a wrapper of `CompiledRunner.__call__` sees one call per
    `repro.solve.chunk` span and none inside the decomposition."""
    sv = _solver()
    sv.solve(cm)                                   # compile outside
    calls = []
    real = api.CompiledRunner.__call__

    def counted(runner, *a):
        calls.append(obs._open._stack()[-1].name)
        return real(runner, *a)

    monkeypatch.setattr(api.CompiledRunner, "__call__", counted)
    with obs.Recorder() as rec:
        res = sv.solve(cm)
    assert res.n_decompose_splits > 0
    assert calls == ["repro.solve.chunk"] * len(rec.named("repro.solve.chunk"))


def test_results_do_not_depend_on_the_recorder(cm):
    sv = _solver()
    off = sv.solve(cm)
    with obs.Recorder() as rec:
        on = sv.solve(cm)
    assert rec.spans
    for f in ("status", "objective", "n_nodes", "n_fails", "n_sols",
              "n_sweeps", "n_sweep_rounds", "n_lanes", "n_supersteps",
              "complete", "n_decompose_dispatches", "n_decompose_splits",
              "n_decompose_sweep_rounds"):
        assert getattr(on, f) == getattr(off, f), f
    assert (on.solution == off.solution).all()
    assert off.status == solver.OPTIMAL


# -- lockstep sweep rounds on every backend --------------------------------

BACKENDS = [("gather", {}), ("scatter", {}),
            ("pallas", {"lane_tile": 4}),
            ("pallas_resident", {"supersteps_per_launch": 1})]


@pytest.mark.parametrize("backend,bopts", BACKENDS,
                         ids=[b for b, _ in BACKENDS])
def test_sweep_rounds_are_the_slowest_lane_each_superstep(cm, backend,
                                                          bopts):
    opts = S.SearchOptions(var_strategy=S.MIN_LB, max_depth=64,
                           backend=backend,
                           backend_opts=tuple(bopts.items()))
    sl, su = (jnp.asarray(x) for x in eps.decompose(cm, 8, opts))
    st, gbest, _, it, head = api._init_carry(cm, 8, opts)
    head = head[0]
    swept = 0
    for _ in range(6):
        if backend == "pallas_resident":
            be = get_backend(backend, **bopts)
            new, gbest, it, head, _ = be.superstep_launch(
                cm, sl, su, st, gbest, it, head, opts=opts)
        else:
            new, head = S.lanes_step(cm, sl, su, opts, st, gbest, head)
            gbest = jnp.minimum(gbest, jnp.min(new.best_obj))
        sweeps = np.asarray(new.n_sweeps - st.n_sweeps)
        rounds = np.asarray(new.n_sweep_rounds - st.n_sweep_rounds)
        assert (rounds == sweeps.max()).all(), (sweeps, rounds)
        swept += int(sweeps.sum())
        st = new
    assert swept > 0
    t = S.lane_totals(st)
    assert t["n_lanes"] == 8
    assert t["n_sweep_rounds"] * t["n_lanes"] >= t["n_sweeps"]


def test_sweep_rounds_reach_every_result(cm):
    sv = _solver()
    one = sv.solve(cm)
    many = sv.solve_many([cm, cm])
    assert one.n_lanes == 4
    assert one.n_sweep_rounds * one.n_lanes >= one.n_sweeps > 0
    for r in many:
        assert (r.n_sweep_rounds, r.n_lanes, r.n_sweeps) == \
            (one.n_sweep_rounds, one.n_lanes, one.n_sweeps)


# -- the chunk runner's name and the device scopes -------------------------

SCOPES = ("superstep.dispatch_pool", "superstep.lane_load",
          "superstep.fixpoint", "superstep.lane_commit",
          "tile.linear", "tile.cumulative")


def test_chunk_runners_are_named_and_scoped(cm):
    sv = _solver()
    sv.solve(cm)
    sv.solve_many([cm, cm])
    mesh = jax.make_mesh((1,), ("lanes",))
    cfg = sv.config.replace(mesh=mesh, lane_axes=("lanes",))
    mesh_res = sv.solve(cm, config=cfg)
    assert mesh_res.status == solver.OPTIMAL
    texts = []
    for runner in sv._runners.values():
        if runner.aot:
            texts += [e.as_text() for e in runner._execs.values()]
        else:
            assert runner.fn.__name__ == "run_chunk"
    assert len(texts) == 2                       # single and batched
    for text in texts:
        assert text.startswith("HloModule jit_run_chunk")
        for scope in SCOPES:
            assert scope in text, scope


def test_decomposition_fixpoint_carries_the_tile_scopes(cm):
    from repro.core.fixpoint import fixpoint
    text = fixpoint.lower(cm, cm.lb0, cm.ub0).as_text(debug_info=True)
    assert "tile.linear" in text and "tile.cumulative" in text
