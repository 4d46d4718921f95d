"""Session-oriented solver API (repro.solver / core/api.py, DESIGN.md
§11): SolveConfig validation + presets, compile-cached Solver sessions
(warm solves compile nothing), solve_many batched-dispatch parity vs
sequential solves, solve_iter anytime streaming, and the single status
derivation (derive_result)."""

import warnings

import numpy as np
import pytest

from repro import solver
from repro.core import engine
from repro.core import models as zoo
from repro.core import search as S
from repro.core.backend import available_backends
from repro.core.models import knapsack, rcpsp

SMALL = dict(n_lanes=4, eps_target=8)


def _compile_zoo(name, seeds):
    mod = zoo.ZOO[name]
    cms, handles, insts = [], [], []
    for s in seeds:
        inst = zoo.small_instance(name, seed=s)
        m, h = mod.build_model(inst)
        cms.append(m.compile())
        handles.append(h)
        insts.append(inst)
    return cms, handles, insts


# -------------------------------------------------------------------------
# SolveConfig: validation + presets
# -------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(n_lanes=0), dict(n_lanes=-3), dict(chunk=0), dict(max_depth=0),
    dict(eps_target=0), dict(max_supersteps=0), dict(max_fixpoint_iters=0),
    dict(timeout_s=0.0), dict(timeout_s=-1.0),
    dict(backend="cuda"),
    dict(var_strategy="random"), dict(val_strategy="max"),
    dict(backend_opts=(("lane_tile", 4, 9),)),
    dict(lane_axes=("workers",)),        # lane_axes without a mesh
])
def test_config_validation_errors(kw):
    with pytest.raises(ValueError):
        solver.SolveConfig(**kw)


def test_config_mesh_needs_lane_axes():
    import jax
    mesh = jax.make_mesh((1,), ("w",))
    with pytest.raises(ValueError):
        solver.SolveConfig(mesh=mesh)                 # no lane_axes
    with pytest.raises(ValueError):
        solver.SolveConfig(mesh=mesh, lane_axes=("bogus",))
    cfg = solver.SolveConfig(mesh=mesh, lane_axes=("w",))
    assert cfg.lane_axes == ("w",)


def test_config_normalizes_backend_opts_dict():
    cfg = solver.SolveConfig(backend="pallas",
                             backend_opts={"lane_tile": 4})
    assert cfg.backend_opts == (("lane_tile", 4),)
    # equal to the tuple spelling => same cache key
    cfg2 = solver.SolveConfig(backend="pallas",
                              backend_opts=(("lane_tile", 4),))
    assert cfg == cfg2 and hash(cfg) == hash(cfg2)


def test_presets():
    prove = solver.SolveConfig.preset("prove")
    first = solver.SolveConfig.preset("first_solution")
    fast = solver.SolveConfig.preset("fast")
    assert prove.var_strategy == S.MIN_LB and not prove.stop_on_first
    assert first.stop_on_first
    assert fast.max_fixpoint_iters == 4
    # overrides apply on top of the recipe
    cfg = solver.SolveConfig.preset("fast", n_lanes=128, backend="scatter")
    assert cfg.n_lanes == 128 and cfg.backend == "scatter" \
        and cfg.max_fixpoint_iters == 4
    with pytest.raises(ValueError):
        solver.SolveConfig.preset("does-not-exist")
    # the provenance tag never splits the cache key
    assert solver.SolveConfig.preset("prove") == solver.SolveConfig(
        var_strategy=S.MIN_LB, max_depth=1024)


def test_config_compile_key_ignores_budgets():
    a = solver.SolveConfig(timeout_s=None, max_supersteps=None)
    b = solver.SolveConfig(timeout_s=10.0, max_supersteps=50, eps_target=3)
    assert a.compile_key() == b.compile_key()


# -------------------------------------------------------------------------
# Solver session: compile cache
# -------------------------------------------------------------------------

def test_session_warm_solve_compiles_nothing():
    """The cache-hit acceptance bar: the second same-shape solve builds
    no runner and compiles no executable (asserted on the session
    counters), and is measurably faster than the cold first.  The cold
    solve compiles two: the chunk runner and, with it, the EPS split
    loop."""
    cms, _, _ = _compile_zoo("knapsack", range(2))
    sess = solver.Solver(solver.SolveConfig.preset("prove", **SMALL))
    r0 = sess.solve(cms[0])
    assert sess.stats["last_solve_cold"]
    cold = sess.session_stats()
    assert cold["runner_builds"] == 1 and cold["n_compiles"] == 2
    assert cold["n_decomposers"] == 1
    cold_wall = r0.wall_s

    r1 = sess.solve(cms[1])       # different instance, same shapes
    assert not sess.stats["last_solve_cold"]
    warm = sess.session_stats()
    assert warm["runner_builds"] == 1, "second solve rebuilt the runner"
    assert warm["n_compiles"] == 2, "second solve recompiled"
    assert warm["runner_hits"] == 1
    assert r0.status == r1.status == solver.OPTIMAL
    # compile dominates the cold solve on these smoke instances; the
    # warm solve skipping it must be visibly faster
    assert r1.wall_s < cold_wall

    # per-call config overrides that only touch host budgets still hit
    sess.solve(cms[0], timeout_s=60.0)
    assert sess.session_stats()["n_compiles"] == 2


def test_session_solve_given_its_pool_compiles_the_decomposition():
    """A warm-up solve handed its pool builds the split loop with the
    chunk runner, so the next same-shape solve that decomposes compiles
    nothing."""
    from repro.core import eps
    cms, _, _ = _compile_zoo("knapsack", range(2))
    sess = solver.Solver(solver.SolveConfig.preset("prove", **SMALL))
    pool = eps.failed_pool(np.asarray(cms[0].lb0), np.asarray(cms[0].ub0),
                           8)
    sess.solve(cms[0], subs=pool)
    warm = sess.session_stats()
    assert warm["n_compiles"] == 2 and warm["n_decomposers"] == 1
    res = sess.solve(cms[1])
    assert res.n_decompose_dispatches == 2 and res.n_decompose_splits > 0
    assert sess.session_stats()["n_compiles"] == 2
    assert not sess.stats["last_solve_cold"]


def test_first_solution_preset_never_claims_optimal():
    """stop_on_first on an optimization model stops at the first
    incumbent: the result must be SAT/incomplete, never a (false)
    OPTIMAL proof — the early-out is not exhaustion."""
    inst = knapsack.generate(n=8, seed=1)
    m, h = knapsack.build_model(inst)
    cm = m.compile()
    sess = solver.Solver(solver.SolveConfig.preset(
        "first_solution", var_strategy=S.INPUT_ORDER, **SMALL))
    res = sess.solve(cm)
    assert res.solution is not None
    assert res.status == solver.SAT
    assert not res.complete
    # the first incumbent of this instance is NOT the optimum — the old
    # gdone-as-proof logic reported OPTIMAL here
    proof = solver.Solver(solver.SolveConfig.preset("prove", **SMALL)) \
        .solve(cm)
    assert proof.status == solver.OPTIMAL
    assert res.objective > proof.objective


def test_clear_cache_recompiles():
    cms, _, _ = _compile_zoo("knapsack", range(1))
    sess = solver.Solver(solver.SolveConfig.preset("prove", **SMALL))
    sess.solve(cms[0])
    sess.clear_cache()
    sess.solve(cms[0])
    assert sess.session_stats()["runner_builds"] == 2


def test_session_distinct_config_distinct_runner():
    cms, _, _ = _compile_zoo("knapsack", range(1))
    sess = solver.Solver(solver.SolveConfig.preset("prove", **SMALL))
    sess.solve(cms[0])
    sess.solve(cms[0], backend="scatter")
    assert sess.session_stats()["runner_builds"] == 2


# -------------------------------------------------------------------------
# solve_many: batched dispatch parity
# -------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["knapsack", "nqueens", "jobshop"])
def test_solve_many_matches_sequential(name):
    """N same-shape instances through ONE batched dispatch return the
    same statuses/objectives as N sequential session solves."""
    cms, handles, insts = _compile_zoo(name, range(3))
    sess = solver.Solver(solver.SolveConfig.preset("prove", **SMALL,
                                                   max_depth=256))
    many = sess.solve_many(cms)
    seq = [sess.solve(cm) for cm in cms]
    mod = zoo.ZOO[name]
    for inst, h, a, b in zip(insts, handles, many, seq):
        assert a.status == b.status == solver.OPTIMAL
        assert a.objective == b.objective
        assert zoo.ground_check(mod, inst, h, a)


@pytest.mark.parametrize("backend", sorted(available_backends()))
def test_solve_many_parity_all_backends(backend):
    """The acceptance bar: solve_many(n=4) == 4 sequential solves on
    every registered propagation backend (knapsack, seeded)."""
    cms, _, _ = _compile_zoo("knapsack", range(4))
    sess = solver.Solver(solver.SolveConfig.preset(
        "prove", **SMALL, backend=backend))
    many = sess.solve_many(cms)
    seq = [sess.solve(cm) for cm in cms]
    assert [(r.status, r.objective) for r in many] == \
        [(r.status, r.objective) for r in seq]
    assert all(r.status == solver.OPTIMAL for r in many)


def test_solve_many_rejects_shape_mismatch():
    k, _, _ = _compile_zoo("knapsack", range(1))
    q, _, _ = _compile_zoo("nqueens", range(1))
    with pytest.raises(ValueError, match="same-shape"):
        solver.Solver().solve_many([k[0], q[0]])


def test_solve_many_empty():
    assert solver.Solver().solve_many([]) == []


# -------------------------------------------------------------------------
# solve_iter: anytime incumbent stream
# -------------------------------------------------------------------------

def test_solve_iter_monotone_bound_trace():
    """Progress events on seeded RCPSP: the incumbent bound is monotone
    non-increasing, the final event carries the OPTIMAL result, and the
    improvements trace is strictly decreasing down to the optimum."""
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    sess = solver.Solver(solver.SolveConfig.preset(
        "prove", n_lanes=8, eps_target=16, chunk=4, max_depth=256))
    events = list(sess.solve_iter(cm))
    assert len(events) >= 2, "chunk=4 must yield multiple progress events"
    assert all(not e.final for e in events[:-1]) and events[-1].final

    bounds = [e.best_objective for e in events
              if e.best_objective is not None]
    assert bounds, "no incumbent ever reported"
    assert all(a >= b for a, b in zip(bounds, bounds[1:])), bounds

    res = events[-1].result
    assert res is not None and res.status == solver.OPTIMAL
    imps = res.improvements
    assert imps and imps[-1].objective == res.objective
    assert all(a.objective > b.objective for a, b in zip(imps, imps[1:]))
    assert all(a.superstep <= b.superstep for a, b in zip(imps, imps[1:]))
    # the trace is also on the blocking path
    res2 = sess.solve(cm)
    assert [i.objective for i in res2.improvements] == \
        [i.objective for i in imps]


def test_solve_iter_max_supersteps_anytime():
    """A superstep budget turns into an anytime answer: SAT with the
    best incumbent found so far, not a blocking failure.  Decomposed
    lowering: the native §12 propagators finish this instance inside the
    budget, which would make the early-out unreachable."""
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    m, _ = rcpsp.build_model(inst, decompose=True)
    cm = m.compile()
    sess = solver.Solver(solver.SolveConfig.preset(
        "prove", n_lanes=4, eps_target=8, chunk=4, max_depth=256,
        max_supersteps=24))
    res = sess.solve(cm)
    assert res.n_supersteps <= 24 + 4       # chunk granularity
    if res.solution is not None:
        assert res.status == solver.SAT     # incumbent, not a proof
        assert not res.complete


# -------------------------------------------------------------------------
# derive_result: the one status derivation (satellite of this PR)
# -------------------------------------------------------------------------

def _sat_cm():
    from repro.core.model import Model
    m = Model("sat")
    x = m.int_var(0, 3, "x")
    y = m.int_var(0, 3, "y")
    m.add(x + y >= 2)                        # satisfaction: no objective
    return m.compile()


def test_derive_result_sat_picks_solution_lane():
    """SAT-mode incumbent pick: the solution must come from a lane with
    has_sol=True, never from argmin of the all-big objective tie (which
    would return lane 0's zeroed best_sol row)."""
    cm = _sat_cm()
    big = np.iinfo(np.int32).max // 4
    L, V = 3, cm.n_vars
    best_obj = np.full((L,), big, np.int32)
    has_sol = np.array([False, False, True])
    best_sol = np.zeros((L, V), np.int32)
    best_sol[2] = np.arange(V)              # only lane 2 holds a solution
    res = engine.derive_result(
        cm, best_obj, has_sol, best_sol, incomplete=np.zeros(L, bool),
        done=True, n_nodes=5, n_fails=1, n_sols=1, n_sweeps=9,
        n_supersteps=4, wall_s=0.1)
    assert res.status == solver.SAT
    assert res.objective is None
    assert (res.solution == best_sol[2]).all()
    assert res.complete


def test_derive_result_statuses():
    cm = _sat_cm()
    L, V = 2, cm.n_vars
    none = dict(best_obj=np.zeros(L, np.int32),
                has_sol=np.zeros(L, bool),
                best_sol=np.zeros((L, V), np.int32),
                incomplete=np.zeros(L, bool),
                n_nodes=0, n_fails=0, n_sols=0, n_sweeps=0,
                n_supersteps=0, wall_s=0.0)
    assert engine.derive_result(cm, done=True, **none).status == \
        solver.UNSAT
    assert engine.derive_result(cm, done=False, **none).status == \
        solver.UNKNOWN
    # depth-limit incompleteness forbids UNSAT even when done
    none["incomplete"] = np.array([True, False])
    r = engine.derive_result(cm, done=True, **none)
    assert r.status == solver.UNKNOWN and not r.complete


def test_derive_result_optimization_statuses():
    inst = knapsack.generate(n=4, seed=0)
    m, _ = knapsack.build_model(inst)
    cm = m.compile()
    L, V = 3, cm.n_vars
    best_obj = np.array([50, -7, 10], np.int32)
    has_sol = np.array([True, True, True])
    best_sol = np.tile(np.arange(V, dtype=np.int32), (L, 1))
    best_sol[1] += 100
    kw = dict(best_obj=best_obj, has_sol=has_sol, best_sol=best_sol,
              incomplete=np.zeros(L, bool), n_nodes=1, n_fails=0,
              n_sols=3, n_sweeps=1, n_supersteps=1, wall_s=0.0)
    r = engine.derive_result(cm, done=True, **kw)
    assert r.status == solver.OPTIMAL and r.objective == -7
    assert (r.solution == best_sol[1]).all()
    r = engine.derive_result(cm, done=False, **kw)
    assert r.status == solver.SAT and r.objective == -7   # incumbent


# -------------------------------------------------------------------------
# engine.solve shim
# -------------------------------------------------------------------------

def test_engine_shim_deprecated_but_equivalent():
    cms, _, _ = _compile_zoo("knapsack", range(1))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        legacy = engine.solve(cms[0], n_lanes=4, n_subproblems=8)
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)
            and "engine.solve is deprecated" in str(w.message)]
    # exactly once per call — the shim is the ONLY warner on this path
    # (internal callers all go through Solver sessions now, so the suite
    # stays warning-clean outside this test)
    assert len(deps) == 1, [str(w.message) for w in caught]
    new = solver.Solver(solver.SolveConfig(**SMALL)).solve(cms[0])
    assert legacy.status == new.status == solver.OPTIMAL
    assert legacy.objective == new.objective


def test_engine_shim_maps_search_options():
    cms, _, _ = _compile_zoo("knapsack", range(1))
    opts = S.SearchOptions(var_strategy=S.MIN_LB, max_depth=128,
                           backend="scatter", stop_on_first=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res = engine.solve(cms[0], n_lanes=4, n_subproblems=8, opts=opts)
    assert res.status == solver.OPTIMAL


# -------------------------------------------------------------------------
# pool padding (eps.pad_pool)
# -------------------------------------------------------------------------

def test_pad_pool_failed_stores_are_inert():
    """Padded pool == unpadded pool results (pads are born failed)."""
    from repro.core import eps
    inst = rcpsp.generate(5, n_resources=2, seed=1, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    subs = eps.decompose(cm, 6)
    padded = eps.pad_pool(*subs, 16)
    assert padded[0].shape[0] == 16
    assert (padded[0][subs[0].shape[0]:, 0] >
            padded[1][subs[0].shape[0]:, 0]).all()     # failed stores
    sess = solver.Solver(solver.SolveConfig.preset(
        "prove", n_lanes=4, max_depth=256, pad_pool=False))
    a = sess.solve(cm, subs=subs)
    b = sess.solve(cm, subs=padded)
    assert a.status == b.status == solver.OPTIMAL
    assert a.objective == b.objective
