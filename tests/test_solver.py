"""End-to-end solver behaviour: engine vs sequential baseline vs brute
force, RCPSP ground checks, EPS completeness, B&B optimality."""

import itertools

import numpy as np
import pytest

from repro.core.model import Model
from repro.core import engine, baseline, eps, search as S
from util import solve_session
from repro.core.models import rcpsp


def brute_force_min(m: Model, cm, obj_idx):
    """Enumerate all assignments of the branch vars (tiny domains only)."""
    lb0, ub0 = np.asarray(cm.lb0), np.asarray(cm.ub0)
    seq = baseline.SequentialSolver(cm)
    best = None
    doms = [range(int(lb0[v]), int(ub0[v]) + 1)
            for v in np.asarray(cm.branch_vars)]
    for combo in itertools.product(*doms):
        lb, ub = lb0.copy(), ub0.copy()
        for v, val in zip(np.asarray(cm.branch_vars), combo):
            lb[v] = ub[v] = val
        if seq.propagate(lb, ub) and (lb == ub).all():
            o = int(lb[obj_idx])
            best = o if best is None else min(best, o)
    return best


def small_opt_model():
    m = Model("m")
    x = m.int_var(0, 4, "x")
    y = m.int_var(0, 4, "y")
    z = m.int_var(0, 9, "z")
    m.add(x + y >= 5)
    m.add(x <= z)
    m.add(y <= z)
    b = m.reify(x <= 1)
    m.add(2 * x + 3 * y <= 11)
    m.minimize(z)
    m.branch_on([x, y, z])
    return m


def test_engine_matches_brute_force():
    m = small_opt_model()
    cm = m.compile()
    bf = brute_force_min(m, cm, cm.obj_var)
    res = solve_session(cm, n_lanes=4, n_subproblems=8)
    assert res.status == engine.OPTIMAL
    assert res.objective == bf


def test_engine_matches_baseline_statuses():
    for seed in range(4):
        inst = rcpsp.generate(5, n_resources=2, seed=seed, edge_prob=0.3)
        m, _ = rcpsp.build_model(inst)
        cm = m.compile()
        opts = S.SearchOptions(var_strategy=S.MIN_LB, max_depth=256)
        seq = baseline.SequentialSolver(cm, opts).solve(timeout_s=120)
        par = solve_session(cm, n_lanes=4, n_subproblems=8, opts=opts,
                           timeout_s=300)
        assert seq.status == par.status == engine.OPTIMAL
        assert seq.objective == par.objective


def test_solution_passes_ground_checker():
    inst = rcpsp.generate(6, n_resources=3, seed=9, edge_prob=0.25)
    m, h = rcpsp.build_model(inst)
    cm = m.compile()
    res = solve_session(cm, n_lanes=8, n_subproblems=16,
                       opts=S.SearchOptions(var_strategy=S.MIN_LB,
                                            max_depth=256))
    assert res.status == engine.OPTIMAL
    s_idx = [v.idx for v in h["s"]]
    ok, mk = rcpsp.check_solution(inst, res.solution[s_idx])
    assert ok and mk == res.objective


def test_unsat_detected():
    m = Model()
    a = m.int_var(0, 3, "a")
    b = m.int_var(0, 3, "b")
    m.add(a + b >= 9)
    res = solve_session(m.compile(), n_lanes=2)
    assert res.status == engine.UNSAT and res.complete


def test_result_invariant_to_lane_count():
    """Paper's determinism claim at system level: decomposition and lane
    counts change the schedule, never the answer."""
    inst = rcpsp.generate(5, n_resources=2, seed=2, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    objs = set()
    for lanes, subs in [(1, 1), (2, 4), (8, 32)]:
        res = solve_session(cm, n_lanes=lanes, n_subproblems=subs,
                           opts=S.SearchOptions(max_depth=256))
        assert res.status == engine.OPTIMAL
        objs.add(res.objective)
    assert len(objs) == 1


def test_eps_partition_is_complete():
    """Union of EPS subproblem boxes must cover every root solution."""
    inst = rcpsp.generate(4, n_resources=2, seed=5, edge_prob=0.3)
    m, h = rcpsp.build_model(inst)
    cm = m.compile()
    subs_lb, subs_ub = eps.decompose(cm, 8)
    # optimal solution found without EPS must fall in exactly >=1 box
    res = solve_session(cm, n_lanes=1, subs=(np.asarray(cm.lb0)[None],
                                            np.asarray(cm.ub0)[None]))
    sol = res.solution
    hits = 0
    for i in range(subs_lb.shape[0]):
        if (subs_lb[i] <= sol).all() and (sol <= subs_ub[i]).all():
            hits += 1
    assert hits >= 1


def test_bnb_prunes_but_keeps_optimum():
    m = small_opt_model()
    cm = m.compile()
    # huge lane count => massive parallel redundancy, same answer
    res = solve_session(cm, n_lanes=16, n_subproblems=64)
    assert res.status == engine.OPTIMAL
    assert res.objective == brute_force_min(m, cm, cm.obj_var)


def test_satisfaction_stop_on_first():
    m = Model()
    x = m.int_var(0, 50, "x")
    y = m.int_var(0, 50, "y")
    m.add((x + y).eq(40))
    m.add(x >= 10)
    opts = S.SearchOptions(stop_on_first=True)
    res = solve_session(m.compile(), n_lanes=4, opts=opts)
    assert res.status == engine.SAT
    assert res.solution[x.idx] + res.solution[y.idx] == 40


def test_multi_device_engine_matches_single():
    """The shard_map engine on a fake 4-device mesh returns the same
    objective as the single-device engine (bound sharing via pmin)."""
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices (run under dryrun XLA flags)")
    mesh = jax.make_mesh((4,), ("workers",))
    inst = rcpsp.generate(5, n_resources=2, seed=1, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    r1 = solve_session(cm, n_lanes=4, n_subproblems=16)
    r2 = solve_session(cm, n_lanes=2, n_subproblems=16, mesh=mesh,
                      lane_axes=("workers",))
    assert r1.status == r2.status == engine.OPTIMAL
    assert r1.objective == r2.objective


def test_dispatch_pool_shared_queue():
    """Shared-queue dispatcher: unique assignment, exhaustion marks done."""
    import jax.numpy as jnp
    from repro.core import search as S
    from repro.core.models import rcpsp

    inst = rcpsp.generate(4, n_resources=2, seed=0)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    opts = S.SearchOptions()
    st = S.init_lanes(cm, 4, opts)
    # 3 subproblems, 4 fresh lanes: three get 0,1,2; the fourth is done
    st2, head = S.dispatch_pool(st, jnp.asarray(0, jnp.int32), 3)
    got = sorted(int(x) for x in st2.next_sub if int(x) < 3)
    assert got == [0, 1, 2]
    assert int(st2.done.sum()) == 1
    assert int(head) == 3
    # nothing further to hand out
    st3, head2 = S.dispatch_pool(st2._replace(
        fresh=jnp.ones(4, bool),
        next_sub=jnp.full((4,), S.UNASSIGNED, jnp.int32)), head, 3)
    assert bool(st3.done.all())


def test_solution_requires_fixpoint_convergence():
    """With a 1-sweep cap, fully-fixed-but-unpropagated stores must not
    be recorded as solutions (the §Perf H1 soundness guard)."""
    from repro.core import search as S
    m = Model()
    x = m.int_var(0, 3, "x")
    y = m.int_var(0, 3, "y")
    m.add((x + y).eq(3))
    m.add(x <= 1)
    opts = S.SearchOptions(max_fixpoint_iters=1, max_depth=64)
    res = solve_session(m.compile(), n_lanes=2, n_subproblems=4, opts=opts)
    assert res.status == engine.SAT
    sol = res.solution
    assert sol[x.idx] + sol[y.idx] == 3 and sol[x.idx] <= 1
