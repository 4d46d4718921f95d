"""EPS decomposition properties and the eps_target solver speedup
(DESIGN.md §9): partition property, UNSAT roots, and same-optimum /
fewer-supersteps vs single-root search."""

import itertools

import numpy as np
import pytest

from repro.core import baseline, engine, eps, search as S
from repro.core.fixpoint import fixpoint
from util import solve_session
from repro.core.model import Model
from repro.core.models import rcpsp


def _boxes_disjoint(lb_a, ub_a, lb_b, ub_b) -> bool:
    return bool(((lb_a > ub_b) | (lb_b > ub_a)).any())


def test_partition_boxes_pairwise_disjoint_and_consistent():
    """Pool boxes are complementary (left x ≤ m / right x ≥ m+1): any two
    are disjoint on at least one variable, and no failed child survives."""
    inst = rcpsp.generate(5, n_resources=2, seed=7, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    subs_lb, subs_ub = eps.decompose(cm, 12)
    Sn = subs_lb.shape[0]
    assert Sn >= 1
    for i in range(Sn):
        assert (subs_lb[i] <= subs_ub[i]).all()          # failed dropped
        assert (np.asarray(cm.lb0) <= subs_lb[i]).all()  # inside root box
        assert (subs_ub[i] <= np.asarray(cm.ub0)).all()
    for i in range(Sn):
        for j in range(i + 1, Sn):
            assert _boxes_disjoint(subs_lb[i], subs_ub[i],
                                   subs_lb[j], subs_ub[j]), (i, j)


def test_partition_covers_every_solution():
    """Completeness (eps.py docstring): every solution of the root lies in
    exactly one box — brute-forced on a tiny model."""
    m = Model("cover")
    x = m.int_var(0, 3, "x")
    y = m.int_var(0, 3, "y")
    z = m.int_var(0, 6, "z")
    m.add(x + y <= 4)
    m.add((x + y).eq(z * 1))
    m.branch_on([x, y, z])
    cm = m.compile()
    subs_lb, subs_ub = eps.decompose(cm, 6)
    seq = baseline.SequentialSolver(cm)
    lb0, ub0 = np.asarray(cm.lb0), np.asarray(cm.ub0)
    n_solutions = 0
    for xv, yv in itertools.product(range(4), range(4)):
        lb, ub = lb0.copy(), ub0.copy()
        lb[x.idx] = ub[x.idx] = xv
        lb[y.idx] = ub[y.idx] = yv
        if not (seq.propagate(lb, ub) and (lb == ub).all()):
            continue
        n_solutions += 1
        hits = sum(1 for i in range(subs_lb.shape[0])
                   if (subs_lb[i] <= lb).all() and (lb <= subs_ub[i]).all())
        assert hits == 1, (xv, yv, hits)
    assert n_solutions > 0


def test_unsat_root_returns_failed_sub():
    """S >= 1 even for unsatisfiable roots: one explicitly failed store so
    downstream shapes never go empty."""
    m = Model("unsat")
    a = m.int_var(0, 3, "a")
    b = m.int_var(0, 3, "b")
    m.add(a + b >= 9)
    cm = m.compile()
    subs_lb, subs_ub = eps.decompose(cm, 8)
    assert subs_lb.shape[0] >= 1
    assert all((subs_lb[i] > subs_ub[i]).any()
               for i in range(subs_lb.shape[0]))


def test_decompose_hits_target_region():
    """On a wide satisfiable root the pool reaches ~target subproblems."""
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    for target in (4, 16):
        subs_lb, _ = eps.decompose(cm, target)
        assert subs_lb.shape[0] >= target


def _decompose_reference(cm, target, opts):
    """The split loop written plainly: recompute every frontier width on
    every step, split the widest (the earliest on ties)."""
    lb, ub, _, _ = fixpoint(cm, cm.lb0, cm.ub0)
    frontier, leaves = [(np.asarray(lb), np.asarray(ub))], []
    bv = np.asarray(cm.branch_vars)
    while frontier and len(frontier) + len(leaves) < target:
        widths = [int((u - l)[bv].clip(min=0).sum()) for l, u in frontier]
        l, u = frontier.pop(int(np.argmax(widths)))
        unf = l[bv] < u[bv]
        if not unf.any():
            leaves.append((l, u))
            continue
        key = {S.MIN_DOM: u[bv] - l[bv], S.MIN_LB: l[bv]}.get(opts.var_strategy)
        v = int(bv[int(np.argmax(unf) if key is None else
                       np.argmin(np.where(unf, key, np.iinfo(l.dtype).max)))])
        m = int(l[v]) if opts.val_strategy == S.VAL_MIN \
            else int((l[v] + u[v]) // 2)
        left_u, right_l = u.copy(), l.copy()
        left_u[v], right_l[v] = min(u[v], m), max(l[v], m + 1)
        for cl, cu in ((l, left_u), (right_l, u)):
            nl, nu, _, _ = fixpoint(cm, cl, cu)
            nl, nu = np.asarray(nl), np.asarray(nu)
            if not (nl > nu).any():
                frontier.append((nl, nu))
    pool = frontier + leaves
    return np.stack([p[0] for p in pool]), np.stack([p[1] for p in pool])


@pytest.mark.parametrize("strategy", [S.INPUT_ORDER, S.MIN_LB, S.MIN_DOM])
def test_decompose_matches_plain_split_loop(strategy):
    """The pool is exactly the one the plain widest-first loop builds."""
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    cm = rcpsp.build_model(inst)[0].compile()
    opts = S.SearchOptions(var_strategy=strategy, val_strategy=S.VAL_SPLIT)
    for target in (5, 24):
        got = eps.decompose(cm, target, opts)
        want = _decompose_reference(cm, target, opts)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


def test_eps_target_same_optimum_fewer_supersteps():
    """The acceptance bar: solve(eps_target=n_lanes) matches single-root
    search on seeded RCPSP and takes strictly fewer supersteps.  Uses the
    decomposed lowering: the native §12 propagators solve this instance
    in so few supersteps that the EPS-vs-single-root gap (what this test
    measures) vanishes into the chunk granularity."""
    inst = rcpsp.generate(5, n_resources=2, seed=1, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst, decompose=True)
    cm = m.compile()
    opts = S.SearchOptions(var_strategy=S.MIN_LB, max_depth=256)
    single = solve_session(cm, n_lanes=8, eps_target=1, opts=opts)
    multi = solve_session(cm, n_lanes=8, eps_target=8, opts=opts)
    assert single.status == multi.status == engine.OPTIMAL
    assert single.objective == multi.objective
    assert multi.n_supersteps < single.n_supersteps


def test_eps_target_matches_default_decomposition():
    """solve(eps_target=8) and the default pool agree on the optimum."""
    inst = rcpsp.generate(5, n_resources=2, seed=0, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    opts = S.SearchOptions(var_strategy=S.MIN_LB, max_depth=256)
    r_eps = solve_session(cm, n_lanes=8, eps_target=8, opts=opts)
    r_def = solve_session(cm, n_lanes=8, opts=opts)
    assert r_eps.status == r_def.status == engine.OPTIMAL
    assert r_eps.objective == r_def.objective
