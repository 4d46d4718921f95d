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
    every step, split the widest (the earliest on ties), propagate each
    child on its own.  Returns the pool and the number of splits."""
    lb, ub, _, _ = fixpoint(cm, cm.lb0, cm.ub0)
    lb, ub = np.asarray(lb), np.asarray(ub)
    if (lb > ub).any():
        return lb[None], ub[None], 0
    frontier, leaves, splits = [(lb, ub)], [], 0
    bv = np.asarray(cm.branch_vars)
    while frontier and len(frontier) + len(leaves) < target:
        widths = [int((u - l)[bv].clip(min=0).sum()) for l, u in frontier]
        l, u = frontier.pop(int(np.argmax(widths)))
        unf = l[bv] < u[bv]
        if not unf.any():
            leaves.append((l, u))
            continue
        splits += 1
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
    if not pool:                       # every child failed: one failed store
        lb, ub = lb.copy(), ub.copy()
        lb[0], ub[0] = 1, 0
        pool = [(lb, ub)]
    return (np.stack([p[0] for p in pool]), np.stack([p[1] for p in pool]),
            splits)


def _assert_matches_reference(cm, target, opts):
    """The device split loop's pool equals the plain loop's, row for row
    and in order, after as many splits."""
    stats = {}
    got = eps.decompose(cm, target, opts, stats)
    want_lb, want_ub, splits = _decompose_reference(cm, target, opts)
    np.testing.assert_array_equal(got[0], want_lb)
    np.testing.assert_array_equal(got[1], want_ub)
    assert got[0].dtype == want_lb.dtype
    assert stats["splits"] == splits
    assert stats["dispatches"] == (1 if (want_lb > want_ub).any()
                                   and splits == 0 else 2)
    return got, stats


VARS = [S.INPUT_ORDER, S.MIN_LB, S.MIN_DOM]


@pytest.mark.parametrize(
    "strategy,value",
    [(v, S.VAL_SPLIT) for v in VARS] + [(v, S.VAL_MIN) for v in VARS],
    ids=VARS + [f"{v}-{S.VAL_MIN}" for v in VARS])
def test_decompose_matches_plain_split_loop(strategy, value):
    """The pool is exactly the one the plain widest-first loop builds,
    for every variable rule and both value rules."""
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    cm = rcpsp.build_model(inst)[0].compile()
    opts = S.SearchOptions(var_strategy=strategy, val_strategy=value)
    for target in (5, 24):
        _, stats = _assert_matches_reference(cm, target, opts)
        assert stats["sweep_rounds"] >= stats["splits"] > 0


def _cover_model():
    m = Model("cover")
    x = m.int_var(0, 3, "x")
    y = m.int_var(0, 3, "y")
    z = m.int_var(0, 6, "z")
    m.add(x + y <= 4)
    m.add((x + y).eq(z * 1))
    m.branch_on([x, y, z])
    return m.compile()


@pytest.mark.parametrize("target", [1, 2, 7, 64])
def test_decompose_targets_match_plain_split_loop(target):
    """Targets 1 (the root alone), 2, an odd one and one above the
    tree's 13 solutions, where every row ends a solution leaf."""
    cm = _cover_model()
    opts = S.SearchOptions(var_strategy=S.MIN_DOM, val_strategy=S.VAL_SPLIT)
    (lb, ub), stats = _assert_matches_reference(cm, target, opts)
    if target == 1:
        assert lb.shape[0] == 1 and stats["splits"] == 0
    if target == 64:
        assert lb.shape[0] == 13 and (lb == ub).all()


def _coin_model():
    """4x + 6y + 9z = 47 has five solutions, which bounds propagation
    does not isolate: splits drop children."""
    m = Model("coins")
    x = m.int_var(0, 9, "x")
    y = m.int_var(0, 9, "y")
    z = m.int_var(0, 9, "z")
    m.add((x * 4 + y * 6 + z * 9).eq(47))
    m.branch_on([x, y, z])
    return m.compile()


def _half_model():
    """x = y and x + y = 9 has no solution, but bounds propagation
    cannot see it at the root: both children of the first split fail."""
    m = Model("half")
    x = m.int_var(0, 9, "x")
    y = m.int_var(0, 9, "y")
    m.add(x.eq(y * 1))
    m.add((x + y).eq(9))
    m.branch_on([x, y])
    return m.compile()


def _unsat_root_model():
    m = Model("unsat")
    a = m.int_var(0, 3, "a")
    b = m.int_var(0, 3, "b")
    m.add(a + b >= 9)
    return m.compile()


def _ties_model():
    """Three unconstrained equal domains: the frontier's widths tie."""
    m = Model("ties")
    xs = [m.int_var(0, 5, f"x{i}") for i in range(3)]
    m.add(xs[0] + xs[1] + xs[2] <= 15)
    m.branch_on(xs)
    return m.compile()


@pytest.mark.parametrize("case", ["root-fails", "children-fail",
                                  "every-child-fails", "width-ties"])
def test_decompose_failures_and_ties_match_plain_split_loop(case):
    opts = S.SearchOptions(var_strategy=S.INPUT_ORDER,
                           val_strategy=S.VAL_SPLIT)
    if case == "root-fails":
        (lb, ub), stats = _assert_matches_reference(_unsat_root_model(), 8,
                                                    opts)
        assert (lb > ub).any() and stats == dict(dispatches=1, splits=0,
                                                 sweep_rounds=0)
    elif case == "children-fail":
        (lb, _), stats = _assert_matches_reference(_coin_model(), 8, opts)
        assert lb.shape[0] < 1 + stats["splits"]     # a child was dropped
    elif case == "every-child-fails":
        (lb, ub), stats = _assert_matches_reference(_half_model(), 64,
                                                    opts)
        assert lb.shape[0] == 1 and (lb[0] > ub[0]).any()
        assert stats["splits"] > 0
    else:
        (lb, ub), _ = _assert_matches_reference(_ties_model(), 11, opts)
        bv = np.asarray(_ties_model().branch_vars)
        widths = (ub - lb)[:, bv].sum(axis=1)
        assert len(set(widths.tolist())) < len(widths)


def _bank_model(layout):
    from repro.core.models import configuration, jobshop, nqueens
    if layout == "dense-cumulative":
        inst = rcpsp.generate(6, n_resources=2, seed=5, edge_prob=0.25)
        cm = rcpsp.build_model(inst)[0].compile(bank_layout="dense")
        assert cm.cu_layout == "dense" and cm.n_cumulative
    elif layout == "sparse-cumulative":
        inst = jobshop.generate(3, n_machines=3, seed=2)
        cm = jobshop.build_model(inst)[0].compile(bank_layout="sparse")
        assert cm.cu_layout == "sparse" and cm.n_cumulative
    elif layout == "alldifferent":
        cm = nqueens.build_model(nqueens.generate(6))[0].compile()
        assert cm.n_alldiff
    else:
        inst = configuration.generate(4, 3, seed=1)
        cm = configuration.build_model(inst)[0].compile()
        assert cm.n_table
    return cm


@pytest.mark.parametrize("layout", ["dense-cumulative", "sparse-cumulative",
                                    "alldifferent", "compact-table"])
def test_decompose_matches_plain_split_loop_on_every_bank(layout):
    """The pair fixpoint of the device loop sweeps every kind tile as the
    single-store fixpoint does, in both Cumulative layouts."""
    opts = S.SearchOptions(var_strategy=S.MIN_LB, val_strategy=S.VAL_MIN)
    _, stats = _assert_matches_reference(_bank_model(layout), 16, opts)
    assert stats["splits"] > 0


def test_root_width_beyond_int32_is_refused():
    m = Model("wide")
    xs = [m.int_var(0, 1 << 26, f"x{i}") for i in range(40)]
    m.add(xs[0] + xs[1] <= 1 << 27)
    m.branch_on(xs)
    with pytest.raises(OverflowError, match="int32"):
        eps.decompose(m.compile(), 8)


def test_solver_reports_decomposition_splits():
    """A solve's counters: two device calls, the reference's split count,
    and at least one lockstep sweep round per split."""
    from repro import solver
    inst = rcpsp.generate(6, n_resources=2, seed=3, edge_prob=0.25)
    cm = rcpsp.build_model(inst)[0].compile()
    sv = solver.Solver(solver.SolveConfig.preset(
        "prove", n_lanes=4, eps_target=12, max_depth=256))
    res = sv.solve(cm)
    _, _, splits = _decompose_reference(cm, 12, sv.config.search_options())
    assert res.status == solver.OPTIMAL
    assert res.n_decompose_dispatches == 2
    assert res.n_decompose_splits == splits > 0
    assert res.n_decompose_sweep_rounds >= splits
    given = sv.solve(cm, subs=sv.decompose(cm))
    assert (given.n_decompose_dispatches, given.n_decompose_splits,
            given.n_decompose_sweep_rounds) == (0, 0, 0)
    assert given.objective == res.objective


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
