"""Embarrassingly-parallel-search decomposition (paper §TURBO, after
Malapert/Régin/Rezgui 2016; DESIGN.md §9).

TURBO "dynamically generates subproblems following a variant of EPS"; we
generate them by iterative splitting on the host: repeatedly split the
widest-frontier subproblem with the search branching rule, propagate both
children with the *same* fixpoint engine, and drop failed children.  The
resulting pool partitions the root search space (left `x ≤ m` / right
`x ≥ m+1` are complementary), so lane-level DFS over the pool is complete.

The pool feeds `engine.solve(eps_target=...)`: it seeds the per-device
lane pools, and `search.dispatch_pool` replenishes idle lanes from the
remainder every superstep (DESIGN.md §9).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.compile import CompiledModel
from repro.core.fixpoint import fixpoint
from repro.core import search as S


def _propagate(cm: CompiledModel, lb, ub,
               stats: dict) -> Tuple[np.ndarray, np.ndarray]:
    """One fixpoint dispatch of the decomposition and its read-back,
    counted in ``stats["dispatches"]``."""
    stats["dispatches"] += 1
    with obs.span("repro.eps.dispatch"):
        nlb, nub, _, _ = fixpoint(cm, lb, ub)
        return np.asarray(nlb), np.asarray(nub)


def decompose(cm: CompiledModel, target: int,
              opts: "S.SearchOptions" = None,
              stats: Optional[dict] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Split the root into ~`target` consistent subproblems.

    Returns (subs_lb, subs_ub) with shape [S, V], S ≥ 1 (S can exceed or
    fall short of `target` when the tree is shallow/unsatisfiable).
    ``stats``, when given, receives ``dispatches``: the number of
    fixpoint dispatches made (the root and every child).
    """
    with obs.span("repro.eps.decompose"):
        return _decompose(cm, target, opts or S.SearchOptions(),
                          stats if stats is not None else {})


def _decompose(cm: CompiledModel, target: int, opts: "S.SearchOptions",
               stats: dict) -> Tuple[np.ndarray, np.ndarray]:
    stats["dispatches"] = 0
    lb, ub = _propagate(cm, cm.lb0, cm.ub0, stats)
    if (lb > ub).any():
        return lb[None], ub[None]          # failed root: one failed sub

    bv = np.asarray(cm.branch_vars)

    def width(l, u):
        return int((u - l)[bv].clip(min=0).sum())

    frontier: List[Tuple[np.ndarray, np.ndarray]] = [(lb, ub)]
    widths = [width(lb, ub)]             # kept parallel to `frontier`
    leaves: List[Tuple[np.ndarray, np.ndarray]] = []

    while frontier and len(frontier) + len(leaves) < target:
        # widest subproblem first (the earliest on ties) keeps the pool
        # balanced
        i = max(range(len(widths)), key=widths.__getitem__)
        widths.pop(i)
        l, u = frontier.pop(i)
        unf = l[bv] < u[bv]
        if not unf.any():
            leaves.append((l, u))          # already a solution leaf
            continue
        if opts.var_strategy == S.MIN_DOM:
            w = np.where(unf, u[bv] - l[bv], np.iinfo(l.dtype).max // 4)
            v = int(bv[int(np.argmin(w))])
        elif opts.var_strategy == S.MIN_LB:
            w = np.where(unf, l[bv], np.iinfo(l.dtype).max // 4)
            v = int(bv[int(np.argmin(w))])
        else:
            v = int(bv[int(np.argmax(unf))])
        m = int(l[v]) if opts.val_strategy == S.VAL_MIN else int((l[v] + u[v]) // 2)
        for child in ("le", "ge"):
            cl, cu = l.copy(), u.copy()
            if child == "le":
                cu[v] = min(cu[v], m)
            else:
                cl[v] = max(cl[v], m + 1)
            nlb, nub = _propagate(cm, cl, cu, stats)
            if not (nlb > nub).any():
                frontier.append((nlb, nub))
                widths.append(width(nlb, nub))

    pool = frontier + leaves
    if not pool:                            # everything failed: UNSAT root
        bad_l = lb.copy(); bad_u = ub.copy()
        bad_l[0] = 1; bad_u[0] = 0          # an explicitly failed store
        pool = [(bad_l, bad_u)]
    subs_lb = np.stack([p[0] for p in pool])
    subs_ub = np.stack([p[1] for p in pool])
    return subs_lb, subs_ub


def pad_pool(subs_lb: np.ndarray, subs_ub: np.ndarray,
             size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a pool ``[S, V]`` up to ``size`` entries with explicitly-failed
    stores (``lb[0] > ub[0]``) — a lane that pops one fails it in a
    single superstep and re-arms, so statuses/objectives are unchanged.

    Used by the session API for two shape-stabilization jobs
    (DESIGN.md §11): bucketing pool sizes (`api._bucket`: powers of two
    up to 1024, then multiples of 1024 — capped so a 10³-variable model
    with a large ``eps_target`` can't silently allocate a pool ~2× the
    request, DESIGN.md §16) so the compiled runner is reused across
    instances whose decompositions differ slightly, and rounding the
    pool to a device-count multiple for the sharded mesh engine.
    ``size <= S`` is a no-op.

    The padded rows are inert under BOTH bank layouts: failure is
    carried by store row 0 (``lb[0] > ub[0]``), which the per-lane
    fixpoint masking freezes before any kind tile — dense or sparse —
    ever sweeps the lane (asserted by `tests/test_sparse_tiles.py`).
    """
    s = subs_lb.shape[0]
    if size <= s:
        return subs_lb, subs_ub
    fl = np.repeat(np.asarray(subs_lb[:1]).copy(), size - s, axis=0)
    fu = np.repeat(np.asarray(subs_ub[:1]).copy(), size - s, axis=0)
    fl[:, 0], fu[:, 0] = 1, 0
    return (np.concatenate([np.asarray(subs_lb), fl]),
            np.concatenate([np.asarray(subs_ub), fu]))


def fit_pool(subs_lb: np.ndarray, subs_ub: np.ndarray,
             size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fit a pool ``[S, V]`` to *exactly* ``size`` entries — the
    fixed-shape splice used by the serving scheduler (DESIGN.md §15):
    a `LaneBatch` slot's pool rows are a fixed ``[size, V]`` block of
    the compiled batch, so an admitted request's pool must be padded up
    (with inert failed stores, `pad_pool`) and can never exceed the
    bucket size without forcing a recompile — that case raises instead.
    """
    s = int(subs_lb.shape[0])
    if s > size:
        raise ValueError(
            f"pool of {s} subproblems does not fit the fixed bucket size "
            f"{size}; decompose with a smaller eps_target or grow the "
            f"bucket (which recompiles the batch runner)")
    return pad_pool(np.asarray(subs_lb), np.asarray(subs_ub), size)


def failed_pool(template_lb: np.ndarray, template_ub: np.ndarray,
                size: int) -> Tuple[np.ndarray, np.ndarray]:
    """An all-failed pool ``[size, V]`` (every store has ``lb[0] >
    ub[0]``) — what an idle/retired `LaneBatch` slot holds so its lanes
    drain in one superstep each and the slot freezes (DESIGN.md §15).
    ``template_lb/ub`` supply the store dtype and width ``V`` (a ``[V]``
    row or any ``[..., V]`` pool)."""
    lb = np.asarray(template_lb).reshape(-1, np.asarray(template_lb).shape[-1])
    ub = np.asarray(template_ub).reshape(-1, np.asarray(template_ub).shape[-1])
    fl = np.repeat(lb[:1].copy(), size, axis=0)
    fu = np.repeat(ub[:1].copy(), size, axis=0)
    fl[:, 0], fu[:, 0] = 1, 0
    return fl, fu
