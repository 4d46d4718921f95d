"""Plain reference for RCPSP: a schedule checker and an exact optimum.

Imports nothing of the system under test.  The optimum comes from a
time-indexed integer program (Pritsker, Watters & Wolfe 1969) solved by
SciPy's HiGHS: ``x[j, t] = 1`` iff job j starts at t, one start per job,
aggregated precedence rows, and one capacity row per resource and time
point.  Start windows come from the critical path and a serial
schedule-generation upper bound, so the program stays small.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np
from scipy import optimize, sparse


def check(inst, starts: Sequence[int]) -> Tuple[bool, int]:
    """(feasible, makespan): every start non-negative, every arc
    respected, and every resource's profile within capacity at every
    time point."""
    s = np.asarray(starts, dtype=np.int64)
    d = np.asarray(inst.durations, dtype=np.int64)
    if s.shape != d.shape or (s < 0).any():
        return False, -1
    if any(s[i] + d[i] > s[j] for i, j in inst.arcs):
        return False, -1
    end = s + d
    mk = int(end.max())
    t = np.arange(mk)
    running = (s[None, :] <= t[:, None]) & (t[:, None] < end[None, :])
    load = running.astype(np.int64) @ np.asarray(inst.usage).T   # [T, K]
    if (load > np.asarray(inst.capacity)[None, :]).any():
        return False, -1
    return True, mk


def longest_paths(durations, arcs):
    """(earliest start of each job under precedence alone, longest path
    from each job's start to the end); arcs go from a lower to a higher
    index."""
    d = np.asarray(durations, dtype=np.int64)
    n = len(d)
    es = np.zeros(n, dtype=np.int64)
    for i, j in sorted(arcs):
        es[j] = max(es[j], es[i] + d[i])
    tail = d.copy()                  # longest path from j's start to the end
    for i, j in sorted(arcs, reverse=True):
        tail[i] = max(tail[i], d[i] + tail[j])
    return es, tail


def serial_schedule(inst, priority: Sequence[float]) -> np.ndarray:
    """Serial schedule-generation scheme: repeatedly take the eligible
    job of least priority value and start it at its earliest
    precedence- and resource-feasible time."""
    d = np.asarray(inst.durations, dtype=np.int64)
    usage = np.asarray(inst.usage, dtype=np.int64)
    cap = np.asarray(inst.capacity, dtype=np.int64)
    n = len(d)
    preds = [[] for _ in range(n)]
    for i, j in inst.arcs:
        preds[j].append(i)
    horizon = int(d.sum()) + 1
    load = np.zeros((horizon, usage.shape[0]), dtype=np.int64)
    start = np.full(n, -1, dtype=np.int64)
    for _ in range(n):
        elig = [j for j in range(n) if start[j] < 0
                and all(start[i] >= 0 for i in preds[j])]
        j = min(elig, key=lambda q: (priority[q], q))
        t = max((start[i] + d[i] for i in preds[j]), default=0)
        while (load[t:t + d[j]] + usage[:, j] > cap).any():
            t += 1
        start[j] = t
        load[t:t + d[j]] += usage[:, j]
    return start


def optimum(inst, time_limit_s: float = 60.0) -> Tuple[int, np.ndarray]:
    """The least makespan and one schedule that reaches it.  Raises if
    HiGHS does not prove optimality within ``time_limit_s``."""
    d = np.asarray(inst.durations, dtype=np.int64)
    usage = np.asarray(inst.usage, dtype=np.int64)
    cap = np.asarray(inst.capacity, dtype=np.int64)
    n, k = len(d), usage.shape[0]
    es, tail = longest_paths(d, inst.arcs)
    lb = int((es + d).max())
    best = serial_schedule(inst, -tail)          # longest tail first
    ub = int((best + d).max())
    if ub == lb:
        return ub, best
    ls = ub - tail                               # latest start under ub
    cols = [(j, t) for j in range(n) for t in range(int(es[j]), int(ls[j]) + 1)]
    col = {c: i for i, c in enumerate(cols)}
    nx = len(cols)
    cm = nx                                      # makespan column
    rows, cidx, vals, lo, hi = [], [], [], [], []

    def row(entries, lower, upper):
        r = len(lo)
        for c, v in entries:
            rows.append(r)
            cidx.append(c)
            vals.append(v)
        lo.append(lower)
        hi.append(upper)

    for j in range(n):
        row([(col[j, t], 1.0) for t in range(es[j], ls[j] + 1)], 1, 1)
        # makespan >= start + duration
        row([(cm, 1.0)] + [(col[j, t], -float(t)) for t in
                           range(es[j], ls[j] + 1)], float(d[j]), np.inf)
    for i, j in inst.arcs:
        row([(col[j, t], float(t)) for t in range(es[j], ls[j] + 1)]
            + [(col[i, t], -float(t)) for t in range(es[i], ls[i] + 1)],
            float(d[i]), np.inf)
    for r in range(k):
        users = [j for j in range(n) if usage[r, j] > 0]
        for tau in range(ub):
            ent = [(col[j, t], float(usage[r, j])) for j in users
                   for t in range(max(es[j], tau - d[j] + 1),
                                  min(ls[j], tau) + 1)]
            if ent and sum(v for _, v in ent) > cap[r]:
                row(ent, -np.inf, float(cap[r]))
    a = sparse.csr_matrix((vals, (rows, cidx)), shape=(len(lo), nx + 1))
    c = np.zeros(nx + 1)
    c[cm] = 1.0
    integrality = np.ones(nx + 1)
    integrality[cm] = 0
    bounds = optimize.Bounds(np.r_[np.zeros(nx), lb], np.r_[np.ones(nx), ub])
    res = optimize.milp(c, constraints=optimize.LinearConstraint(a, lo, hi),
                        integrality=integrality, bounds=bounds,
                        options=dict(time_limit=time_limit_s))
    if res.status != 0:
        raise RuntimeError(f"reference MILP did not prove an optimum: "
                           f"{res.message}")
    x = res.x[:nx] > 0.5
    start = np.zeros(n, dtype=np.int64)
    for (j, t), on in zip(cols, x):
        if on:
            start[j] = t
    ok, mk = check(inst, start)
    if not ok:
        raise RuntimeError("reference MILP returned an infeasible schedule")
    return mk, start
