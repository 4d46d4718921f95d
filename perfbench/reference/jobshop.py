"""Plain reference for job-shop: a schedule checker.

Imports nothing of the system under test.  An anytime answer promises a
feasible schedule and its true makespan; no exact optimum of a 15x15
instance is within a run's reach, so this reference checks the schedule
and measures its quality against a lower bound that needs no search.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def check(inst, starts: Sequence[int]) -> Tuple[bool, int]:
    """(feasible, makespan) of a row-major ``[J * M]`` start vector:
    starts non-negative, each job's operations in order without overlap,
    and no two operations on one machine overlapping."""
    mach = np.asarray(inst.machines, dtype=np.int64)
    d = np.asarray(inst.durations, dtype=np.int64)
    s = np.asarray(starts, dtype=np.int64)
    if s.size != d.size or (s < 0).any():
        return False, -1
    s = s.reshape(d.shape)
    end = s + d
    if (end[:, :-1] > s[:, 1:]).any():
        return False, -1
    for m in range(mach.shape[1]):
        on = mach == m
        order = np.argsort(s[on], kind="stable")
        if (end[on][order][:-1] > s[on][order][1:]).any():
            return False, -1
    return True, int(end.max())


def lower_bound(inst) -> int:
    """The larger of the longest job and the most loaded machine: no
    schedule is shorter (Taillard 1993 reports the same bound)."""
    mach = np.asarray(inst.machines, dtype=np.int64)
    d = np.asarray(inst.durations, dtype=np.int64)
    loads = np.bincount(mach.ravel(), weights=d.ravel(),
                        minlength=mach.shape[1])
    return int(max(d.sum(axis=1).max(), loads.max()))
