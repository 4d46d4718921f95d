"""PSPLIB-style single-mode RCPSP instances (Kolisch & Sprecher 1996;
ProGen parameters from Kolisch, Sprecher & Drexl 1995), and their
lowering onto the system under test.

The generator follows ProGen's controls for the j30 set: 30 non-dummy
jobs, 3 start and 3 finish jobs, at most 3 predecessors and successors
per job, non-redundant arcs, durations and demands U[1,10], network
complexity NC (non-redundant arcs per node, the two dummies counted),
resource factor RF and resource strength RS, the three set by the grid
point.  Where it departs from
ProGen so that every instance of one grid point compiles to one program
shape, the configuration file lists the departure under ``assumed``:

* each job requests exactly ``round(RF * K)`` resources, spread so that
  every resource serves the same number of jobs, give or take one;
* durations are redrawn until ``sum(d) + max(d) + 2``, the last time
  point a start variable can reach, lies in ``last_finish_window``.

Everything here is drawn from a `numpy.random.Generator`, so one seed
gives one instance.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from perfbench.reference.rcpsp import longest_paths


@dataclasses.dataclass
class Instance:
    durations: np.ndarray          # i[n]
    arcs: List[Tuple[int, int]]    # (i, j): i finishes before j starts
    usage: np.ndarray              # i[K, n]
    capacity: np.ndarray           # i[K]
    nc: float
    rf: float
    rs: float
    name: str = "j30"

    @property
    def n_jobs(self) -> int:
        return len(self.durations)


def _round(x: float) -> int:
    return int(np.floor(x + 0.5))


def network(rng: np.random.Generator, n: int, n_start: int, n_end: int,
            max_pred: int, max_succ: int, n_arcs: int,
            tries: int = 200) -> List[Tuple[int, int]]:
    """A random non-redundant precedence network on jobs ``0..n-1`` with
    ``n_arcs`` arcs, all from a lower to a higher index.  Jobs below
    ``n_start`` have no predecessor and the last ``n_end`` no successor
    (they hang off the dummy source and sink)."""
    for _ in range(tries):
        arcs = _try_network(rng, n, n_start, n_end, max_pred, max_succ,
                            n_arcs)
        if arcs is not None:
            return arcs
    raise RuntimeError(f"no network with {n_arcs} arcs after {tries} tries")


def _try_network(rng, n, n_start, n_end, max_pred, max_succ, n_arcs):
    preds = [set() for _ in range(n)]
    succs = [set() for _ in range(n)]
    reach = np.eye(n, dtype=bool)          # reach[a, b]: a path a ~> b

    def can_add(i, j):
        if i >= j or j < n_start or i >= n - n_end or reach[i, j]:
            return False
        if len(succs[i]) >= max_succ or len(preds[j]) >= max_pred:
            return False
        # i -> j would make an arc a -> b redundant where a ~> i, j ~> b
        into_i = np.flatnonzero(reach[:, i])
        from_j = reach[j]
        return not any(from_j[b] for a in into_i for b in succs[a])

    def add(i, j):
        succs[i].add(j)
        preds[j].add(i)
        reach[reach[:, i]] |= reach[j]

    for j in range(n_start, n):
        cand = [i for i in range(j) if can_add(i, j)]
        if not cand:
            return None
        add(int(rng.choice(cand)), j)
    for i in range(n - n_end):
        if succs[i]:
            continue
        cand = [j for j in range(i + 1, n) if can_add(i, j)]
        if not cand:
            return None
        add(i, int(rng.choice(cand)))
    count = sum(len(s) for s in succs)
    while count < n_arcs:
        cand = [(i, j) for i in range(n) for j in range(i + 1, n)
                if can_add(i, j)]
        if not cand:
            return None
        i, j = cand[int(rng.integers(len(cand)))]
        add(i, j)
        count += 1
    if count != n_arcs:
        return None
    return sorted((i, j) for i in range(n) for j in succs[i])


def resource_sets(rng: np.random.Generator, n: int, k: int,
                  per_job: int) -> np.ndarray:
    """bool[K, n]: job j requests ``per_job`` resources; jobs are taken
    in random order and each picks the least-used resources (ties at
    random), so every resource serves the same number of jobs within
    one."""
    uses = np.zeros((k, n), dtype=bool)
    load = np.zeros(k, dtype=np.int64)
    for j in rng.permutation(n):
        order = np.lexsort((rng.random(k), load))
        pick = order[:per_job]
        uses[pick, j] = True
        load[pick] += 1
    return uses


def generate(params: dict, grid_point: dict,
             rng: np.random.Generator, name: str = "j30") -> Instance:
    n = int(params["jobs"])
    k = int(params["resources"])
    dlo, dhi = params["duration_range"]
    rlo, rhi = params["demand_range"]
    nc, rf = float(grid_point["nc"]), float(grid_point["rf"])
    n_nodes = n + 2
    n_dummy_arcs = int(params["start_jobs"]) + int(params["finish_jobs"])
    arcs = network(rng, n, int(params["start_jobs"]),
                   int(params["finish_jobs"]), int(params["max_predecessors"]),
                   int(params["max_successors"]),
                   _round(nc * n_nodes) - n_dummy_arcs)
    lo, hi = params["last_finish_window"]
    while True:
        d = rng.integers(dlo, dhi + 1, size=n)
        if lo <= int(d.sum() + d.max() + 2) <= hi:
            break
    uses = resource_sets(rng, n, k, _round(rf * k))
    usage = np.where(uses, rng.integers(rlo, rhi + 1, size=(k, n)), 0)
    rs = float(grid_point["rs"])
    es, _ = longest_paths(d, arcs)
    horizon = int((es + d).max())
    t = np.arange(horizon)
    running = (es[None, :] <= t[:, None]) & (t[:, None] < (es + d)[None, :])
    peak = (running[None, :, :] * usage[:, None, :]).sum(axis=2).max(axis=1)
    kmin = usage.max(axis=1)
    cap = np.array([int(kmin[r]) + _round(rs * (int(peak[r]) - int(kmin[r])))
                    for r in range(k)], dtype=np.int64)
    return Instance(durations=d.astype(np.int64), arcs=arcs,
                    usage=usage.astype(np.int64), capacity=cap,
                    nc=nc, rf=rf, rs=rs, name=name)


def build(inst: Instance, force_dtype=None):
    """Lower an instance through the program's own RCPSP model
    (`repro.core.models.rcpsp.build_model`) and compile it.  Returns the
    compiled model and the store indices of the start variables."""
    from repro.core.models import rcpsp as prog

    pinst = prog.RCPSP(durations=inst.durations.copy(),
                       precedences=list(inst.arcs),
                       usage=inst.usage.copy(),
                       capacity=inst.capacity.copy(), name=inst.name)
    model, handles = prog.build_model(pinst)
    kw = {} if force_dtype is None else dict(force_dtype=force_dtype)
    return model.compile(**kw), [v.idx for v in handles["check_vars"]]
