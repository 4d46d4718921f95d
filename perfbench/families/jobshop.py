"""Taillard-style job-shop instances (Taillard 1993, EJOR 64: 278-285),
and their lowering onto the system under test.

Taillard's job-shop sets draw every processing time from U[1,99] and
send each job through every machine once, in an order made by random
swaps, i.e. a uniform random permutation.  This generator draws the same
distributions from a `numpy.random.Generator` (not Taillard's own LCG
and seeds).  So that every instance compiles to one program shape, the
processing times are redrawn until ``sum(d) + max(d) + 2``, the last
time point a start variable can reach, lies in ``last_finish_window``;
the configuration file lists this under ``assumed``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Instance:
    machines: np.ndarray       # i[J, M]: machine of job j's k-th operation
    durations: np.ndarray      # i[J, M]: its processing time
    name: str = "ta15x15"


def generate(params: dict, grid_point: dict,
             rng: np.random.Generator, name: str = "ta15x15") -> Instance:
    n_jobs, n_mach = int(params["jobs"]), int(params["machines"])
    dlo, dhi = params["duration_range"]
    lo, hi = params["last_finish_window"]
    while True:
        d = rng.integers(dlo, dhi + 1, size=(n_jobs, n_mach))
        if lo <= int(d.sum() + d.max() + 2) <= hi:
            break
    mach = np.stack([rng.permutation(n_mach) for _ in range(n_jobs)])
    return Instance(machines=mach.astype(np.int64),
                    durations=d.astype(np.int64), name=name)


def build(inst: Instance, force_dtype=None):
    """Lower an instance through the program's own job-shop model
    (`repro.core.models.jobshop.build_model`) and compile it.  Returns
    the compiled model and the store indices of the start variables,
    row-major over (job, operation)."""
    from repro.core.models import jobshop as prog

    pinst = prog.JobShop(machines=inst.machines.copy(),
                         durations=inst.durations.copy(), name=inst.name)
    model, handles = prog.build_model(pinst)
    kw = {} if force_dtype is None else dict(force_dtype=force_dtype)
    return model.compile(**kw), [v.idx for v in handles["check_vars"]]
