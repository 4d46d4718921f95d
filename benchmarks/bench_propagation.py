"""Propagation-throughput microbenchmark (the paper's core claim:
propagation parallelizes).

Measures lane-batched fixpoint throughput (propagator-executions/sec) of
every registered propagation backend (`core/backend.py`) as the lane
count and instance size grow — the CPU-visible analogue of filling GPU
SMs with blocks.  Near-flat time per sweep as lanes grow ⇒ the work
vectorizes, which is what TURBO exploits on real parallel hardware.

  PYTHONPATH=src python -m benchmarks.bench_propagation \
      --sizes 8 12 --lanes 1 8 32 [--backends gather scatter pallas] \
      [--json BENCH_propagation.json]

CSV columns: backend,n_tasks,lanes,ms_per_fixpoint,ms_per_lane,
sweeps_exec.  `sweeps_exec` is the backend-reported number of sweeps
physically executed (pallas runs whole lane *tiles* in lockstep, so it
exceeds the per-lane counts of the XLA backends on the same input).
These are CPU timings of the backends against each other; what the
solver costs on the chip is measured by `perfbench` (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import available_backends, get_backend
from repro.core.models import rcpsp


def bench(cm, lbs, ubs, backend_name: str, iters: int = 5, **backend_kw):
    """Return (seconds_per_fixpoint, total_sweeps) for one backend."""
    backend = get_backend(backend_name, **backend_kw)
    f = lambda: backend.fixpoint_batch(cm, lbs, ubs)  # noqa: E731
    out = f()
    jax.block_until_ready(out)                       # compile
    sweeps = int(np.asarray(out[2]).sum())
    t0 = time.time()
    for _ in range(iters):
        out = f()
    jax.block_until_ready(out)
    return (time.time() - t0) / iters, sweeps


def perturbed_stores(cm, n_lanes: int, rng: np.random.Generator):
    """n_lanes copies of the root store, one random tell each so lanes
    aren't identical (fixpoints then differ per lane)."""
    lb0 = np.tile(np.asarray(cm.lb0), (n_lanes, 1))
    ub0 = np.tile(np.asarray(cm.ub0), (n_lanes, 1))
    for i in range(n_lanes):
        v = int(rng.integers(1, cm.n_vars))
        if lb0[i, v] < ub0[i, v]:
            lb0[i, v] += 1
    return jnp.asarray(lb0), jnp.asarray(ub0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+", default=[8, 12],
                    help="RCPSP task counts (>=2 sizes for the trajectory)")
    ap.add_argument("--lanes", type=int, nargs="+", default=[1, 8, 32])
    ap.add_argument("--backends", nargs="+", default=None,
                    help=f"subset of {available_backends()}")
    ap.add_argument("--skip-pallas", action="store_true")
    ap.add_argument("--json", default=None,
                    help="also write rows as JSON (perf trajectory file)")
    args = ap.parse_args(argv)

    backends = list(args.backends or available_backends())
    if args.skip_pallas and "pallas" in backends:
        backends.remove("pallas")

    rng = np.random.default_rng(0)
    header = ("backend,n_tasks,lanes,ms_per_fixpoint,ms_per_lane,"
              "sweeps_exec")
    rows = [header]
    records = []
    for n_tasks in args.sizes:
        inst = rcpsp.generate(n_tasks, n_resources=4, seed=0)
        m, _ = rcpsp.build_model(inst)
        cm = m.compile()
        for L in args.lanes:
            lbs, ubs = perturbed_stores(cm, L, rng)
            for name in backends:
                kw = dict(lane_tile=min(8, L)) if name == "pallas" else {}
                dt, sweeps = bench(cm, lbs, ubs, name, **kw)
                rows.append(f"{name},{n_tasks},{L},{dt * 1e3:.2f},"
                            f"{dt * 1e3 / L:.3f},{sweeps}")
                records.append(dict(backend=name, n_tasks=n_tasks, lanes=L,
                                    ms_per_fixpoint=dt * 1e3,
                                    ms_per_lane=dt * 1e3 / L,
                                    sweeps_exec=sweeps))
    print("\n".join(rows))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"bench": "propagation", "rows": records}, fh,
                      indent=2)
    return rows


if __name__ == "__main__":
    main()
