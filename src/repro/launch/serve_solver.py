"""Solver-as-a-service launcher: continuous-batching scheduler under a
seeded open-loop load (DESIGN.md §15).

  PYTHONPATH=src python -m repro.launch.serve_solver \\
      --requests 20 --rate 50 --seed 0 --max-batch 4 --chunk 16

Generates a Poisson arrival trace over the default zoo mix (two
seed-stable shape buckets), drives a `SolverScheduler` on the host
clock, and prints the latency/occupancy summary plus per-bucket compile
counters.  With ``--parity`` every result is also checked bit-identical
against a sequential `Solver.solve` reference (deadline evictions
excepted).

Scope note: this serves the *constraint solver*.  The NN token-serving
demo lives in `repro.launch.serve`.
"""

from __future__ import annotations

import argparse
import json

from repro.core.api import SolveConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.loadgen import (poisson_trace, run_open_loop,
                                 sequential_reference)
from repro.serve.scheduler import SolverScheduler


def build_config(args) -> SolveConfig:
    return SolveConfig.preset(
        args.preset, backend=args.backend, n_lanes=args.lanes,
        eps_target=args.eps_target, chunk=args.chunk,
        max_depth=args.max_depth)


def main():
    ap = argparse.ArgumentParser(
        description="Serve the solver under open-loop Poisson load")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="arrival rate (requests/s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="lane-batch slots per bucket")
    ap.add_argument("--preset", default="prove")
    ap.add_argument("--backend", default="gather")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--eps-target", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-depth", type=int, default=256)
    ap.add_argument("--max-wall-s", type=float, default=600.0)
    ap.add_argument("--parity", action="store_true",
                    help="check results against sequential Solver.solve")
    ap.add_argument("--json", default=None,
                    help="also dump the metrics summary to this file")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = build_config(args)
    trace = poisson_trace(args.requests, args.rate, seed=args.seed)
    sched = SolverScheduler(cfg, max_batch=args.max_batch)
    handles = run_open_loop(sched, trace, max_wall_s=args.max_wall_s)

    summary = sched.recorder.summary()
    print(json.dumps(summary, indent=2, default=str))
    print("buckets:", json.dumps(sched.buckets(), indent=2))

    if args.parity:
        ref = sequential_reference(trace, build_config(args))
        n_bad = 0
        for _, h in handles:
            res = h.result()
            want = ref[h.request.request_id]
            got = (res.status, res.objective)
            if res.complete and got != want:
                n_bad += 1
                print(f"PARITY MISMATCH {h.request.request_id}: "
                      f"served={got} sequential={want}")
        print(f"parity: {'OK' if n_bad == 0 else f'{n_bad} MISMATCHES'} "
              f"over {len(handles)} requests")
        if n_bad:
            raise SystemExit(1)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(summary=summary, buckets=sched.buckets()), f,
                      indent=2, default=str)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
