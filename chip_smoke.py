#!/usr/bin/env python3
"""Chip smoke: drive the solver's main path once on a TPU, in one process.

    python3 chip_smoke.py            # phases 1-3 on one chip
    python3 chip_smoke.py --mesh4    # only the 4-chip distributed phase
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny   # CPU rehearsal

Phases, each through the public entry points and each checked:

1. single proof — rcpsp `large_instance` (96 tasks, 4 resources, seed 0)
   through `repro.solver.Solver` on the gather backend at 1024 lanes and
   a 4096-subproblem EPS pool: OPTIMAL, ground-checked, and the same
   objective as the same model solved on the host CPU at the default
   lane count (the plain reference);
2. batch — `Solver.solve_many` on 8 same-shape job-shop instances, each
   result equal field by field to its sequential `Solver.solve`;
3. serving — an open-loop Poisson trace through `repro.serve`, every
   result equal to `loadgen.sequential_reference`.

``--mesh4`` runs only the phase-1 model with ``mesh_shards=4`` over four
chips and on one chip, and checks that status and objective agree and
that the lane state really sits on four devices.

The numbers printed are those of one smoke run, not benchmark results.
The last line of standard output is one JSON object naming the device.
On a host without a TPU the script exits non-zero at its device check
and prints no result (``--tiny`` runs the phases at toy sizes first, as a
rehearsal of their control flow, and still fails that check).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# The same model is solved on the host CPU as the plain reference, so the
# CPU platform must be initialised next to the TPU.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

RESULT_FIELDS = ("status", "objective", "complete", "n_nodes", "n_fails",
                 "n_sols", "n_sweeps", "n_supersteps")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def device_info():
    import jax
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def require_tpu(n_chips):
    dev = device_info()
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's first device is {dev['platform']!r}; this smoke "
          f"runs only on a TPU")
    check(dev["count"] >= n_chips,
          f"needs {n_chips} TPU chip(s), JAX sees {dev['count']}")
    return dev


def sizes(tiny):
    from repro.core import models as zoo
    if tiny:
        return dict(rcpsp=zoo.small_instance, lanes=8, eps=16,
                    batch_lanes=4, serve_requests=6)
    return dict(rcpsp=zoo.large_instance, lanes=1024, eps=4096,
                batch_lanes=128, serve_requests=20)


def report(phase, res, stats, cold_wall, warm_wall=None):
    warm = ("" if warm_wall is None else
            f"warm_wall_s={warm_wall:.3f} "
            f"nodes_per_s_warm={res.n_nodes / max(warm_wall, 1e-9):.1f} ")
    log(f"{phase}: status={res.status} objective={res.objective} "
        f"supersteps={res.n_supersteps} nodes={res.n_nodes} "
        f"cold_compile_s={stats['compile_s']:.3f} "
        f"cold_wall_s={cold_wall:.3f} {warm}"
        f"placement={stats['placement']}")


def rcpsp_model(sz):
    from repro.core.models import rcpsp
    inst = sz["rcpsp"]("rcpsp", seed=0)
    m, handles = rcpsp.build_model(inst)
    return inst, handles, m.compile()


def check_placement(phase, stats):
    import jax
    want = str(jax.devices()[0])
    got = {d for p in stats["placement"] for d, _ in p}
    check(got == {want}, f"{phase}: outputs on {sorted(got)}, expected "
          f"only {want}")


def phase_single(sz, timeout_s):
    import jax
    from repro import solver
    from repro.core import eps
    from repro.core.models import ZOO, ground_check
    from repro.launch.compile_cache import cache_counts

    inst, handles, cm = rcpsp_model(sz)
    cfg = solver.SolveConfig.preset("prove", backend="gather",
                                    n_lanes=sz["lanes"],
                                    eps_target=sz["eps"],
                                    timeout_s=timeout_s)
    t0 = time.time()
    subs = eps.decompose(cm, cfg.resolved_eps_target(),
                         cfg.search_options())
    log(f"phase 1: rcpsp {cm.n_vars} vars, EPS pool {subs[0].shape[0]} "
        f"subproblems decomposed on the host in {time.time() - t0:.3f}s")

    sess = solver.Solver(cfg)
    t0 = time.time()
    res = sess.solve(cm, subs=subs)
    cold = time.time() - t0
    t0 = time.time()
    warm_res = sess.solve(cm, subs=subs)
    warm = time.time() - t0
    stats = sess.session_stats()
    report("phase 1 single proof", res, stats, cold, warm)
    check(res.status == solver.OPTIMAL,
          f"phase 1: status {res.status}, expected OPTIMAL")
    check(ground_check(ZOO["rcpsp"], inst, handles, res) is True,
          "phase 1: the solution fails the ground check")
    check((warm_res.status, warm_res.objective)
          == (res.status, res.objective),
          "phase 1: the warm solve disagrees with the cold one")
    check(not sess.stats["last_solve_cold"], "phase 1: warm solve compiled")
    check_placement("phase 1", stats)

    # a second compile of the same shape in a fresh session: the
    # persistent cache should answer it
    hits0 = cache_counts()["hits"]
    solver.Solver(cfg).solve(cm, subs=subs, max_supersteps=1)
    hit = cache_counts()["hits"] > hits0
    log(f"compile cache: second compile of the phase-1 runner "
        f"{'hit' if hit else 'missed'} the persistent cache "
        f"({cache_counts()})")

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        _, _, cm_cpu = rcpsp_model(sz)
        ref_sess = solver.Solver(solver.SolveConfig.preset(
            "prove", timeout_s=timeout_s))
        t0 = time.time()
        ref = ref_sess.solve(cm_cpu)
        ref_wall = time.time() - t0
    log(f"phase 1 reference on {cpu}: status={ref.status} "
        f"objective={ref.objective} lanes={ref_sess.config.n_lanes} "
        f"wall_s={ref_wall:.3f} "
        f"placement={ref_sess.session_stats()['placement']}")
    check(ref.status == solver.OPTIMAL, "phase 1: CPU reference not OPTIMAL")
    check(res.objective == ref.objective,
          f"phase 1: objective {res.objective} != CPU reference "
          f"{ref.objective}")
    log("phase 1 passed: OPTIMAL, ground-checked, objective equals the "
        "CPU reference")
    return res


def phase_batch(sz, timeout_s):
    import numpy as np
    from repro import solver
    from repro.core import models as zoo

    cms = [zoo.ZOO["jobshop"].build_model(zoo.bench_instance(
        "jobshop", seed=s))[0].compile() for s in range(8)]
    cfg = solver.SolveConfig.preset("prove", n_lanes=sz["batch_lanes"],
                                    timeout_s=timeout_s)
    sess = solver.Solver(cfg)
    t0 = time.time()
    many = sess.solve_many(cms)
    cold = time.time() - t0
    t0 = time.time()
    many_warm = sess.solve_many(cms)
    warm = time.time() - t0
    stats = sess.session_stats()
    nodes = sum(r.n_nodes for r in many)
    log(f"phase 2 batch: {len(cms)} jobshop bench instances in one "
        f"dispatch, statuses={[r.status for r in many]} "
        f"objectives={[r.objective for r in many]} "
        f"supersteps={max(r.n_supersteps for r in many)} nodes={nodes} "
        f"cold_compile_s={stats['compile_s']:.3f} cold_wall_s={cold:.3f} "
        f"warm_wall_s={warm:.3f} "
        f"nodes_per_s_warm={nodes / max(warm, 1e-9):.1f} "
        f"placement={stats['placement']}")
    check_placement("phase 2", stats)
    seq_sess = solver.Solver(cfg)
    for k, (a, w, cm) in enumerate(zip(many, many_warm, cms)):
        b = seq_sess.solve(cm)
        for f in RESULT_FIELDS:
            check(getattr(a, f) == getattr(b, f) == getattr(w, f),
                  f"phase 2: instance {k} field {f}: batched "
                  f"{getattr(a, f)!r} / warm {getattr(w, f)!r} != "
                  f"sequential {getattr(b, f)!r}")
        check(np.array_equal(a.solution, b.solution),
              f"phase 2: instance {k} solution differs from sequential")
        check(a.status == solver.OPTIMAL, f"phase 2: instance {k} "
              f"status {a.status}")
    log(f"phase 2 passed: {len(cms)} batched results equal their "
        f"sequential solves field by field ({', '.join(RESULT_FIELDS)}, "
        f"solution)")


def phase_serve(sz):
    from repro.core.api import SolveConfig
    from repro.serve.loadgen import (poisson_trace, run_open_loop,
                                     sequential_reference)
    from repro.serve.scheduler import SolverScheduler

    # the `launch/serve_solver.py` defaults
    cfg = SolveConfig.preset("prove", n_lanes=8, eps_target=16, chunk=16,
                             max_depth=256)
    trace = poisson_trace(sz["serve_requests"], 50.0, seed=0)
    ref = sequential_reference(trace, cfg)
    sched = SolverScheduler(cfg, max_batch=4)
    for rnd in ("cold", "warm"):
        t0 = time.time()
        handles = run_open_loop(sched, trace, max_wall_s=600.0)
        wall = time.time() - t0
        results = [h.result() for _, h in handles]
        summ = sched.recorder.summary()
        stats = sched.session.session_stats()
        nodes = sum(r.n_nodes for r in results)
        log(f"phase 3 serving ({rnd}): {len(results)} requests over "
            f"{len(sched.buckets())} buckets, wall_s={wall:.3f} "
            f"compile_s={stats['compile_s']:.3f} "
            f"supersteps_max={max(r.n_supersteps for r in results)} "
            f"nodes={nodes} nodes_per_s={nodes / max(wall, 1e-9):.1f} "
            f"latency_s={summ['latency_s']} "
            f"placement={stats['placement']}")
        check_placement("phase 3", stats)
        for (_, h), res in zip(handles, results):
            rid = h.request.request_id
            check(res.complete, f"phase 3: request {rid} not complete "
                  f"({res.status})")
            check((res.status, res.objective) == ref[rid],
                  f"phase 3: request {rid} served "
                  f"{(res.status, res.objective)} != sequential {ref[rid]}")
    log(f"phase 3 passed: {len(trace)} requests, twice, equal "
        f"sequential_reference")


def phase_mesh4(sz, timeout_s):
    from repro import solver
    from repro.core import dist_solve, eps

    _, _, cm = rcpsp_model(sz)
    cfg = solver.SolveConfig.preset("prove", backend="gather",
                                    n_lanes=sz["lanes"],
                                    eps_target=sz["eps"],
                                    timeout_s=timeout_s)
    t0 = time.time()
    subs = eps.decompose(cm, cfg.resolved_eps_target(),
                         cfg.search_options())
    log(f"mesh4: EPS pool {subs[0].shape[0]} subproblems in "
        f"{time.time() - t0:.3f}s")

    one = solver.Solver(cfg)
    t0 = time.time()
    r1 = one.solve(cm, subs=subs)
    report("mesh4 one chip", r1, one.session_stats(), time.time() - t0)

    cfg4 = cfg.replace(mesh_shards=4)
    sess = solver.Solver(cfg4)
    t0 = time.time()
    r4, trace = dist_solve.solve_dist(cm, cfg4, subs=subs, session=sess)
    cold = time.time() - t0
    t0 = time.time()
    r4w, _ = dist_solve.solve_dist(cm, cfg4, subs=subs, session=sess)
    warm = time.time() - t0
    stats = sess.session_stats()
    report("mesh4 four chips", r4, stats, cold, warm)
    log(f"mesh4 DistTrace: shards=4 chunks={trace.n_chunks} "
        f"bound_syncs={trace.n_bound_syncs} "
        f"supersteps={trace.n_supersteps} steals={trace.n_steals} "
        f"steal_events={trace.steal_events} "
        f"remeshes={len(trace.remesh_events)}")
    (placement,) = stats["placement"]
    devices = {d for d, _ in placement}
    check(len(devices) == 4 and len(placement) == 4,
          f"mesh4: lane state is on {sorted(devices)}, expected 4 devices")
    check(all(shape[0] == cfg.n_lanes for _, shape in placement),
          f"mesh4: per-device lane shards {placement}, expected "
          f"{cfg.n_lanes} lanes each")
    for r in (r4, r4w):
        check((r.status, r.objective) == (r1.status, r1.objective),
              f"mesh4: 4 shards gave {(r.status, r.objective)}, one chip "
              f"{(r1.status, r1.objective)}")
    check(r1.status == solver.OPTIMAL, f"mesh4: status {r1.status}")
    log("mesh4 passed: 4-shard status and objective equal one chip's")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the 4-chip distributed phase")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the phases at toy sizes (still fails "
                         "the device check off a TPU)")
    ap.add_argument("--timeout-s", type=float, default=600.0,
                    help="per-solve budget")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"no repro package under {SRC}: run from a checkout of the repo")
        return 2
    sys.path.insert(0, SRC)

    n_chips = 4 if args.mesh4 else 1
    try:
        if not args.tiny:
            require_tpu(n_chips)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache at {enable_compile_cache()}")
        log(f"devices: {device_info()}")
        sz = sizes(args.tiny)
        if args.mesh4:
            phase_mesh4(sz, args.timeout_s)
        else:
            log("the pallas and pallas_resident backends are skipped: "
                "Mosaic does not lower their kernels for a TPU yet "
                "(kernels/fixpoint_kernel.MOSAIC_REFUSAL)")
            phase_single(sz, args.timeout_s)
            phase_batch(sz, args.timeout_s)
            phase_serve(sz)
        dev = require_tpu(n_chips)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
