"""Table-1 analogue: TURBO-style batched engine vs sequential CPU solver.

The paper compares TURBO (GPU, 3072 cores) against parallel GECODE (6
cores) on Patterson + PSPLIB j30.  This container has one CPU and no
PSPLIB files, so (DESIGN.md §8): instances come from the seeded generator
in the same families, GECODE's role is played by our event-driven
sequential solver (same model, same branching), and the batched engine
runs with `--lanes` vectorized lanes.  Columns mirror Table 1:
feas / opt / nodes-per-sec / time.  The GPU-side claim that survives CPU
emulation is *throughput scaling with lanes* (bench_propagation.py) and
*identical objectives* (determinism, Thm 6); wall-clock superiority needs
the real accelerator.

All solving goes through the session API (`repro.solver`, DESIGN.md
§11); the `prove` / `fast` presets replace the old hand-rolled
SearchOptions recipes.

``--zoo`` adds a per-model section over the whole model zoo (DESIGN.md
§10: rcpsp, nqueens, coloring, knapsack, jobshop) through the
EPS-decomposed engine; ``--zoo-smoke --json BENCH_propagation_smoke.json``
is the `make check` tier — small instances, records merged into the bench
JSON as its `solver` section.  Since §12 each record also carries the
typed propagator-table size (`n_props`, per-kind split, and
`n_props_decomposed` — the pre-§12 ReifLinLe blowup the native lowering
replaced), so the table-size win is tracked per PR alongside nodes/s.

``--throughput`` is the serving-story benchmark (DESIGN.md §11): one
`Solver` session over 4 same-shape knapsack instances — cold-vs-warm
solve (compile amortization) and `solve_many` batched dispatch
(instances/s) vs sequential warm solves; records land in the `api`
section of the bench JSON.

``--superstep-bench`` is the resident-megakernel metric (DESIGN.md §13):
per backend, one warm solve driven at ``chunk=1`` host granularity,
recording ms_per_superstep / supersteps_per_sec / dispatches_per_solve
into the `superstep` section — the unfused backends pay one host
dispatch per superstep, ``pallas_resident`` one per K supersteps.

``--serve-bench`` is the solver-as-a-service metric (DESIGN.md §15): a
seeded open-loop Poisson load (≥50 requests over ≥2 shape buckets with
mixed deadlines) through the continuous-batching `SolverScheduler`,
hard-failing unless every completed result is bit-identical to a
sequential `Solver.solve` reference, slots actually batch (>1 request
co-resident) and each bucket compiled at most once; p50/p99
TTFI/latency, queue depth, occupancy and instances/s land in the
`serving` section.

``--scale-smoke`` is the sparse-bank scale metric (DESIGN.md §16):
analytic dense-vs-sparse peak bank-tile bytes for nqueens N ∈ {32, 128,
256, 512} (hard-failing unless the sparse O(M²) tile is strictly smaller
than the dense O(N³) tile at N ≥ 128), forced dense/sparse
objective-parity solves on smoke instances (hard-failing on any
status/objective mismatch), and bounded large-tier throughput probes
(props/s at the root fixpoint, nodes/s over a supersteps-capped solve);
records land in the `scale` section.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import List

from repro import solver
from repro.core import baseline
from repro.core import models as zoo
from repro.core.backend import available_backends
from repro.core.models import knapsack, rcpsp


def suite(kind: str, full: bool):
    if kind == "patterson-like":
        sizes = [14, 18, 22] if full else [6, 8, 10]
        return [rcpsp.generate(n, n_resources=3, seed=s, edge_prob=0.25)
                for n in sizes for s in range(4 if full else 3)]
    if kind == "j30-like":
        sizes = [30] if full else [12]
        return [rcpsp.generate(n, n_resources=4, seed=s, edge_prob=0.2)
                for n in sizes for s in range(4 if full else 3)]
    raise ValueError(kind)


def run_suite(name: str, instances: List[rcpsp.RCPSP], timeout_s: float,
              lanes: int, subs: int, rows: List[str],
              backend: str = "gather"):
    cfg = solver.SolveConfig.preset(
        "prove", n_lanes=lanes, eps_target=subs, timeout_s=timeout_s,
        backend=backend)
    # §Perf P0/H1: the `fast` preset caps sweeps per superstep (bounded
    # chaotic iteration; identical optima, 1.7–2.5× faster)
    cfg_fast = solver.SolveConfig.preset(
        "fast", n_lanes=lanes, eps_target=subs, timeout_s=timeout_s,
        backend=backend)
    sess = solver.Solver(cfg)
    agg = {}
    for solver_name in ("sequential", "turbo-jax", "turbo-jax-opt"):
        feas = opt = nodes = 0
        wall = 0.0
        objs = []
        for inst in instances:
            m, _ = rcpsp.build_model(inst)
            cm = m.compile()
            if solver_name == "sequential":
                res = baseline.SequentialSolver(cm, cfg.search_options()) \
                    .solve(timeout_s=timeout_s)
            elif solver_name == "turbo-jax":
                res = sess.solve(cm)
            else:
                res = sess.solve(cm, config=cfg_fast)
            feas += res.solution is not None
            opt += res.status == solver.OPTIMAL
            nodes += res.n_nodes
            wall += res.wall_s
            objs.append((res.objective, res.status))
        agg[solver_name] = objs
        rows.append(f"{name},{solver_name},{len(instances)},{feas},{opt},"
                    f"{nodes / max(wall, 1e-9):.0f},{wall:.1f}")
    # determinism cross-check: identical objectives wherever BOTH proved
    # optimality (timed-out incumbents legitimately differ)
    def _mism(x, y):
        return sum(1 for (a, sa), (b, sb) in zip(x, y)
                   if sa == solver.OPTIMAL and sb == solver.OPTIMAL
                   and a != b)
    mism = _mism(agg["sequential"], agg["turbo-jax"]) + \
        _mism(agg["turbo-jax"], agg["turbo-jax-opt"])
    rows.append(f"{name},objective-mismatches,{len(instances)},{mism},,,")
    return rows


def run_zoo(timeout_s: float, lanes: int, eps_target: int, rows: List[str],
            backend="gather", smoke: bool = False, seed: int = 0):
    """Per-model solver numbers across the whole zoo (DESIGN.md §10):
    nodes/s and time-to-optimum through the EPS-decomposed engine.
    `backend` may be a name or a sequence of names (the smoke tier
    records every registered backend, pallas_resident included, so the
    `solver` section tracks objective parity per backend per PR).
    Returns the JSON-able records for the BENCH `solver` section."""
    backends = ((backend,) if isinstance(backend, str) else tuple(backend))
    records = []
    objectives = {}                       # model -> {backend: objective}
    for be in backends:
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=lanes, eps_target=eps_target,
            timeout_s=timeout_s, backend=be, max_depth=512)
        sess = solver.Solver(cfg)
        for name in sorted(zoo.ZOO):
            mod = zoo.ZOO[name]
            inst = (zoo.small_instance(name, seed=seed) if smoke
                    else zoo.bench_instance(name, seed=seed))
            m, h = mod.build_model(inst)
            cm = m.compile()
            # typed-table size vs the pre-§12 ReifLinLe decomposition
            # (models without a native lowering — knapsack — compile
            # identically)
            import inspect
            if "decompose" in inspect.signature(mod.build_model).parameters:
                cmd = mod.build_model(inst, decompose=True)[0].compile()
                decomposed_props = cmd.total_props
            else:
                decomposed_props = cm.total_props
            res = sess.solve(cm)
            # True/False = checked; None = nothing to check (timeout/UNSAT)
            checked = zoo.ground_check(mod, inst, h, res)
            rows.append(f"zoo,{name},{be},{res.status},{res.objective},"
                        f"{res.nodes_per_sec:.0f},{res.wall_s:.2f},"
                        f"{checked},P={cm.total_props}/{decomposed_props}")
            objectives.setdefault(name, {})[be] = (res.status,
                                                   res.objective)
            # time to the *proven* optimum: wall clock until B&B returned
            # OPTIMAL, jit compile included (the honest CPU-emulation
            # figure); the improvements trace also gives time-to-incumbent
            records.append(dict(
                model=name, instance=inst.name, backend=be,
                status=res.status, objective=res.objective,
                n_props=cm.total_props,
                n_props_by_kind=dict(lin=cm.n_props, alldiff=cm.n_alldiff,
                                     cumulative=cm.n_cumulative),
                n_props_decomposed=decomposed_props,
                n_vars=cm.n_vars,
                n_nodes=res.n_nodes, nodes_per_sec=res.nodes_per_sec,
                n_supersteps=res.n_supersteps,
                time_to_proven_optimum_s=(
                    res.wall_s if res.status == solver.OPTIMAL else None),
                time_to_first_incumbent_s=(
                    res.improvements[0].wall_s if res.improvements
                    else None),
                wall_s=res.wall_s, ground_check=checked))
    # cross-backend determinism: proven optima must agree bit-for-bit
    for name, per_be in objectives.items():
        proven = {o for s, o in per_be.values() if s == solver.OPTIMAL}
        if len(proven) > 1:
            raise SystemExit(f"zoo objective mismatch on {name}: {per_be}")
    return records


def run_superstep_bench(rows: List[str], backends, lanes: int = 8,
                        eps_target: int = 16, timeout_s: float = 300.0,
                        supersteps_per_launch: int = 16):
    """Superstep-orchestration overhead per backend (the ISSUE-6 metric):
    drive each solve at the finest host granularity — ``chunk=1`` so
    every unfused runner call is exactly ONE superstep (one host
    dispatch of the 4-phase `lanes_step`), while ``pallas_resident``
    returns per K-superstep megakernel launch — and count the host
    dispatches to completion via the `solve_iter` event stream (one
    event per runner call, by the Progress granularity contract).

    Records ms_per_superstep / supersteps_per_sec / dispatches_per_solve
    (warm timings; the cold solve is run first to compile) for the BENCH
    `superstep` section.
    """
    inst = zoo.small_instance("rcpsp", seed=0)
    m, _ = zoo.ZOO["rcpsp"].build_model(inst)
    cm = m.compile()
    records = []
    for backend in backends:
        kw = dict(supersteps_per_launch=supersteps_per_launch) \
            if backend == "pallas_resident" else {}
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=lanes, eps_target=eps_target, chunk=1,
            timeout_s=timeout_s, backend=backend, max_depth=512, **kw)
        sess = solver.Solver(cfg)
        res = sess.solve(cm)                       # cold: compile
        wall = float("inf")
        for _ in range(5):                         # warm: best of 5 drains
            dispatches = 0
            for ev in sess.solve_iter(cm):
                dispatches += 1
                if ev.final:
                    res = ev.result
            # the Progress timing contract (api.Progress): wall_s is the
            # event stream's own elapsed-since-solve-start clock — the
            # single timing source shared with the serving metrics, so
            # this bench never re-times what solve_iter already stamped
            wall = min(wall, res.wall_s)
        n_steps = max(res.n_supersteps, 1)
        rec = dict(
            backend=backend, model=inst.name,
            supersteps_per_launch=(supersteps_per_launch
                                   if backend == "pallas_resident" else 1),
            n_supersteps=res.n_supersteps,
            dispatches_per_solve=dispatches,
            ms_per_superstep=round(1e3 * wall / n_steps, 3),
            supersteps_per_sec=round(n_steps / max(wall, 1e-9), 1),
            status=res.status, objective=res.objective,
            wall_s=round(wall, 4))
        records.append(rec)
        rows.append(
            f"superstep,{backend},K={rec['supersteps_per_launch']},"
            f"steps={rec['n_supersteps']},"
            f"dispatches={rec['dispatches_per_solve']},"
            f"{rec['ms_per_superstep']}ms/step,"
            f"{rec['supersteps_per_sec']}steps/s,{res.status}")
    return records


def run_throughput(lanes: int, eps_target: int, rows: List[str],
                   backends=("gather",), n_instances: int = 4,
                   seed0: int = 0, timeout_s: float = 120.0):
    """The serving benchmark (DESIGN.md §11): cold vs warm session solve
    and `solve_many` batched throughput on same-shape knapsack
    instances, per backend.  Returns records for the BENCH `api`
    section."""
    instances = [knapsack.generate(n=6, seed=seed0 + s)
                 for s in range(n_instances)]
    cms = []
    for inst in instances:
        m, _ = knapsack.build_model(inst)
        cms.append(m.compile())

    records = []
    for backend in backends:
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=lanes, eps_target=eps_target,
            timeout_s=timeout_s, backend=backend)
        sess = solver.Solver(cfg)

        t0 = time.time()
        cold_res = sess.solve(cms[0])
        cold_s = time.time() - t0
        assert sess.stats["last_solve_cold"], "first solve must compile"

        t0 = time.time()
        warm_res = sess.solve(cms[0])
        warm_s = time.time() - t0
        assert not sess.stats["last_solve_cold"], "second solve recompiled!"
        assert warm_res.objective == cold_res.objective

        # sequential warm throughput: every instance through the session
        t0 = time.time()
        seq = [sess.solve(cm) for cm in cms]
        seq_s = time.time() - t0

        # batched: ONE device dispatch for all instances (cold for the
        # batched runner, so also record a warm repeat)
        t0 = time.time()
        many = sess.solve_many(cms)
        many_cold_s = time.time() - t0
        t0 = time.time()
        many = sess.solve_many(cms)
        many_s = time.time() - t0

        parity = all(a.status == b.status and a.objective == b.objective
                     for a, b in zip(many, seq))
        stats = sess.session_stats()
        rec = dict(
            backend=backend, n_instances=len(cms),
            model="knapsack-n6",
            cold_solve_s=round(cold_s, 4), warm_solve_s=round(warm_s, 4),
            cold_warm_speedup=round(cold_s / max(warm_s, 1e-9), 1),
            compile_s=round(stats["compile_s"], 4),
            n_compiles=stats["n_compiles"],
            runner_builds=stats["runner_builds"],
            runner_hits=stats["runner_hits"],
            solve_many_cold_s=round(many_cold_s, 4),
            solve_many_warm_s=round(many_s, 4),
            instances_per_sec_batched=round(len(cms) / max(many_s, 1e-9), 1),
            instances_per_sec_sequential=round(
                len(cms) / max(seq_s, 1e-9), 1),
            batched_vs_sequential=round(seq_s / max(many_s, 1e-9), 2),
            parity_ok=parity,
            objectives=[r.objective for r in many],
        )
        records.append(rec)
        rows.append(
            f"api,{backend},cold={cold_s:.2f}s,warm={warm_s:.3f}s,"
            f"x{rec['cold_warm_speedup']},batched="
            f"{rec['instances_per_sec_batched']}/s,sequential="
            f"{rec['instances_per_sec_sequential']}/s,parity={parity}")
        if not parity:
            raise SystemExit(
                f"solve_many parity FAILED on {backend}: "
                f"{[(r.status, r.objective) for r in many]} vs "
                f"{[(r.status, r.objective) for r in seq]}")
    return records


def run_dist_bench(rows: List[str], timeout_s: float = 120.0,
                   lanes: int = 4, eps_target: int = 16,
                   meshes=(1, 2, 4, 8)):
    """Distributed-EPS benchmark (core/dist_solve.py, DESIGN.md §14):
    per mesh size, warm-solve wall time (speedup vs mesh=1), steal
    events, bound-all-reduce count, and status/objective parity with the
    single-shard solve.  Returns records for the BENCH `distributed`
    section.  Mesh sizes beyond `jax.device_count()` are skipped — the
    make-check invocation fakes 8 host devices via XLA_FLAGS."""
    import jax

    from repro.core import dist_solve
    from repro.core import models as zoo

    m, _ = zoo.ZOO["coloring"].build_model(
        zoo.small_instance("coloring", seed=0))
    cm = m.compile()
    n_dev = jax.device_count()
    records = []
    ref = None
    warm1 = None
    for D in [d for d in meshes if d <= n_dev]:
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=lanes, eps_target=eps_target,
            timeout_s=timeout_s, mesh_shards=D)
        sess = solver.Solver(cfg)
        res, _ = dist_solve.solve_dist(cm, cfg, session=sess)   # cold
        t0 = time.time()
        res, tr = dist_solve.solve_dist(cm, cfg, session=sess)  # warm
        warm_s = time.time() - t0
        if D == 1:
            ref, warm1 = res, warm_s
        parity = (res.status == ref.status
                  and res.objective == ref.objective)
        rec = dict(
            mesh=D, model="coloring-small", status=res.status,
            objective=res.objective, warm_solve_s=round(warm_s, 4),
            speedup_vs_mesh1=round(warm1 / max(warm_s, 1e-9), 2),
            n_chunks=tr.n_chunks, n_bound_allreduce=tr.n_bound_syncs,
            n_steals=tr.n_steals, n_remeshes=len(tr.remesh_events),
            parity_ok=parity)
        records.append(rec)
        rows.append(
            f"distributed,mesh={D},{res.status},obj={res.objective},"
            f"warm={warm_s:.3f}s,x{rec['speedup_vs_mesh1']},"
            f"steals={tr.n_steals},allreduce={tr.n_bound_syncs},"
            f"parity={parity}")
        if not parity:
            raise SystemExit(
                f"dist parity FAILED at mesh={D}: "
                f"{(res.status, res.objective)} vs "
                f"{(ref.status, ref.objective)}")
    if n_dev < max(meshes):
        rows.append(f"distributed,NOTE,only {n_dev} device(s) visible; "
                    f"run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=8 for the "
                    f"full sweep")
    return records


def run_serve_bench(rows: List[str], *, n_requests: int = 50,
                    rate_rps: float = 100.0, seed: int = 0,
                    max_batch: int = 4, backend: str = "gather",
                    max_wall_s: float = 600.0):
    """Solver-as-a-service under seeded open-loop load (DESIGN.md §15).

    Drives the continuous-batching `SolverScheduler` with a fixed-seed
    Poisson trace over the default zoo mix (two seed-stable shape
    buckets, mixed deadlines) and HARD-FAILS (SystemExit) unless:

    * parity — every completed request's (status, objective) is
      bit-identical to a sequential warm `Solver.solve` of the same
      instance;
    * batching — more than one request was co-resident in a lane batch
      at some quantum (the continuous-batching win actually happened);
    * compile discipline — every bucket cold-compiled at most once
      (late same-shape requests joined warm).

    Returns one record for the BENCH `serving` section: the
    `MetricsRecorder` summary (p50/p99 TTFI / time-to-optimal / latency,
    queue depth, occupancy, instances/s) plus per-bucket counters.
    """
    from repro.serve.loadgen import (poisson_trace, run_open_loop,
                                     sequential_reference)
    from repro.serve.scheduler import SolverScheduler

    cfg = solver.SolveConfig.preset(
        "prove", backend=backend, n_lanes=8, eps_target=16, chunk=16,
        max_depth=256)
    trace = poisson_trace(n_requests, rate_rps, seed=seed)
    sched = SolverScheduler(cfg, max_batch=max_batch)
    handles = run_open_loop(sched, trace, max_wall_s=max_wall_s)
    summary = sched.recorder.summary()
    buckets = sched.buckets()

    ref = sequential_reference(trace, cfg)
    n_checked = n_bad = 0
    for _, h in handles:
        res = h.result()
        if not res.complete:        # deadline evictions have no oracle
            continue
        n_checked += 1
        if (res.status, res.objective) != ref[h.request.request_id]:
            n_bad += 1
            print(f"serve-bench PARITY MISMATCH {h.request.request_id}: "
                  f"served={(res.status, res.objective)} "
                  f"sequential={ref[h.request.request_id]}")
    max_live = summary["batch_live_slots"].get("max", 0.0)
    bad_compiles = {k: v["n_compiles"] for k, v in buckets.items()
                    if v["n_compiles"] > 1}
    if n_bad:
        raise SystemExit(f"serve-bench: {n_bad}/{n_checked} parity "
                         f"mismatches vs sequential Solver.solve")
    if len(buckets) < 2:
        raise SystemExit(f"serve-bench: expected >= 2 shape buckets, "
                         f"got {list(buckets)}")
    if not max_live > 1:
        raise SystemExit(f"serve-bench: no continuous batching happened "
                         f"(max live slots {max_live} <= 1)")
    if bad_compiles:
        raise SystemExit(f"serve-bench: buckets recompiled after their "
                         f"cold compile: {bad_compiles}")

    rec = dict(n_requests=n_requests, rate_rps=rate_rps, seed=seed,
               max_batch=max_batch, backend=backend,
               parity_checked=n_checked, parity_ok=True,
               summary=summary, buckets=buckets)
    rows.append(
        f"serving,{backend},req={n_requests},rate={rate_rps}/s,"
        f"buckets={len(buckets)},"
        f"ttfi_p50={summary['ttfi_s'].get('p50')}s,"
        f"ttfi_p99={summary['ttfi_s'].get('p99')}s,"
        f"lat_p50={summary['latency_s'].get('p50')}s,"
        f"lat_p99={summary['latency_s'].get('p99')}s,"
        f"occ_max={summary['batch_occupancy'].get('max')},"
        f"live_max={max_live},"
        f"inst/s={summary['instances_per_sec']},parity=OK")
    return [rec]


def run_scale_smoke(rows: List[str], timeout_s: float = 120.0,
                    seed: int = 0):
    """Scale-tier records (DESIGN.md §16) for the bench `scale` section.

    Three bounded sub-parts (the make-check tier):

    * ``bank_bytes`` — analytic per-lane tile scratch, dense O(N³) vs
      sparse O(M²), for nqueens N ∈ {32, 128, 256, 512} via the same
      estimators `compile.py`'s crossover and `kernels.vmem_budget`
      use; **hard-fails** unless sparse < dense at N ≥ 128;
    * ``parity`` — full proven solves of smoke-tier alldiff/cumulative
      models under both *forced* layouts; **hard-fails** on any
      status/objective mismatch (the dense/sparse determinism gate);
    * ``large`` — the industrial-size tier compiled onto the auto
      (sparse) layouts: root-fixpoint props/s and a supersteps-capped
      solve's nodes/s per model (throughput probes, not proofs — the
      proven-optimum run is the `large`-marked test).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fixpoint as F
    from repro.core.compile import (alldiff_dense_tile_bytes,
                                    alldiff_sparse_tile_bytes,
                                    cumulative_dense_tile_bytes,
                                    cumulative_sparse_tile_bytes)
    from repro.core.models import nqueens as nq_mod

    records = []

    # ---- (a) peak bank-tile bytes, dense vs sparse ----------------------
    for n in (32, 128, 256, 512):
        m, _ = nq_mod.build_model(nq_mod.generate(n, seed=seed))
        cm = m.compile()                        # auto crossover layout
        it = cm.jdtype.itemsize
        dense_b = alldiff_dense_tile_bytes(cm.n_alldiff, cm.ad_width, it)
        sparse_b = alldiff_sparse_tile_bytes(cm.ad_packed, it)
        rows.append(f"scale,bank_bytes,nqueens-{n},layout={cm.ad_layout},"
                    f"dense={dense_b},sparse={sparse_b},"
                    f"ratio={dense_b / max(sparse_b, 1):.1f}x")
        records.append(dict(
            kind="bank_bytes", model=f"nqueens-{n}", layout=cm.ad_layout,
            ad_packed=cm.ad_packed, dense_tile_bytes=dense_b,
            sparse_tile_bytes=sparse_b))
        if n >= 128 and not sparse_b < dense_b:
            raise SystemExit(
                f"scale: sparse AllDifferent tile not smaller than the "
                f"dense O(N³) tile at N={n}: {sparse_b} >= {dense_b}")

    # ---- (b) dense vs sparse objective parity (hard gate) ---------------
    for name in ("nqueens", "rcpsp"):
        mod = zoo.ZOO[name]
        inst = zoo.small_instance(name, seed=seed)
        m, h = mod.build_model(inst)
        out = {}
        for layout in ("dense", "sparse"):
            cm = m.compile(bank_layout=layout)
            cfg = solver.SolveConfig.preset(
                "prove", n_lanes=8, eps_target=16, timeout_s=timeout_s)
            res = solver.Solver(cfg).solve(cm)
            out[layout] = (res.status, res.objective)
            checked = zoo.ground_check(mod, inst, h, res)
            rows.append(f"scale,parity,{name},{layout},{res.status},"
                        f"{res.objective},{checked}")
            records.append(dict(
                kind="parity", model=name, instance=inst.name,
                layout=layout, status=res.status, objective=res.objective,
                ground_check=checked))
        if out["dense"] != out["sparse"]:
            raise SystemExit(
                f"scale: dense/sparse status/objective mismatch on "
                f"{name}: {out}")

    # ---- (c) large-tier throughput probes (auto = sparse layouts) -------
    for name in ("nqueens", "rcpsp", "jobshop"):
        inst = zoo.large_instance(name, seed=seed)
        m, _ = zoo.ZOO[name].build_model(inst)
        cm = m.compile()
        it = cm.jdtype.itemsize
        bank_bytes = dict(
            alldiff=(alldiff_sparse_tile_bytes(cm.ad_packed, it)
                     if cm.ad_layout == "sparse"
                     else alldiff_dense_tile_bytes(cm.n_alldiff,
                                                   cm.ad_width, it)),
            cumulative=(cumulative_sparse_tile_bytes(
                            cm.n_cumulative, cm.cu_width, it)
                        if cm.cu_layout == "sparse"
                        else cumulative_dense_tile_bytes(
                            cm.n_cumulative, cm.cu_width, cm.horizon, it)))
        L = 4
        lb = jnp.broadcast_to(cm.lb0[None], (L, cm.n_vars))
        ub = jnp.broadcast_to(cm.ub0[None], (L, cm.n_vars))
        F.fixpoint_batch(cm, lb, ub, max_iters=2)[0].block_until_ready()
        t0 = time.time()
        sweeps = int(np.asarray(
            F.fixpoint_batch(cm, lb, ub, max_iters=8)[2]).sum())
        wall = max(time.time() - t0, 1e-9)
        props_per_sec = cm.total_props * sweeps / wall
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=4, eps_target=4, timeout_s=timeout_s,
            max_supersteps=6)
        res = solver.Solver(cfg).solve(cm)
        rows.append(
            f"scale,large,{inst.name},ad={cm.ad_layout},cu={cm.cu_layout},"
            f"props/s={props_per_sec:.0f},nodes/s={res.nodes_per_sec:.0f},"
            f"peak_bank_bytes={max(bank_bytes.values())}")
        records.append(dict(
            kind="large", model=name, instance=inst.name,
            n_vars=cm.n_vars, n_props=cm.total_props,
            ad_layout=cm.ad_layout, cu_layout=cm.cu_layout,
            ad_packed=cm.ad_packed,
            peak_bank_tile_bytes=bank_bytes,
            root_fixpoint_sweeps=sweeps,
            props_per_sec=props_per_sec,
            capped_solve_status=res.status,
            nodes_per_sec=res.nodes_per_sec,
            n_nodes=res.n_nodes, wall_s=res.wall_s))
    return records


def run_ct_smoke(rows: List[str], timeout_s: float = 120.0, seed: int = 0):
    """Compact-Table smoke records (DESIGN.md §17) for the bench
    `compact_table` section.

    Per extensional zoo model (crossword, configuration):

    * root-fixpoint **props/s** with the bitset store carried (the
      native CT path) plus the per-bank statics — currtable words
      (`ct_words`), bitset words per variable (`n_words`);
    * a proven solve on EVERY backend; **hard-fails** on any
      status/objective mismatch or ground-check failure (the §17
      determinism gate);
    * the same instance under ``decompose=True`` (the reified
      disjunction oracle) — **hard-fails** on status/objective
      mismatch vs native; the wall-clock ratio is the
      `native_speedup` headline.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bitset as B
    from repro.core import fixpoint as F

    records = []
    for name in ("crossword", "configuration"):
        mod = zoo.ZOO[name]
        inst = zoo.small_instance(name, seed=seed)
        mn, h = mod.build_model(inst)
        md, _ = mod.build_model(inst, decompose=True)
        cmn, cmd = mn.compile(), md.compile()

        # ---- root-fixpoint propagation throughput (bitset carried) ----
        L = 8
        lb = jnp.broadcast_to(cmn.lb0[None], (L, cmn.n_vars))
        ub = jnp.broadcast_to(cmn.ub0[None], (L, cmn.n_vars))
        dom = B.from_bounds(lb, ub, jnp.asarray(cmn.dom_off), cmn.n_words,
                            track=jnp.asarray(cmn.dom_track))
        F.fixpoint_batch(cmn, lb, ub, dom, max_iters=2)[0] \
            .block_until_ready()
        t0 = time.time()
        sweeps = int(np.asarray(
            F.fixpoint_batch(cmn, lb, ub, dom, max_iters=8)[3]).sum())
        wall = max(time.time() - t0, 1e-9)
        props_per_sec = cmn.total_props * sweeps / wall

        # ---- every backend proves the same optimum (hard gate) --------
        native = {}
        for be in available_backends():
            cfg = solver.SolveConfig.preset(
                "prove", backend=be, n_lanes=8, eps_target=16,
                timeout_s=timeout_s)
            res = solver.Solver(cfg).solve(cmn)
            checked = zoo.ground_check(mod, inst, h, res)
            native[be] = dict(status=res.status, objective=res.objective,
                              wall_s=res.wall_s, ground_check=checked)
            rows.append(f"compact_table,{name},{be},{res.status},"
                        f"{res.objective},{res.wall_s:.3f},{checked}")
            if res.status != solver.OPTIMAL or checked is not True:
                raise SystemExit(
                    f"compact_table: {name} on {be} not proven+checked: "
                    f"{res.status} gc={checked}")
        if len({(r['status'], r['objective'])
                for r in native.values()}) != 1:
            raise SystemExit(
                f"compact_table: backend status/objective mismatch on "
                f"{name}: {native}")

        # ---- native CT vs the reified-disjunction oracle --------------
        cfg = solver.SolveConfig.preset(
            "prove", n_lanes=8, eps_target=16, timeout_s=timeout_s)
        rd = solver.Solver(cfg).solve(cmd)
        ref = native["gather"]
        if (rd.status, rd.objective) != (ref["status"], ref["objective"]):
            raise SystemExit(
                f"compact_table: native vs decomposed mismatch on {name}: "
                f"native={ref['status']}/{ref['objective']} "
                f"decomposed={rd.status}/{rd.objective}")
        speedup = rd.wall_s / max(ref["wall_s"], 1e-9)
        rows.append(
            f"compact_table,{name},tables={cmn.n_table},"
            f"arity={cmn.ct_arity},currtable_words={cmn.ct_words},"
            f"bitset_words={cmn.n_words},props/s={props_per_sec:.0f},"
            f"native_speedup={speedup:.1f}x")
        records.append(dict(
            model=name, instance=inst.name,
            n_table=cmn.n_table, ct_arity=cmn.ct_arity,
            currtable_words=cmn.ct_words, bitset_words=cmn.n_words,
            props_native=cmn.total_props, props_decomposed=cmd.total_props,
            root_fixpoint_sweeps=sweeps, props_per_sec=props_per_sec,
            native=native,
            decomposed=dict(status=rd.status, objective=rd.objective,
                            wall_s=rd.wall_s),
            native_speedup=speedup))
    return records


def merge_json(path: str, section: str, records) -> None:
    """Merge `records` into `path` under `section`, preserving whatever
    the propagation smoke already wrote there."""
    doc = {}
    if os.path.exists(path):
        with open(path) as fh:
            doc = json.load(fh)
    doc[section] = records
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)


def write_solver_json(path: str, records) -> None:
    merge_json(path, "solver", records)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="bigger instances (minutes-scale, paper-like)")
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--subs", type=int, default=128)
    ap.add_argument("--backend", default="gather",
                    choices=available_backends(),
                    help="propagation backend for the batched engine")
    ap.add_argument("--zoo", action="store_true",
                    help="also run the model-zoo section (all 5 models)")
    ap.add_argument("--zoo-size", choices=("small", "bench"), default=None,
                    help="zoo instance tier (default: bench for --zoo, "
                         "small for --zoo-smoke)")
    ap.add_argument("--zoo-smoke", action="store_true",
                    help="ONLY the zoo on small instances (the make-check "
                         "tier); implies --zoo, skips the RCPSP tables")
    ap.add_argument("--throughput", action="store_true",
                    help="ONLY the session-API serving benchmark: cold/warm "
                         "compile amortization + solve_many instances/s on "
                         "4 knapsack instances, all backends (the make-"
                         "check api tier)")
    ap.add_argument("--superstep-bench", action="store_true",
                    help="ONLY the superstep-orchestration benchmark "
                         "(DESIGN.md §13): ms_per_superstep / "
                         "supersteps_per_sec / dispatches_per_solve per "
                         "backend at chunk=1 host granularity; records go "
                         "to the bench JSON `superstep` section")
    ap.add_argument("--supersteps-per-launch", type=int, default=16,
                    help="K for pallas_resident in --superstep-bench")
    ap.add_argument("--serve-bench", action="store_true",
                    help="ONLY the solver-as-a-service benchmark "
                         "(DESIGN.md §15): fixed-seed open-loop Poisson "
                         "load through the continuous-batching "
                         "scheduler; hard-fails on parity vs sequential "
                         "Solver.solve, on no-batching, and on per-"
                         "bucket recompiles; records go to the bench "
                         "JSON `serving` section")
    ap.add_argument("--serve-requests", type=int, default=50,
                    help="trace length for --serve-bench")
    ap.add_argument("--serve-rate", type=float, default=100.0,
                    help="arrival rate (req/s) for --serve-bench")
    ap.add_argument("--dist-bench", action="store_true",
                    help="ONLY the distributed-EPS benchmark (DESIGN.md "
                         "§14): warm solve wall per mesh size with "
                         "speedup vs mesh=1, steal events and bound-all-"
                         "reduce counts; records go to the bench JSON "
                         "`distributed` section (run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--scale-smoke", action="store_true",
                    help="ONLY the scale-tier benchmark (DESIGN.md §16): "
                         "dense-vs-sparse peak bank-tile bytes for "
                         "nqueens N∈{32..512} (hard-fails unless sparse "
                         "< dense at N ≥ 128), forced dense/sparse "
                         "objective-parity solves (hard-fails on "
                         "mismatch), and large-tier props/s + nodes/s "
                         "probes; records go to the bench JSON `scale` "
                         "section")
    ap.add_argument("--ct-smoke", action="store_true",
                    help="ONLY the Compact-Table benchmark (DESIGN.md "
                         "§17): bitset-carried root-fixpoint props/s + "
                         "currtable/bitset word statics on the "
                         "extensional zoo models, every backend proven "
                         "and ground-checked (hard-fails on any "
                         "status/objective mismatch), native vs "
                         "decompose=True oracle speedup; records go to "
                         "the bench JSON `compact_table` section")
    ap.add_argument("--eps-target", type=int, default=64,
                    help="EPS pool size for the zoo runs (DESIGN.md §9)")
    ap.add_argument("--json", default=None,
                    help="merge the zoo records into this JSON file as its "
                         "`solver` section (and `--throughput` records as "
                         "its `api` section), e.g. "
                         "BENCH_propagation_smoke.json")
    args = ap.parse_args(argv)
    if args.json and not (args.zoo or args.zoo_smoke or args.throughput
                          or args.superstep_bench or args.dist_bench
                          or args.serve_bench or args.scale_smoke
                          or args.ct_smoke):
        ap.error("--json records the zoo/api/superstep/distributed/"
                 "serving/scale/compact_table sections; pass --zoo, "
                 "--zoo-smoke, --throughput, --superstep-bench, "
                 "--dist-bench, --serve-bench, --scale-smoke or "
                 "--ct-smoke")
    timeout = args.timeout or (300 if args.full else 30)

    rows = []
    if args.ct_smoke:
        rows.append("compact_table,model,backend,status,objective,time_s,"
                    "ground_check (+ per-model statics/speedup line)")
        records = run_ct_smoke(rows, timeout_s=timeout if args.timeout
                               else 120.0)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "compact_table", records)
        return rows
    if args.scale_smoke:
        rows.append("scale,kind,model,per-kind columns "
                    "(bank_bytes|parity|large)")
        records = run_scale_smoke(rows, timeout_s=timeout if args.timeout
                                  else 120.0)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "scale", records)
        return rows
    if args.serve_bench:
        rows.append("serving,backend,requests,rate,buckets,ttfi_p50,"
                    "ttfi_p99,lat_p50,lat_p99,occ_max,live_max,inst_s,"
                    "parity")
        records = run_serve_bench(rows, n_requests=args.serve_requests,
                                  rate_rps=args.serve_rate,
                                  backend=args.backend)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "serving", records)
        return rows
    if args.dist_bench:
        rows.append("distributed,mesh,status,objective,warm,speedup,"
                    "steals,allreduce,parity")
        records = run_dist_bench(rows, timeout_s=timeout)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "distributed", records)
        return rows
    if args.superstep_bench:
        rows.append("superstep,backend,K,steps,dispatches,ms_per_step,"
                    "steps_per_sec,status")
        records = run_superstep_bench(
            rows, backends=available_backends(), timeout_s=timeout,
            supersteps_per_launch=args.supersteps_per_launch)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "superstep", records)
        return rows
    if args.throughput:
        rows.append("api,backend,cold,warm,speedup,batched,sequential,"
                    "parity")
        records = run_throughput(lanes=8, eps_target=16, rows=rows,
                                 backends=available_backends(),
                                 timeout_s=timeout)
        print("\n".join(rows))
        if args.json:
            merge_json(args.json, "api", records)
        return rows
    if not args.zoo_smoke:
        rows.append(
            "suite,solver,instances,feasible,optimal,nodes_per_sec,time_s")
        for kind in ("patterson-like", "j30-like"):
            run_suite(kind, suite(kind, args.full), timeout, args.lanes,
                      args.subs, rows, backend=args.backend)
    records = None
    if args.zoo or args.zoo_smoke:
        rows.append("zoo,model,backend,status,objective,nodes_per_sec,"
                    "time_s,ground_check,props_native/decomposed")
        smoke = (args.zoo_size == "small" if args.zoo_size
                 else args.zoo_smoke)
        # the smoke tier sweeps EVERY backend (objective-parity gate);
        # full --zoo runs stay single-backend (they're minutes-scale)
        be = available_backends() if args.zoo_smoke else args.backend
        records = run_zoo(timeout, args.lanes, args.eps_target, rows,
                          backend=be, smoke=smoke)
    print("\n".join(rows))
    if args.json and records is not None:
        write_solver_json(args.json, records)
    return rows


if __name__ == "__main__":
    main()
