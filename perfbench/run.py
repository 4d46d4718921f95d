#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 perfbench/run.py --workload j30-prove --seed 7 --seconds 45 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; both are data files found by name
(`perfbench.manifest`).  The run:

1. checks that JAX sees a TPU with as many chips as the cell asks for,
   and otherwise exits non-zero without a result;
2. set-up: builds the configuration's fixed replay set of instances,
   lowers each through the program's own ``build_model``, draws the order
   of the window from ``--seed``, and compiles every
   program the window will run (the decomposition fixpoint and the
   chunk runner at the pool bucket), with JAX's persistent compilation
   cache on (`repro.launch.compile_cache`);
3. the window: one closed-loop caller solves the replay set back to back
   through `repro.solver.Solver.solve`, in an order drawn from the seed,
   cycling, until ``--seconds`` have passed and the cycle in flight is
   complete, so every window does whole cycles of the same work;
4. checks every answer against the plain reference (`perfbench/reference`)
   and prints each number compared beside its limit;
5. prints one JSON line: the end-to-end metrics with ``--trace 0``; with
   ``--trace 1`` the window is traced by the JAX profiler and the line
   carries the per-layer metrics, the device's busy and window seconds
   and a breakdown of device time and idle gaps.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import manifest  # noqa: E402
from perfbench.trace import SOLVE_SPAN, WINDOW_MARK  # noqa: E402

SRC = os.path.join(ROOT, "src")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Answer:
    slot: int
    t_call: float
    t_done: float
    result: Any = None
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What one traced run hands to the per-layer readers."""
    answers: List[Answer]
    trace: Any = None          # perfbench.trace.Trace of the traced slice
    spans: Any = None          # perfbench.spans.Spans of the window


def device_info(n_chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU; this benchmark measures only on a TPU")
    if len(devs) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chip(s), JAX sees "
                     f"{len(devs)}")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=n_chips)


def memory_peak(n_chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class CompileCounter:
    """Counts compilations and persistent-cache loads until closed."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.loads = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, *_a, **_k) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, *_a, **_k) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


def build_replay(config, traffic, seed: int, control: bool):
    """The replay set, lowered and compiled by the program, and the
    order the window cycles through it.  The instances are the
    configuration's fixed set (slot i drawn from the stream
    ``[instance_seed, i]``, like a published benchmark set); ``seed``
    draws the order, so every run does the same work.  The control
    hands the program every model with each Cumulative capacity one
    above the instance's (`relax_capacity`)."""
    import numpy as np
    gen, _ = manifest.family(config)
    grid = config.get("grid") or [{}]
    insts, models = [], []
    for i in range(int(traffic["replay"])):
        rng = np.random.default_rng([int(config["instance_seed"]), i])
        inst = gen.generate(config["generator"], grid[i % len(grid)], rng,
                            name=f"{config['name']}-{i}")
        insts.append(inst)
        cm, starts = gen.build(inst)
        models.append((relax_capacity(cm) if control else cm, starts))
    order = np.random.default_rng(seed).permutation(len(insts))
    return insts, models, [int(i) for i in order]


def relax_capacity(cm):
    """The control: the compiled model with every Cumulative capacity
    (resource or machine) one higher, which breaks the capacity
    guarantee every configuration states.  The capacities are arguments
    of the compiled programs, so nothing compiles anew."""
    return dataclasses.replace(cm, cu_cap=cm.cu_cap + 1)


def solve_config(traffic, n_chips: int):
    from repro.solver import SolveConfig
    solve = traffic["solve"]
    over = dict(solve.get("overrides", {}))
    if n_chips > 1:
        over.setdefault("mesh_shards", n_chips)
    return SolveConfig.preset(solve["preset"], **over)


def warm_up(solver, models) -> None:
    """Compile what the window runs, for every distinct program shape:
    the decomposition's fixpoint and the chunk runner at the pool bucket
    the EPS target gives.  The runner is driven on an all-failed pool,
    which ends in a few supersteps, so no measured work runs here."""
    import numpy as np
    from repro.core import eps
    from repro.core.api import shape_signature
    from repro.core.fixpoint import fixpoint

    target = solver.config.resolved_eps_target()
    bucket = 1 << (target - 1).bit_length()
    seen = set()
    for cm, _ in models:
        sig = shape_signature(cm)
        if sig in seen:
            continue
        seen.add(sig)
        cmc = dataclasses.replace(cm, name="")
        fixpoint(cmc, cmc.lb0, cmc.ub0)[0].block_until_ready()
        fixpoint(cmc, np.asarray(cmc.lb0), np.asarray(cmc.ub0))[0] \
            .block_until_ready()
        pool = eps.failed_pool(np.asarray(cm.lb0), np.asarray(cm.ub0), bucket)
        solver.solve(cm, subs=pool)


def window(solver, models, order, seconds: float,
           spans=None) -> List[Answer]:
    """The closed loop: one caller, the replay set in ``order``, cycling,
    until ``seconds`` have passed and the cycle in flight has completed:
    every seed's window then solves each instance equally often, and only
    the order differs.  A traced window instead runs on until every slice
    has been traced and one solve has completed after the last
    (`perfbench.spans`), so its per-layer numbers exist."""
    import jax
    answers: List[Answer] = []
    t0 = time.time()
    if spans is not None:
        spans.start()
    k = 0
    while True:
        slot = order[k % len(order)]
        k += 1
        with jax.profiler.TraceAnnotation(SOLVE_SPAN):
            if spans is not None:
                spans.begin_solve()
            a = Answer(slot=slot, t_call=time.time(), t_done=0.0)
            try:
                a.result = solver.solve(models[slot][0])
            except Exception as e:          # an answer that never comes
                a.error = f"{type(e).__name__}: {e}"
            a.t_done = time.time()
            if spans is not None:
                spans.end_solve()
        answers.append(a)
        if a.t_done - t0 < seconds:
            continue
        if spans is None and k % len(order) == 0:
            return answers
        if spans is not None and spans.done() \
                and not spans.solves[-1].disturbed:
            return answers


def judge(config, traffic, insts, models, answers):
    """Every number of the window's answers: counts of answers that
    raised, came back without a schedule, broke a constraint, or
    misreported their makespan; with proof traffic, of answers that did
    not prove, or proved a makespan other than the reference optimum;
    with anytime traffic, the largest makespan over the reference's
    lower bound, which no limit holds (PERF.md says why).  Returns those
    numbers and the number of answers with any fault."""
    _, ref = manifest.family(config)
    prove = traffic["mode"] == "prove"
    n: Dict[str, Any] = dict(errors=0, unsolved=0, infeasible=0,
                             objective_mismatch=0)
    if prove:
        n.update(not_optimal=0, wrong_optimum=0)
    else:
        n["makespan_over_lb"] = 0.0
    optimum: Dict[int, int] = {}
    bad = 0
    for a in answers:
        faults = []
        res = a.result
        if a.error is not None:
            faults.append("errors")
        elif res.solution is None:
            faults.append("unsolved")
        else:
            starts = [int(res.solution[v]) for v in models[a.slot][1]]
            ok, makespan = ref.check(insts[a.slot], starts)
            if not ok:
                faults.append("infeasible")
            elif makespan != res.objective:
                faults.append("objective_mismatch")
            if prove:
                if res.status != "OPTIMAL":
                    faults.append("not_optimal")
                if a.slot not in optimum:
                    optimum[a.slot] = ref.optimum(insts[a.slot])[0]
                if res.objective != optimum[a.slot]:
                    faults.append("wrong_optimum")
            else:
                ratio = res.objective / ref.lower_bound(insts[a.slot])
                n["makespan_over_lb"] = max(n["makespan_over_lb"], ratio)
        for f in faults:
            n[f] += 1
        bad += bool(faults)
    return n, bad


def run_cell(bench: "manifest.Benchmark", cell_name: str, seed: int,
             seconds: float, trace: bool, *, control: bool = False,
             require_tpu: bool = True) -> Dict[str, Any]:
    """One run of one cell; returns the result object (not printed)."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    n_chips = int(cell["chips"])
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise FileNotFoundError(f"no repro package under {SRC}: run from a "
                                f"checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    device = device_info(n_chips, require_tpu)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.solver import Solver
    log(f"compile cache at {enable_compile_cache()}")

    insts, models, order = build_replay(config, traffic, seed, control)
    for cm, _ in models:
        if cm.dtype != config["dtype"]:
            raise RuntimeError(f"the program chose store {cm.dtype}, the "
                               f"configuration states {config['dtype']}")
    solver = Solver(solve_config(traffic, n_chips))
    warm_up(solver, models)
    setup_s = time.time() - T_START
    log(f"set-up {setup_s:.3f}s: replay set of {len(models)}, order {order}, "
        f"{solver.session_stats()['n_compiles']} runner compile(s)")

    spans = None
    if trace:
        import jax
        from perfbench.spans import Spans
        from perfbench.trace import PROFILE_OPTIONS
        tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        for k, v in PROFILE_OPTIONS.items():
            setattr(opts, k, v)

        def start_trace(k: int) -> None:
            jax.profiler.start_trace(os.path.join(tdir, str(k)),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation(WINDOW_MARK):
                pass                         # the slice's opening mark

        spans = Spans(traffic["trace_slices"], start_trace,
                      jax.profiler.stop_trace)
    counter = CompileCounter()
    try:
        answers = window(solver, models, order, seconds, spans)
    finally:
        counter.close()
        if spans is not None:
            spans.close()
    window_s = answers[-1].t_done - answers[0].t_call
    run = Run(answers=answers, spans=spans)
    if trace:
        from perfbench import trace as tr
        try:
            run.trace = tr.Slices(
                tr.Trace.from_dir(os.path.join(tdir, str(k)), n_chips)
                for k in range(len(spans.slices)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    device["memory_peak_bytes"] = memory_peak(n_chips)
    log(f"window {window_s:.3f}s: {len(answers)} solve(s), "
        f"{counter.compiles} compile(s) and {counter.loads} cache load(s) "
        f"inside it; host load {os.getloadavg()} on {os.cpu_count()} cores")
    del solver

    checks, failed = judge(config, traffic, insts, models, answers)
    limits = traffic["limits"]
    missing = set(limits) - set(checks)
    if missing:
        raise manifest.ManifestError(f"limits for numbers no check "
                                     f"computes: {sorted(missing)}")
    for k in sorted(set(checks) - set(limits)):
        log(f"reading {k} = {checks[k]} (no limit: not compared)")
    correct = bool(answers) and all(checks[k] <= limits[k] for k in limits)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in bench.per_layer(cell_name):
            value = bench.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
            elif cell_name in m.get("workloads", ()):
                # the metric names this cell as one where it reads
                # something: finding nothing means the program no longer
                # runs the path its reader looks at
                raise RuntimeError(f"per-layer metric {m['name']!r} read "
                                   f"nothing in cell {cell_name}")
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
    else:
        for m in bench.end_to_end(cell_name):
            if m["name"] == "setup_s":
                metrics["setup_s"] = dict(value=setup_s, unit="s")
            elif m["name"] == traffic["metric"]:
                metrics[m["name"]] = dict(value=window_s / len(answers),
                                          unit=m["unit"])
            else:
                raise manifest.ManifestError(
                    f"cell {cell_name} lists end-to-end metric "
                    f"{m['name']!r}, which its traffic does not measure")
    out: Dict[str, Any] = dict(correct=correct, attempted=len(answers),
                               failed=failed, metrics=metrics, device=device)
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: dict(value=checks[k], limit=limits[k])
                     for k in limits}
    for a in answers:
        if a.error is not None:
            log(f"solve of slot {a.slot} raised {a.error}")
        else:
            r = a.result
            log(f"solve of slot {a.slot}: {a.t_done - a.t_call:.3f}s, "
                f"{r.n_supersteps} supersteps, {r.n_sweeps} sweeps, "
                f"incumbent at {[round(i.wall_s, 3) for i in r.improvements]}s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="give the program every model with each "
                         "Cumulative capacity one higher (the correctness "
                         "control; never part of a measured run)")
    args = ap.parse_args(argv)
    try:
        out = run_cell(manifest.Benchmark(), args.workload, args.seed,
                       args.seconds, bool(args.trace), control=args.control)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    except Exception:
        log("no result: the run failed")
        traceback.print_exc()
        return 1
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
