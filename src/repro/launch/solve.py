"""Solver launcher + solver-on-production-mesh dry-run.

  PYTHONPATH=src python -m repro.launch.solve --n 10                # solve
  PYTHONPATH=src python -m repro.launch.solve --preset fast --n 12
  PYTHONPATH=src python -m repro.launch.solve --dryrun [--multi-pod]

``--preset {prove,first,fast}`` picks the named `SolveConfig` recipe
(DESIGN.md §11): `prove` runs B&B to a proof (default), `first` stops at
the first solution, `fast` caps fixpoint sweeps (§Perf P0).  The solve
path goes through the session API (`repro.solver`), streaming anytime
incumbents as they improve.

The dry-run lowers+compiles one solver chunk (`api._run_chunk` under
shard_map) for the full production mesh — the paper's own system passing
the same bar as the LM cells: lanes sharded over all 256/512 devices,
bound sharing via pmin visible as `all-reduce` in the HLO.
"""

import os
if "XLA_FLAGS" not in os.environ and "--dryrun" in __import__("sys").argv:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import time              # noqa: E402
import warnings          # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

# CLI name -> SolveConfig preset name
_PRESETS = {"prove": "prove", "first": "first_solution", "fast": "fast"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10, help="RCPSP tasks")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resources", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--subs", type=int, default=128)
    ap.add_argument("--eps-target", type=int, default=None,
                    help="EPS pool size (DESIGN.md §9): decompose the root "
                         "into ~this many subproblems; 1 = single-root "
                         "search; default --subs")
    ap.add_argument("--timeout", type=float, default=120)
    ap.add_argument("--preset", choices=sorted(_PRESETS), default="prove",
                    help="SolveConfig preset (DESIGN.md §11): prove = full "
                         "B&B proof, first = stop at first solution, fast "
                         "= capped fixpoint sweeps (§Perf P0)")
    ap.add_argument("--fast", action="store_true",
                    help="DEPRECATED: use --preset fast")
    from repro.core.backend import available_backends
    ap.add_argument("--backend", default="gather",
                    choices=available_backends(),
                    help="propagation backend for the superstep fixpoint "
                         "(core/backend.py; pallas = VMEM kernel, "
                         "interpret-mode on CPU)")
    ap.add_argument("--lane-tile", type=int, default=None,
                    help="pallas backends: lanes per VMEM grid cell "
                         "(default 8 for pallas; 0 = whole batch in one "
                         "cell for pallas_resident, its bit-parity mode)")
    ap.add_argument("--supersteps-per-launch", type=int, default=None,
                    help="pallas_resident: K supersteps fused per "
                         "megakernel launch (DESIGN.md §13; default 16)")
    ap.add_argument("--branch-value", default=None,
                    choices=("min", "split", "middle_out"),
                    help="value branching (DESIGN.md §17): min = x≤lb, "
                         "split = bisect at the midpoint, middle_out = "
                         "x=m | x≠m on the bitset-domain value nearest "
                         "the midpoint (needs no tables — the bitset "
                         "store is carried automatically)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="distributed EPS (core/dist_solve.py, DESIGN.md "
                         "§14): shard the lane pool over N devices with "
                         "per-chunk bound sharing, work stealing and "
                         "elastic device-loss recovery; on CPU fake "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--file", default=None)
    args = ap.parse_args()

    from repro import solver
    from repro.core.models import rcpsp
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.fast:
        warnings.warn("--fast is deprecated; use --preset fast",
                      DeprecationWarning)
        args.preset = "fast"

    if args.file:
        inst = (rcpsp.parse_psplib_sm(args.file) if args.file.endswith(".sm")
                else rcpsp.parse_patterson(args.file))
    else:
        inst = rcpsp.generate(args.n, n_resources=args.resources,
                              seed=args.seed)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    if args.supersteps_per_launch and args.backend != "pallas_resident":
        ap.error("--supersteps-per-launch needs --backend pallas_resident")
    bo = {}
    if args.lane_tile is not None and args.backend.startswith("pallas"):
        bo["lane_tile"] = args.lane_tile
    extra = {}
    if args.branch_value is not None:
        extra["val_strategy"] = args.branch_value
    cfg = solver.SolveConfig.preset(
        _PRESETS[args.preset],
        n_lanes=args.lanes,
        eps_target=(args.eps_target if args.eps_target is not None
                    else args.subs),
        timeout_s=args.timeout, backend=args.backend,
        backend_opts=tuple(sorted(bo.items())),
        supersteps_per_launch=args.supersteps_per_launch,
        mesh_shards=args.mesh, **extra)

    if args.dryrun:
        from repro.launch.mesh import make_production_mesh
        from repro.core.api import _run_chunk, _init_carry
        from repro.core import search as S
        from jax.sharding import PartitionSpec as P
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        axes = tuple(mesh.axis_names)
        n_dev = int(np.prod(list(mesh.shape.values())))
        lanes = 8                                  # per device
        V = cm.n_vars
        Spool = n_dev * 16
        opts = cfg.search_options()
        carry = _init_carry(cm, lanes * n_dev, opts, n_heads=n_dev)
        spec = P(axes)
        state_spec = jax.tree.map(lambda _: spec, carry[0])
        carry_spec = (state_spec, P(), P(), P(), spec)
        dev_fn = lambda sl, su, c: _run_chunk(   # noqa: E731
            opts, False, 64, axes, cm, sl, su, c)
        f = jax.jit(jax.shard_map(dev_fn, mesh=mesh,
                                  in_specs=(spec, spec, carry_spec),
                                  out_specs=carry_spec, check_vma=False))
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = f.lower(
                jax.ShapeDtypeStruct((Spool, V), cm.jdtype,
                                     sharding=jax.NamedSharding(mesh, spec)),
                jax.ShapeDtypeStruct((Spool, V), cm.jdtype,
                                     sharding=jax.NamedSharding(mesh, spec)),
                jax.tree.map(
                    lambda x, s: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=jax.NamedSharding(mesh, s)),
                    carry, carry_spec))
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        txt = compiled.as_text()
        mesh_tag = "2x16x16" if args.multi_pod else "16x16"
        print(f"SOLVER dry-run OK on {mesh_tag} ({n_dev} devices): "
              f"compile={time.time()-t0:.1f}s "
              f"args={ma.argument_size_in_bytes/1e6:.1f}MB/dev "
              f"temp={ma.temp_size_in_bytes/1e6:.1f}MB/dev "
              f"all-reduce ops={txt.count(' all-reduce')} "
              f"(B&B bound pmin + done/any-sol flags)")
        return

    t0 = time.time()
    sess = solver.Solver(cfg)
    res, trace = None, None
    if args.mesh is not None:
        # dist path driven directly so the solve's DistTrace (steal /
        # remesh / bound-sync counters) is printable at the end
        from repro.core import dist_solve
        from repro.core.api import _canonical
        trace = dist_solve.DistTrace()
        events = dist_solve.solve_iter_dist(sess, _canonical(cm), cfg,
                                            trace=trace)
    else:
        events = sess.solve_iter(cm)
    for ev in events:
        if ev.final:
            res = ev.result
        elif ev.best_objective is not None and ev.incumbent is not None:
            # a fresh incumbent this chunk — the anytime answer
            print(f"  [{ev.wall_s:6.1f}s] superstep={ev.superstep:6d} "
                  f"incumbent={ev.best_objective} nodes={ev.n_nodes}")
    print(f"{inst.name}: {res.status} objective={res.objective} "
          f"nodes={res.n_nodes} ({res.nodes_per_sec:.0f}/s) "
          f"supersteps={res.n_supersteps} improvements="
          f"{[i.objective for i in res.improvements]} "
          f"wall={time.time()-t0:.1f}s complete={res.complete}")
    if trace is not None:
        print(f"  distributed: shards={args.mesh} chunks={trace.n_chunks} "
              f"bound_syncs={trace.n_bound_syncs} steals={trace.n_steals} "
              f"remeshes={len(trace.remesh_events)}")


if __name__ == "__main__":
    main()
