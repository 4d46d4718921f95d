"""Roofline analysis (deliverable g).

Per (arch × shape × mesh): the three roofline terms derived from compiled
HLO on the production mesh —

    compute    = HLO_FLOPs_per_chip / 197e12  (bf16 peak, TPU v5e)
    memory     = HLO_bytes_per_chip / 819e9   (HBM bandwidth)
    collective = collective_bytes_per_chip / 50e9 (ICI per link)

Methodology note (verified empirically, see DESIGN.md §8): XLA's
`cost_analysis()` counts a while/scan body ONCE regardless of trip count,
so naive numbers undercount by ~n_layers.  This harness therefore lowers
two reduced-depth UNROLLED variants of every cell (`unroll_scans()`
replaces every scan — layer stacks, attention chunk loops, SSD chunk
recurrence — with an exact python unroll), and linearly extrapolates
per-unit cost to full depth:

    X_total = X(k_a) + (units_full − k_a) · (X(k_b) − X(k_a)) / (k_b − k_a)

Collective bytes come from the same unrolled HLO text (the scanned text
has the identical undercount).  The full-depth *scanned* compile remains
the memory/fits proof (launch/dryrun.py); the two artifacts are reported
side by side in EXPERIMENTS.md.

MODEL_FLOPS = 6·N·D (train) or 2·N·D (prefill/decode) with N = active
params, D = tokens — the "useful compute" yardstick; the ratio
MODEL_FLOPS/HLO_FLOPS exposes remat/attention/routing overheads.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12      # bf16 / chip, TPU v5e
HBM_BW = 819e9           # B/s / chip
ICI_BW = 50e9            # B/s / link


def reduced_cfg(arch: str, k: int):
    """Config with k repeating units (structure-preserving)."""
    from repro import configs
    from repro.configs.base import EncDecConfig
    cfg = configs.get(arch)
    if cfg.encdec is not None:
        return dataclasses.replace(
            cfg, n_layers=2 * k, encdec=EncDecConfig(k, k))
    if cfg.family == "hybrid":
        pat = len(cfg.rglru.pattern)
        return dataclasses.replace(cfg,
                                   n_layers=pat * k + cfg.n_layers % pat)
    if cfg.moe is not None and cfg.moe.first_dense:
        return dataclasses.replace(cfg, n_layers=k + cfg.moe.first_dense)
    return dataclasses.replace(cfg, n_layers=k)


def unit_counts(arch: str) -> Tuple[int, Tuple[int, int]]:
    """(units_full, (k_a, k_b)) for the extrapolation."""
    from repro import configs
    cfg = configs.get(arch)
    if cfg.encdec is not None:
        return cfg.encdec.enc_layers, (1, 2)
    if cfg.family == "hybrid":
        return cfg.n_layers // len(cfg.rglru.pattern), (1, 2)
    if cfg.moe is not None and cfg.moe.first_dense:
        return cfg.n_layers - cfg.moe.first_dense, (1, 3)
    return cfg.n_layers, (1, 3)


def _cost_lowering(arch: str, shape_name: str, k: int, mesh) -> Dict:
    """Compile a reduced-depth unrolled variant; return per-device costs."""
    import jax
    from repro.launch import dryrun as DR
    from repro import configs
    from repro.nn.scanctl import unroll_scans

    shape = configs.get_shape(shape_name)
    cfg = reduced_cfg(arch, k)
    # big chunks: fewer unrolled attention bodies, identical FLOPs
    ch = min(4096, shape.seq_len)
    if cfg.ssm is not None:
        pass  # ssd chunk scan unrolls exactly; keep production chunk size
    fn, args, outs, donate = DR.build_cell(arch, shape_name, mesh,
                                           chunks=(ch, ch), cfg=cfg)
    with unroll_scans():
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, out_shardings=outs,
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    coll = DR.collective_bytes(txt)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total"]),
        "coll_breakdown": {c: coll[c] for c in DR._COLLECTIVES},
    }


def model_flops_per_chip(arch: str, shape_name: str, n_chips: int) -> float:
    from repro import configs
    cfg = configs.get(arch)
    shape = configs.get_shape(shape_name)
    n_active = cfg.n_active_params()
    # exclude the embedding *lookup* table (no matmul), keep unembed
    embed_tables = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n_eff = n_active - embed_tables + cfg.vocab * cfg.d_model
    if shape.kind == "train":
        D = shape.global_batch * shape.seq_len
        per_tok = 6 * n_eff
    elif shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        per_tok = 2 * n_eff
    else:
        D = shape.global_batch
        per_tok = 2 * n_eff
    return per_tok * D / n_chips


def roofline_cell(arch: str, shape_name: str, multi_pod: bool = False
                  ) -> Optional[Dict]:
    import jax
    from repro import configs
    from repro.configs.base import skip_reason
    from repro.launch.mesh import make_production_mesh

    cfg = configs.get(arch)
    shape = configs.get_shape(shape_name)
    reason = skip_reason(cfg, shape)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                "status": "SKIP", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    full, (ka, kb) = unit_counts(arch)
    t0 = time.time()
    a = _cost_lowering(arch, shape_name, ka, mesh)
    b = _cost_lowering(arch, shape_name, kb, mesh)

    def extrap(key):
        # per-unit delta can be slightly negative when the base (embed/
        # unembed) collectives dominate and layout noise shifts between
        # the two lowerings — clamp: totals can't shrink with depth.
        per = max((b[key] - a[key]) / (kb - ka), 0.0)
        return max(a[key] + (full - ka) * per, a[key], b[key])

    flops = extrap("flops")
    byts = extrap("bytes")
    coll = extrap("coll_bytes")
    t_comp = flops / PEAK_FLOPS
    t_mem = byts / HBM_BW
    t_coll = coll / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops_per_chip(arch, shape_name, n_chips)
    step = max(terms.values())
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "status": "OK", "n_chips": n_chips,
        "per_chip_flops": flops, "per_chip_bytes": byts,
        "per_chip_coll_bytes": coll,
        "coll_breakdown_at_kb": b["coll_breakdown"],
        **{k: round(v, 6) for k, v in terms.items()},
        "bottleneck": bottleneck.replace("_s", ""),
        "model_flops_per_chip": mf,
        "useful_ratio": round(mf / flops, 4) if flops else None,
        "roofline_frac": round((mf / PEAK_FLOPS) / step, 4) if step else None,
        "analysis_s": round(time.time() - t0, 1),
    }


def solver_roofline(lanes: int = 32, supersteps_per_launch: int = 16
                    ) -> Dict:
    """Superstep roofline for the constraint solver (DESIGN.md §13).

    Two terms bound a superstep of the resident search megakernel on the
    zoo smoke tier:

      memory   = per-launch VMEM traffic / HBM_BW — the state the kernel
                 streams in/out of HBM once per K supersteps (tables +
                 lane state + subproblem pool), amortized over K;
      dispatch = host launch overhead / K — measured per-dispatch cost
                 from the unfused path (`bench_solver --superstep-bench`
                 ms_per_superstep is dominated by it on CPU interpret).

    The unfused path pays BOTH terms every superstep (traffic and a
    dispatch per phase); the resident kernel pays traffic once per
    launch and keeps supersteps in VMEM, so its modeled
    ms_per_superstep(K) = t_kernel + overhead/K — the K-amortization
    curve this function tabulates.
    """
    from repro.core import models as zoo
    from repro.kernels.fixpoint_kernel import vmem_budget

    inst = zoo.small_instance("rcpsp", seed=0)
    cm = zoo.ZOO["rcpsp"].build_model(inst)[0].compile()
    K = supersteps_per_launch
    bud = vmem_budget(cm, lanes, resident=True, max_depth=512,
                      pool_size=64)
    traffic = bud["total"]                    # bytes in+out per launch
    t_mem_launch = traffic / HBM_BW
    # per-dispatch host overhead: order-10µs on a real accelerator
    # (launch latency); the measured CPU-interpret figure lives in
    # BENCH_propagation_smoke.json's `superstep` section
    overhead_s = 10e-6
    curve = {k: round(1e3 * (t_mem_launch / k + overhead_s / k
                             + t_mem_launch), 6)
             for k in (1, 4, 16, 64)}
    rec = {
        "model": inst.name, "lanes": lanes, "K": K,
        "vmem_bytes": {k: int(v) for k, v in bud.items()},
        "launch_traffic_bytes": int(traffic),
        "memory_s_per_launch": round(t_mem_launch, 9),
        "dispatch_overhead_s": overhead_s,
        "modeled_ms_per_superstep_by_K": curve,
        "bottleneck": ("dispatch" if overhead_s > t_mem_launch
                       else "memory"),
    }
    print(f"solver roofline: {inst.name} lanes={lanes} "
          f"VMEM={bud['total']/2**20:.2f}MiB "
          f"traffic={traffic/2**10:.1f}KiB/launch "
          f"bottleneck={rec['bottleneck']}")
    for k, ms in curve.items():
        print(f"  K={k:>3}: modeled {ms:.6f} ms/superstep")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--solver", action="store_true",
                    help="ONLY the solver superstep roofline (DESIGN.md "
                         "§13): VMEM footprint, per-launch HBM traffic "
                         "and the K-amortization curve for the resident "
                         "megakernel")
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--supersteps-per-launch", type=int, default=16)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.solver:
        rec = solver_roofline(
            lanes=args.lanes,
            supersteps_per_launch=args.supersteps_per_launch)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
            print("wrote", args.out)
        return [rec]

    from repro import configs
    cells = []
    if args.all:
        cells = [(a, s.name) for a in configs.ARCH_IDS
                 for s in configs.ALL_SHAPES]
    else:
        archs = [args.arch] if args.arch else list(configs.ARCH_IDS)
        shapes = [args.shape] if args.shape else \
            [s.name for s in configs.ALL_SHAPES]
        cells = [(a, s) for a in archs for s in shapes]

    out = []
    for arch, shp in cells:
        rec = roofline_cell(arch, shp, multi_pod=args.multi_pod)
        out.append(rec)
        if rec["status"] == "SKIP":
            print(f"SKIP {arch} × {shp}: {rec['reason']}")
        else:
            print(f"OK {arch} × {shp}: comp={rec['compute_s']*1e3:.2f}ms "
                  f"mem={rec['memory_s']*1e3:.2f}ms "
                  f"coll={rec['collective_s']*1e3:.2f}ms "
                  f"bottleneck={rec['bottleneck']} "
                  f"useful={rec['useful_ratio']} "
                  f"roofline={rec['roofline_frac']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", args.out)
    return out


if __name__ == "__main__":
    import os
    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
    main()
