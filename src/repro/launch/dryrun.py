"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512
placeholder host devices stand in for 2 pods × 256 chips; every cell must
lower, SPMD-partition, and compile, and the compiled artifact yields the
memory/cost/collective numbers for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \\
      --shape train_4k [--multi-pod] [--all] [--out report.json]
"""

# MUST be the very first lines — before any other import, including repro
# (jax locks the device count on first backend initialization).
import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
from functools import partial  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro import configs                        # noqa: E402
from repro.configs.base import skip_reason       # noqa: E402
from repro.data.pipeline import input_shapes     # noqa: E402
from repro.distributed import sharding as SH     # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.nn import model as MD                 # noqa: E402
from repro.nn.layers import abstract_params      # noqa: E402
from repro.train.optimizer import OptConfig, init_opt_state  # noqa: E402
from repro.train.train_step import train_step    # noqa: E402
from repro.train.serve_step import decode_step, prefill_step  # noqa: E402

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# HLO shapes like bf16[2,16,128]{2,1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in (post-SPMD) HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)", ls)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in _COLLECTIVES:
            # match the op name right after the output shape, e.g.
            # "bf16[..] all-reduce(...)" — avoids fusion-comment hits
            if re.search(r"\)?\s" + c + r"(\.\d+)?\(", rhs) or \
               re.search(r"\}\s*" + c + r"(\.\d+)?\(", rhs) or \
               re.search(r"\]\s*" + c + r"(\.\d+)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        shm = _SHAPE_RE.match(rhs) or _SHAPE_RE.search(rhs.split(op)[0])
        if not shm:
            continue
        dt, dims = shm.group(1), shm.group(2)
        if dt == "tuple" or dt not in _BYTES:
            continue
        n = int(np.prod([int(d) for d in dims.split(",") if d])) \
            if dims else 1
        out[op] += n * _BYTES[dt]
        out["count"] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def build_cell(arch: str, shape_name: str, mesh, chunks=(1024, 1024),
               cfg=None, microbatches: int = 1):
    """Returns (fn, example_args (abstract), out_shardings, donate).
    `cfg` overrides the registry config (roofline reduced-depth variants);
    `microbatches` enables grad-accumulation in the train cells."""
    cfg = cfg or configs.get(arch)
    shape = configs.get_shape(shape_name)
    mode = "train" if shape.kind == "train" else "serve"
    rules = SH.rules_for(mode)
    specs = MD.param_specs(cfg)
    p_shard = SH.shardings_for_specs(specs, rules, mesh)
    params = _abstract(abstract_params(
        specs, jnp.float32 if mode == "train" else jnp.bfloat16), p_shard)

    n_dev = int(np.prod(list(mesh.shape.values())))
    batch_shapes = input_shapes(cfg, shape)
    b_shard = SH.batch_sharding(batch_shapes, rules, mesh)
    batch = _abstract(batch_shapes, b_shard)

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(init_opt_state, params)
        o_shard = {"mu": p_shard, "nu": p_shard,
                   "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        opt = _abstract(opt_shapes, o_shard)
        opt_cfg = OptConfig()
        fn = partial(train_step, cfg=cfg, opt_cfg=opt_cfg, remat=True,
                     chunks=chunks, microbatches=microbatches)
        out_shardings = (p_shard, o_shard, None)
        return fn, (params, opt, batch), out_shardings, (0, 1)

    smax = shape.seq_len
    if shape.kind == "prefill":
        def fn(params, batch):
            return prefill_step(params, cfg, batch, smax, chunks=chunks)

        cache_shapes = jax.eval_shape(
            lambda p, b: prefill_step(p, cfg, b, smax, chunks=chunks)[1],
            params, batch)
        c_shard = SH.cache_shardings(cfg, cache_shapes, mesh)
        out_shardings = (None, c_shard)
        return fn, (params, batch), out_shardings, ()

    # decode: primed cache at length smax-1, one-token step
    B = shape.global_batch
    # closure (not args) so the dims stay static under eval_shape
    cache_shapes = jax.eval_shape(lambda: MD.init_cache(cfg, B, smax))
    c_shard = SH.cache_shardings(cfg, cache_shapes, mesh)
    caches = _abstract(cache_shapes, c_shard)
    tokens = jax.ShapeDtypeStruct(
        (B, 1), jnp.int32,
        sharding=SH.batch_sharding(
            {"t": jax.ShapeDtypeStruct((B, 1), jnp.int32)}, rules, mesh)["t"])
    # decode q=1: a single full-length KV chunk keeps the per-layer cache
    # all-gather to ONE op instead of one per 1024-chunk (§Perf P2b);
    # scores are [B,H,1,S] — small at decode
    kv_chunk = min(shape.seq_len, max(chunks))

    def fn(params, tokens, caches):
        return decode_step(params, cfg, tokens, caches,
                           chunks=(1, kv_chunk))

    out_shardings = (None, c_shard, None)
    return fn, (params, tokens, caches), out_shardings, (2,)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             chunks=(1024, 1024)) -> Dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    cfg = configs.get(arch)
    shape = configs.get_shape(shape_name)
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "SKIP", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    fn, args, out_shardings, donate = build_cell(arch, shape_name, mesh,
                                                 chunks)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, out_shardings=out_shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "status": "OK",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "per_device": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "flops": float(ca.get("flops", -1)),
            "bytes_accessed": float(ca.get("bytes accessed", -1)),
            "transcendentals": float(ca.get("transcendentals", -1)),
        },
        "collectives": coll,
        "hlo_ops": {c: txt.count(f" {c}") for c in _COLLECTIVES},
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) cell")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in configs.ARCH_IDS:
            for s in configs.ALL_SHAPES:
                cells.append((a, s.name))
    else:
        shapes = [args.shape] if args.shape else \
            [s.name for s in configs.ALL_SHAPES]
        cells = [(args.arch, s) for s in shapes]

    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    reports = []
    for arch, shp in cells:
        for mp in meshes:
            rec = run_cell(arch, shp, multi_pod=mp)
            reports.append(rec)
            tag = f"{arch} × {shp} × {'2x16x16' if mp else '16x16'}"
            if rec["status"] == "SKIP":
                print(f"SKIP {tag}: {rec['reason']}")
            else:
                pd = rec["per_device"]
                print(f"OK   {tag}: compile={rec['compile_s']}s "
                      f"args={pd['argument_bytes']/1e9:.2f}GB "
                      f"temp={pd['temp_bytes']/1e9:.2f}GB "
                      f"flops={pd['flops']:.3g} "
                      f"coll={rec['collectives']['total']/1e9:.3f}GB")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(reports, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
