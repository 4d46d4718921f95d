"""Training launcher: ``--arch`` × ``--shape`` (or smoke dims), mesh-aware,
checkpoint/resume, deterministic data, failure-injection hooks.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
      --steps 200 --ckpt-dir /tmp/ckpt [--resume] [--devices 4]
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt.checkpoint import Checkpointer
from repro.data.pipeline import SyntheticLM
from repro.distributed import sharding as SH
from repro.ft.fault_tolerance import TrainSupervisor
from repro.launch.mesh import make_host_mesh
from repro.nn import model as MD
from repro.nn.layers import init_params
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import train_step


def build(arch: str, smoke: bool, seq: int, global_batch: int,
          opt_cfg: OptConfig, n_devices: int = 1, chunks=(256, 256),
          seed: int = 0):
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    mesh = make_host_mesh(n_devices) if n_devices > 1 else None
    data = SyntheticLM(cfg, seq, global_batch, seed=seed)
    key = jax.random.PRNGKey(seed)
    specs = MD.param_specs(cfg)
    params = init_params(specs, key)
    opt = init_opt_state(params)
    if mesh is not None:
        rules = SH.rules_for("train")
        p_sh = SH.shardings_for_specs(specs, rules, mesh)
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt = {"mu": jax.tree.map(jax.device_put, opt["mu"], p_sh),
               "nu": jax.tree.map(jax.device_put, opt["nu"], p_sh),
               "step": opt["step"]}
    step_jit = jax.jit(partial(train_step, cfg=cfg, opt_cfg=opt_cfg,
                               remat=True, chunks=chunks))

    def one_step(params, opt_state, step):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        ctx = jax.set_mesh(mesh) if mesh is not None else _null()
        with ctx:
            return step_jit(params, opt_state, batch)

    return cfg, params, opt, one_step


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "const"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    # minicpm trains with WSD per its paper; make that the arch default
    sched = args.schedule
    if args.arch == "minicpm-2b" and sched == "cosine":
        sched = "wsd"
    ocfg = OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                     total_steps=args.steps, schedule=sched)
    cfg, params, opt, one_step = build(
        args.arch, args.smoke, args.seq, args.global_batch, ocfg,
        n_devices=args.devices, seed=args.seed)
    print(f"arch={cfg.name} params="
          f"{sum(int(np.prod(v.shape)) for v in params.values()):,}")

    t0 = time.time()
    log = {"last": t0}

    def step_fn(params, opt_state, step):
        params, opt_state, m = one_step(params, opt_state, step)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(m["loss"])
            now = time.time()
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.3f} "
                  f"({now - log['last']:.1f}s)")
            log["last"] = now
        return params, opt_state, m

    if args.ckpt_dir:
        sup = TrainSupervisor(Checkpointer(args.ckpt_dir),
                              ckpt_every=args.ckpt_every)
        params, opt, hist = sup.run(params, opt, step_fn, args.steps)
        losses = [h["loss"] for h in hist]
    else:
        losses = []
        for s in range(args.steps):
            params, opt, m = step_fn(params, opt, s)
            losses.append(float(m["loss"]))
    if losses:
        print(f"done in {time.time() - t0:.1f}s  "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
