"""Fault tolerance: atomic checkpoints, bit-exact kill-and-resume,
heartbeat failure detection, elastic re-mesh, lane rebalance."""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt.checkpoint import Checkpointer
from repro.data.pipeline import SyntheticLM
from repro.ft.fault_tolerance import (FailureInjector, Heartbeat,
                                      TrainSupervisor, rebalance_lanes,
                                      scaled_batch)
from repro.nn import model as MD
from repro.nn.layers import init_params
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import train_step


def _tiny_setup(tmp, ckpt_every=5):
    cfg = configs.get_smoke("qwen2.5-3b")
    data = SyntheticLM(cfg, seq_len=16, global_batch=4, seed=0)
    key = jax.random.PRNGKey(0)
    params = init_params(MD.param_specs(cfg), key)
    opt = init_opt_state(params)
    ocfg = OptConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20,
                     schedule="cosine")
    jstep = jax.jit(lambda p, o, b: train_step(p, o, b, cfg, ocfg,
                                               remat=False, chunks=(8, 8)))

    def step_fn(params, opt_state, step):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        return jstep(params, opt_state, batch)

    return params, opt, step_fn


def test_checkpoint_roundtrip():
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        params = {"a/b": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
        opt = init_opt_state(params)
        ck.save(7, params, opt)
        step, p2, o2 = ck.restore()
        assert step == 7
        np.testing.assert_array_equal(p2["a/b"], np.asarray(params["a/b"]))
        assert o2["step"] == 0


def test_checkpoint_retention_and_latest():
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp, keep=2)
        params = {"w": jnp.ones(3)}
        opt = init_opt_state(params)
        for s in (1, 2, 3, 4):
            ck.save(s, params, opt)
        assert ck.steps() == [3, 4]
        assert ck.latest_step() == 4


def test_kill_and_resume_bit_exact():
    """A run killed at step 10 and resumed must end bit-identical to an
    uninterrupted run (deterministic data + optimizer)."""
    with tempfile.TemporaryDirectory() as tmp:
        params, opt, step_fn = _tiny_setup(tmp)

        # uninterrupted reference
        p_ref, o_ref = params, opt
        for s in range(14):
            p_ref, o_ref, _ = step_fn(p_ref, o_ref, s)

        # interrupted: supervisor checkpoints every 5; run to 10, "crash"
        ck = Checkpointer(os.path.join(tmp, "ck"))
        sup = TrainSupervisor(ck, ckpt_every=5)
        sup.run(params, opt, step_fn, n_steps=10)
        # resume a fresh supervisor (simulates restarted process)
        ck2 = Checkpointer(os.path.join(tmp, "ck"))
        sup2 = TrainSupervisor(ck2, ckpt_every=5)
        p_res, o_res, _ = sup2.run(params, opt, step_fn, n_steps=14)

        for k in p_ref:
            np.testing.assert_array_equal(np.asarray(p_ref[k]),
                                          np.asarray(p_res[k]), err_msg=k)


def test_heartbeat_failure_detection():
    t = {"now": 0.0}
    hb = Heartbeat(["h0", "h1", "h2"], timeout_s=10, clock=lambda: t["now"])
    inj = FailureInjector({3: ["h1"]})
    for step in range(6):
        t["now"] += 5.0
        inj.advance(step, hb)
    assert hb.dead_hosts() == ["h1"]


def test_supervisor_invokes_failure_path():
    with tempfile.TemporaryDirectory() as tmp:
        params, opt, step_fn = _tiny_setup(tmp)
        t = {"now": 0.0}
        hb = Heartbeat(["h0", "h1"], timeout_s=1, clock=lambda: t["now"])

        def clockstep(p, o, s):
            t["now"] += 2.0
            return step_fn(p, o, s)

        sup = TrainSupervisor(Checkpointer(tmp), ckpt_every=100,
                              heartbeat=hb, injector=FailureInjector(
                                  {4: ["h1"]}))
        seen = {}

        def on_failure(dead, step, log):
            seen["dead"] = dead
            seen["step"] = step
            return None

        sup.run(params, opt, clockstep, n_steps=20, on_failure=on_failure)
        assert seen["dead"] == ["h1"] and seen["step"] >= 4


def test_scaled_batch():
    assert scaled_batch(256, 16) == 16
    assert scaled_batch(256, 15) == 17


def test_rebalance_lanes():
    # lane 0 exhausted, lane 1 has 4 subproblems queued
    next_sub = np.array([20, 1], dtype=np.int64)     # n_lanes=2, n_subs=9
    done = np.array([True, False])
    ns, dn, moved = rebalance_lanes(next_sub, done, n_subs=9, n_lanes=2)
    assert moved == 1
    assert not dn[0]                  # revived
    assert ns[0] in (7,)              # stole the donor's last queued sub


def test_elastic_remesh_subprocess():
    """Re-shard a params tree from an 8-device mesh to a 4-device mesh in
    a subprocess with fake devices; values must be preserved."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.ft.fault_tolerance import elastic_remesh
from repro.distributed import sharding as SH
from repro.nn import model as MD
from repro import configs

cfg = configs.get_smoke("llama3-8b")
specs = MD.param_specs(cfg)
rules = SH.rules_for("train")

def mk(n):
    return jax.make_mesh((n,), ("data",))

mesh8, mesh4 = mk(8), mk(4)
from repro.nn.layers import init_params
params = init_params(specs, jax.random.PRNGKey(0))
sh8 = SH.shardings_for_specs(specs, rules, mesh8)
params8 = jax.tree.map(jax.device_put, params, sh8)
params4 = elastic_remesh(params8,
                         mesh4,
                         lambda m: SH.shardings_for_specs(specs, rules, m))
for k in params:
    np.testing.assert_array_equal(np.asarray(params[k]),
                                  np.asarray(params4[k]))
    assert len(params4[k].sharding.mesh.devices.flatten()) == 4
print("ELASTIC_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]
