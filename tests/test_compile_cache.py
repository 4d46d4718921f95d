"""Where `launch.compile_cache` puts JAX's persistent compilation cache.

Each case runs in a fresh interpreter: turning the cache on changes the
process-wide JAX configuration, which the other tests must not inherit.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = r"""
import json, jax
from repro.launch.compile_cache import enable_compile_cache, cache_counts
path = enable_compile_cache()
jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()
print(json.dumps(dict(path=path, config=jax.config.jax_compilation_cache_dir,
                      counts=cache_counts())))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir_honours_env_else_fixed_checkout_path(from_env, tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env=env, cwd=str(tmp_path), timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    want = (str(tmp_path) if from_env
            else os.path.join(ROOT, ".jax_cache"))
    assert out["path"] == out["config"]
    assert os.path.realpath(out["path"]) == os.path.realpath(want)
    assert out["counts"]["misses"] + out["counts"]["hits"] >= 1
