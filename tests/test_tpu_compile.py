"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed next to JAX, so the chunk runners can be
lowered and compiled for a ``v5e:2x2`` topology that is only described:
what the chip's compiler would refuse (a shape, a sharding, device
memory) fails here at no chip time.  Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and with
several test workers every worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro import solver
from repro.core import api, dist_solve, eps
from repro.core.models import ZOO, large_instance
from repro.distributed.sharding import dist_solve_specs
from repro.kernels.fixpoint_kernel import MOSAIC_REFUSAL, fixpoint_pallas

LANES, POOL = 1024, 4096          # chip_smoke.py phase 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), (dist_solve.AXIS,))


@pytest.fixture(scope="module")
def rcpsp96():
    return ZOO["rcpsp"].build_model(large_instance("rcpsp", seed=0))[0] \
        .compile()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _config(**kw):
    return solver.SolveConfig.preset("prove", backend="gather",
                                     n_lanes=LANES, eps_target=POOL, **kw)


def test_gather_chunk_runner_compiles_for_one_chip(one_chip, rcpsp96):
    """(a) The single-device chunk runner at chip width."""
    cfg = _config()
    opts = cfg.search_options()
    fn = jax.jit(lambda cm, sl, su, c: api._run_chunk(
        opts, cfg.stop_on_first, cfg.chunk, (), cm, sl, su, c))
    pool = jax.ShapeDtypeStruct((POOL, rcpsp96.n_vars), rcpsp96.jdtype,
                                sharding=one_chip)
    carry = jax.eval_shape(lambda: api._init_carry(rcpsp96, LANES, opts))
    compiled = fn.lower(_shapes(rcpsp96, one_chip), pool, pool,
                        _shapes(carry, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30      # v5e HBM


def test_dist_runner_compiles_for_four_chips(mesh4, rcpsp96):
    """(b) The `dist_solve` sharded runner on a 4-chip mesh: the bound
    sync is an all-reduce across the chips."""
    cfg = _config(mesh_shards=4)
    opts = cfg.search_options()
    carry = jax.eval_shape(lambda: api._init_carry(rcpsp96, 4 * LANES, opts,
                                                   n_heads=4))
    runner = dist_solve._build_runner(solver.Solver(cfg), rcpsp96, cfg,
                                      mesh4, carry[0], POOL)
    pool_spec, carry_spec = dist_solve_specs(carry[0], POOL, mesh4)
    replicated = NamedSharding(mesh4, jax.sharding.PartitionSpec())
    pool = jax.ShapeDtypeStruct((POOL, rcpsp96.n_vars), rcpsp96.jdtype,
                                sharding=NamedSharding(mesh4, pool_spec))
    carry_s = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh4, s)),
        carry, carry_spec)
    compiled = runner.fn.lower(_shapes(rcpsp96, replicated), pool, pool,
                               carry_s).compile()
    assert "all-reduce" in compiled.as_text()


def test_eps_split_loop_compiles_for_one_chip(one_chip, rcpsp96):
    """(d) The EPS decomposition's split loop at the chip's pool size."""
    cfg = _config()
    fn = jax.jit(eps.split_program(POOL, cfg.var_strategy,
                                   cfg.val_strategy))
    root = jax.ShapeDtypeStruct((rcpsp96.n_vars,), rcpsp96.jdtype,
                                sharding=one_chip)
    compiled = fn.lower(_shapes(rcpsp96, one_chip), root, root).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason=MOSAIC_REFUSAL)
def test_fixpoint_pallas_lowers_for_one_chip(one_chip, rcpsp96):
    """(c) The unfused Pallas kernel compiled by Mosaic, not interpreted.
    Whoever makes it lower removes the guard and this mark."""
    store = jax.ShapeDtypeStruct((32, rcpsp96.n_vars), rcpsp96.jdtype,
                                 sharding=one_chip)
    fn = jax.jit(lambda cm, lb, ub: fixpoint_pallas(cm, lb, ub, lane_tile=32,
                                                    interpret=False))
    fn.lower(_shapes(rcpsp96, one_chip), store, store).compile()
