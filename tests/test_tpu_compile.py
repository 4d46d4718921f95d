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

import collections
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro import solver
from repro.core import api, dist_solve, eps, fixpoint
from repro.core.models import ZOO, jobshop, large_instance, rcpsp
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


def test_optional_members_runner_compiles_for_one_chip(one_chip):
    """(e) The chunk runner of a multi-mode model (optional Cumulative
    members, DESIGN.md §18) at the prove preset: 64 lanes, a pool of
    256, 30 jobs x 3 modes."""
    cm = ZOO["mrcpsp"].build_model(large_instance("mrcpsp", seed=0))[0] \
        .compile()
    assert cm.cu_optional and cm.cu_layout == "dense"
    cfg = solver.SolveConfig.preset("prove", backend="gather")
    opts = cfg.search_options()
    fn = jax.jit(lambda cm, sl, su, c: api._run_chunk(
        opts, cfg.stop_on_first, cfg.chunk, (), cm, sl, su, c))
    pool = jax.ShapeDtypeStruct((256, cm.n_vars), cm.jdtype,
                                sharding=one_chip)
    carry = jax.eval_shape(lambda: api._init_carry(cm, cfg.n_lanes, opts))
    compiled = fn.lower(_shapes(cm, one_chip), pool, pool,
                        _shapes(carry, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


# Operations per StableHLO op kind in the lowered programs of a
# dense-layout RCPSP (30 jobs, 4 resources, the prove preset: 64 lanes, a
# pool of 256): a change to another tile must leave these programs alone.
DENSE_RUN_CHUNK_OPS = {
    "add": 37, "and": 66, "broadcast_in_dim": 267, "compare": 82,
    "concatenate": 3, "constant": 159, "convert": 16, "divide": 1,
    "gather": 10, "iota": 7, "maximum": 12, "minimum": 16, "multiply": 7,
    "negate": 13, "not": 15, "or": 23, "reduce": 36, "reduce_window": 2,
    "remainder": 1, "reshape": 24, "return": 9, "scatter": 2, "select": 45,
    "sign": 2, "slice": 7, "subtract": 11, "while": 2}
DENSE_SPLIT_LOOP_OPS = {
    "add": 51, "and": 33, "broadcast_in_dim": 227, "case": 1, "compare": 86,
    "concatenate": 7, "constant": 171, "convert": 12, "divide": 1,
    "dynamic_slice": 4, "gather": 13, "iota": 5, "maximum": 13,
    "minimum": 9, "multiply": 6, "negate": 12, "not": 4, "or": 16,
    "reduce": 33, "reduce_window": 1, "remainder": 1, "reshape": 21,
    "return": 21, "scatter": 11, "select": 54, "sign": 2, "slice": 4,
    "subtract": 9, "while": 2}


def _op_counts(text):
    """Operations per StableHLO op kind in a lowered module's text."""
    ops = re.findall(r"(?<![#!\w.])stablehlo\.([a-z_]+)", text)
    return dict(collections.Counter(ops))


def test_dense_layout_programs_keep_their_operations(one_chip):
    """(f) The chunk runner and the split loop of a dense-layout model,
    lowered for one chip, count the recorded operations per kind."""
    cm = rcpsp.build_model(rcpsp.generate(30, n_resources=4, seed=0))[0] \
        .compile(bank_layout="dense")
    cfg = solver.SolveConfig.preset("prove", backend="gather")
    opts = cfg.search_options()
    fn = jax.jit(lambda cm, sl, su, c: api._run_chunk(
        opts, cfg.stop_on_first, cfg.chunk, (), cm, sl, su, c))
    pool = jax.ShapeDtypeStruct((256, cm.n_vars), cm.jdtype,
                                sharding=one_chip)
    carry = jax.eval_shape(lambda: api._init_carry(cm, cfg.n_lanes, opts))
    text = fn.lower(_shapes(cm, one_chip), pool, pool,
                    _shapes(carry, one_chip)).as_text()
    assert _op_counts(text) == DENSE_RUN_CHUNK_OPS
    split = jax.jit(eps.split_program(256, cfg.var_strategy,
                                      cfg.val_strategy))
    root = jax.ShapeDtypeStruct((cm.n_vars,), cm.jdtype, sharding=one_chip)
    text = split.lower(_shapes(cm, one_chip), root, root).as_text()
    assert _op_counts(text) == DENSE_SPLIT_LOOP_OPS


def _scan_lengths(jaxpr):
    """Trip counts of every scan in a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_lengths(sub)
    return out


def test_sparse_cumulative_loops_span_one_row():
    """(g) On a Taillard-shaped job-shop (15 x 15, the sparse Cumulative
    tile) the sweep's loops run 2T steps, T the widest row's block, and
    none runs over all 2M events of the bank."""
    m, _ = jobshop.build_model(jobshop.generate(15, n_machines=15, seed=0,
                                                max_duration=99))
    cm = m.compile()
    assert cm.cu_layout == "sparse" and cm.cu_width == 16
    n_members = sum(len(cu.starts) for cu in m.cumulatives)
    store = jnp.broadcast_to(cm.lb0, (64, cm.n_vars))
    lengths = _scan_lengths(jax.make_jaxpr(
        lambda lb, ub: fixpoint.sweep_batch(cm, lb, ub))(
            store, jnp.broadcast_to(cm.ub0, store.shape)).jaxpr)
    assert lengths == [2 * cm.cu_width]
    assert max(lengths) < 2 * n_members


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
