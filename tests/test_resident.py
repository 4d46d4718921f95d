"""Resident search megakernel tests (DESIGN.md §13, ISSUE 6).

Three layers of coverage for `kernels/fixpoint_kernel.search_pallas`
and its `pallas_resident` backend:

* **bit-parity** — K fused supersteps inside the megakernel must equal
  K unfused `search.lanes_step` iterations field-for-field (stores,
  decision path, status flags, stats, best bound, pool cursor), for
  K ∈ {1, 4, 16} and for the §Perf-H1 capped-fixpoint soundness guard
  (an unconverged superstep defers branching *inside the kernel* too);
* **solver parity** — `pallas_resident` with K=16 proves the same
  optimum as `gather` through the full session API on zoo instances;
* **VMEM budget** — `vmem_budget`/`fit_lane_tile` raise clear errors /
  auto-shrink with a warning instead of handing Mosaic an
  un-allocatable kernel, and the auto-shrunk multi-tile kernel (strided
  pool shards — a different dispatch trajectory) stays sound+complete.

Everything runs in Pallas interpret mode (no TPU in CI).
"""

import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro import solver
from repro.core import eps, models as zoo, search as S
from repro.kernels import fixpoint_kernel as FK


def _setup(n_lanes=8, eps_target=8, max_depth=64, **opt_kw):
    inst = zoo.small_instance("rcpsp", seed=0)
    cm = zoo.ZOO["rcpsp"].build_model(inst)[0].compile()
    opts = S.SearchOptions(max_depth=max_depth, **opt_kw)
    subs_lb, subs_ub = eps.decompose(cm, eps_target, opts)
    subs_lb = jnp.asarray(subs_lb)
    subs_ub = jnp.asarray(subs_ub)
    st = S.init_lanes(cm, n_lanes, opts)
    gbest = jnp.asarray(jnp.iinfo(cm.jdtype).max // 4, cm.jdtype)
    return cm, opts, subs_lb, subs_ub, st, gbest


def _gdone(st, stop_on_first):
    g = bool(np.asarray(st.done).all())
    if stop_on_first:
        g |= bool(np.asarray(st.has_sol).any())
    return g


def _unfused(cm, opts, subs_lb, subs_ub, st, gbest, supersteps):
    """The host reference: K guarded `lanes_step` iterations — exactly
    the unfused `_run_chunk` semantics the kernel's per-superstep
    `lax.cond(gdone, identity, run)` must reproduce."""
    pool_head = jnp.zeros((), jnp.int32)
    it = 0
    for _ in range(supersteps):
        if _gdone(st, opts.stop_on_first):
            break
        st, pool_head = S.lanes_step(cm, subs_lb, subs_ub, opts, st,
                                     gbest, pool_head)
        gbest = jnp.minimum(gbest, S.lanes_best(st, cm.jdtype))
        it += 1
    return st, gbest, it, int(pool_head)


def _assert_state_equal(a: S.LaneState, b: S.LaneState):
    for f in S.LaneState._fields:
        av, bv = getattr(a, f), getattr(b, f)
        if av is None or bv is None:       # inactive bitset stores
            assert av is None and bv is None, f"LaneState.{f} presence"
            continue
        ref, got = np.asarray(av), np.asarray(bv)
        assert ref.dtype == got.dtype or f in FK._BOOL_FIELDS
        np.testing.assert_array_equal(
            ref.astype(np.int64), got.astype(np.int64),
            err_msg=f"LaneState.{f} diverged")


@pytest.mark.parametrize("supersteps", [1, 4, 16])
def test_fused_bit_parity(supersteps):
    cm, opts, subs_lb, subs_ub, st0, gbest0 = _setup()
    ref_st, ref_gbest, ref_it, ref_head = _unfused(
        cm, opts, subs_lb, subs_ub, st0, gbest0, supersteps)
    st, gbest, it, head, stopped = FK.search_pallas(
        cm, subs_lb, subs_ub, st0, gbest0, jnp.asarray(0, jnp.int32),
        jnp.zeros((1,), jnp.int32), supersteps=supersteps, lane_tile=0,
        interpret=True)
    _assert_state_equal(ref_st, st)
    assert int(gbest) == int(ref_gbest)
    assert int(it) == ref_it
    assert int(head[0]) == ref_head
    assert bool(stopped) == _gdone(ref_st, opts.stop_on_first)


def test_fused_bit_parity_capped_fixpoint():
    """§Perf H1 soundness guard inside the kernel: with
    max_fixpoint_iters=1 most supersteps end unconverged, so
    `lane_commit_tile` must defer branching (keep sweeping, no node
    expansion) — fused and unfused must still agree bit-for-bit, and
    the capped search must still reach the true optimum."""
    cm, opts, subs_lb, subs_ub, st0, gbest0 = _setup(max_fixpoint_iters=1)
    ref_st, ref_gbest, ref_it, ref_head = _unfused(
        cm, opts, subs_lb, subs_ub, st0, gbest0, 16)
    st, gbest, it, head, _ = FK.search_pallas(
        cm, subs_lb, subs_ub, st0, gbest0, jnp.asarray(0, jnp.int32),
        jnp.zeros((1,), jnp.int32), supersteps=16, lane_tile=0,
        max_fixpoint_iters=1, interpret=True)
    _assert_state_equal(ref_st, st)
    assert int(gbest) == int(ref_gbest)
    assert int(it) == ref_it
    # the guard really fired: mid-flight (before the search exhausts and
    # totals converge to the same tree) a capped run has expanded fewer
    # nodes than an uncapped one, because unconverged supersteps defer
    # branching.  Exercise it THROUGH the kernel at supersteps=4.
    capped4, *_ = FK.search_pallas(
        cm, subs_lb, subs_ub, st0, gbest0, jnp.asarray(0, jnp.int32),
        jnp.zeros((1,), jnp.int32), supersteps=4, lane_tile=0,
        max_fixpoint_iters=1, interpret=True)
    full4, *_ = _unfused(cm, S.SearchOptions(max_depth=64),
                         subs_lb, subs_ub, st0, gbest0, 4)
    assert (int(np.asarray(capped4.n_nodes).sum())
            < int(np.asarray(full4.n_nodes).sum()))


def test_stop_on_first_freezes_mid_launch():
    """`stop_on_first` can trip in the middle of a K-launch; the kernel
    must freeze (identity supersteps) from that point, matching the
    host loop's early break — `it` counts only the live supersteps."""
    cm, opts, subs_lb, subs_ub, st0, gbest0 = _setup(stop_on_first=True)
    ref_st, ref_gbest, ref_it, ref_head = _unfused(
        cm, opts, subs_lb, subs_ub, st0, gbest0, 16)
    st, gbest, it, head, stopped = FK.search_pallas(
        cm, subs_lb, subs_ub, st0, gbest0, jnp.asarray(0, jnp.int32),
        jnp.zeros((1,), jnp.int32), supersteps=16, lane_tile=0,
        stop_on_first=True, interpret=True)
    assert ref_it < 16, "instance too easy to exercise mid-launch stop"
    _assert_state_equal(ref_st, st)
    assert int(it) == ref_it
    assert bool(stopped)


@pytest.mark.parametrize("model", ["rcpsp", "nqueens", "jobshop"])
def test_zoo_proven_optimum_parity(model):
    """K=16 resident solve proves the same optimum as gather through the
    session API (the ISSUE-6 acceptance bar, bit-identical objectives)."""
    inst = zoo.small_instance(model, seed=0)
    cm = zoo.ZOO[model].build_model(inst)[0].compile()
    kw = dict(n_lanes=8, eps_target=8, timeout_s=600, max_depth=512)
    ref = solver.Solver(solver.SolveConfig.preset(
        "prove", backend="gather", **kw)).solve(cm)
    res = solver.Solver(solver.SolveConfig.preset(
        "prove", backend="pallas_resident", supersteps_per_launch=16,
        **kw)).solve(cm)
    assert ref.status == solver.OPTIMAL
    assert res.status == ref.status
    assert res.objective == ref.objective


# -------------------------------------------------------------------------
# VMEM budget + auto-shrink
# -------------------------------------------------------------------------

def _cm():
    inst = zoo.small_instance("rcpsp", seed=0)
    return zoo.ZOO["rcpsp"].build_model(inst)[0].compile()


def test_vmem_budget_shape():
    cm = _cm()
    b1 = FK.vmem_budget(cm, 1)
    b8 = FK.vmem_budget(cm, 8)
    assert set(b1) == {"tables", "stores", "state", "scratch", "total"}
    assert b1["state"] == 0                      # non-resident: no state
    assert b8["tables"] == b1["tables"]          # broadcast, tile-invariant
    assert b8["stores"] == 8 * b1["stores"]
    assert b8["total"] > b1["total"]
    r8 = FK.vmem_budget(cm, 8, resident=True, max_depth=64, pool_size=8)
    assert r8["state"] > 0
    assert r8["total"] > b8["total"]
    # smoke-tier models must actually fit the default budget
    assert r8["total"] <= FK.VMEM_LIMIT_BYTES


def test_fit_lane_tile_clamps_and_shrinks():
    cm = _cm()
    assert FK.fit_lane_tile(cm, 64, 8) == 8      # clamped to n_lanes
    assert FK.fit_lane_tile(cm, 8, 8) == 8       # fits: unchanged
    # a limit between budget(4) and budget(8) forces exactly one halving
    lim = (FK.vmem_budget(cm, 4)["total"]
           + FK.vmem_budget(cm, 8)["total"]) // 2
    with pytest.warns(UserWarning, match="shrinking to 4"):
        assert FK.fit_lane_tile(cm, 8, 8, limit_bytes=lim) == 4


def test_fit_lane_tile_compiled_kernel_refuses_to_shrink():
    """A compiled kernel raises where the interpreter would halve the
    tile: the TPU's tiling refuses 4-, 2- and 1-lane blocks."""
    cm = _cm()
    lim = (FK.vmem_budget(cm, 4)["total"]
           + FK.vmem_budget(cm, 8)["total"]) // 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(ValueError, match="cannot shrink"):
            FK.fit_lane_tile(cm, 8, 8, limit_bytes=lim, interpret=False)
    assert FK.fit_lane_tile(cm, 8, 8, interpret=False) == 8


def test_fit_lane_tile_clear_error_when_nothing_fits():
    cm = _cm()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(ValueError, match="does not fit VMEM"):
            FK.fit_lane_tile(cm, 8, 8, limit_bytes=1024)


def test_auto_shrink_resident_still_sound(monkeypatch):
    """Force the resident kernel to auto-shrink to 2 grid cells (strided
    pool shards — a different dispatch trajectory than the one-cell
    parity mode) and check the solve is still sound and complete: same
    proven optimum as gather."""
    cm = _cm()
    kw = dict(n_lanes=8, eps_target=8, timeout_s=600, max_depth=512)
    ref = solver.Solver(solver.SolveConfig.preset(
        "prove", backend="gather", **kw)).solve(cm)
    # the limit must straddle budget(tile=4)..budget(tile=8) for the
    # ACTUAL pool the session will decompose, so one halving happens
    pool = eps.decompose(cm, 8, S.SearchOptions(max_depth=512))[0].shape[0]
    lim = (FK.vmem_budget(cm, 4, resident=True, max_depth=512,
                          pool_size=pool)["total"]
           + FK.vmem_budget(cm, 8, resident=True, max_depth=512,
                            pool_size=pool)["total"]) // 2
    monkeypatch.setattr(FK, "VMEM_LIMIT_BYTES", int(lim))
    with pytest.warns(UserWarning, match="search_pallas: lane_tile=8"):
        res = solver.Solver(solver.SolveConfig.preset(
            "prove", backend="pallas_resident", supersteps_per_launch=8,
            **kw)).solve(cm)
    assert res.status == ref.status == solver.OPTIMAL
    assert res.objective == ref.objective


def test_config_rejects_supersteps_on_other_backends():
    with pytest.raises(ValueError, match="pallas_resident"):
        solver.SolveConfig.preset("prove", backend="gather",
                                  supersteps_per_launch=4)
