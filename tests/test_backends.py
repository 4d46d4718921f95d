"""Backend-parity tests for the propagation-backend layer (core/backend.py).

The three registered backends — gather (XLA sweep), scatter (join oracle)
and pallas (VMEM kernel, interpret-mode on CPU) — must reach identical
least fixed points on lane-batched [L, V] stores, per the comparison spec
of kernels/ops.py: equal failed-lane masks, bit-identical stores on every
non-failed lane (integer lattice ⇒ exact equality, no tolerance).

Seeded-random instances keep these property-shaped without requiring
`hypothesis` (which the offline container lacks); the loops below are the
batched-path extension of the gather/scatter oracle tests in
test_semantics.py / test_kernels.py.
"""

import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, search as S
from repro.core.backend import (PropagationBackend, available_backends,
                                get_backend, register_backend)
from repro.core.fixpoint import fixpoint, fixpoint_batch
from repro.core.models import rcpsp
from util import random_model, random_substores, solve_session

ALL = ("gather", "scatter", "pallas")


def _pallas_kw(name, lanes):
    return dict(lane_tile=min(4, lanes)) if name == "pallas" else {}


def _assert_parity(cm, lbs, ubs, max_iters=None):
    lbs, ubs = jnp.asarray(lbs), jnp.asarray(ubs)
    L = int(lbs.shape[0])
    ref_l, ref_u, _, ref_conv = get_backend("gather").fixpoint_batch(
        cm, lbs, ubs, max_iters=max_iters)
    ref_l, ref_u = np.asarray(ref_l), np.asarray(ref_u)
    failed = (ref_l > ref_u).any(axis=1)
    ok = ~failed
    for name in ("scatter", "pallas"):
        al, au, _, conv = get_backend(name, **_pallas_kw(name, L)) \
            .fixpoint_batch(cm, lbs, ubs, max_iters=max_iters)
        al, au = np.asarray(al), np.asarray(au)
        np.testing.assert_array_equal(failed, (al > au).any(axis=1),
                                      err_msg=f"failed-mask mismatch: {name}")
        np.testing.assert_array_equal(ref_l[ok], al[ok], err_msg=name)
        np.testing.assert_array_equal(ref_u[ok], au[ok], err_msg=name)
        if max_iters is None:
            # uncapped: every backend must report a genuine fixed point
            assert bool(np.asarray(ref_conv).all())
            assert bool(np.asarray(conv).all()), name
    return failed


def test_backend_parity_random_rcpsp_batched():
    """Seeded random RCPSP instances: all backends agree on batched
    fixpoints (the acceptance-criterion property test)."""
    saw_failed = saw_ok = False
    for seed in range(4):
        inst = rcpsp.generate(4 + seed, n_resources=2, seed=seed,
                              edge_prob=0.3)
        m, _ = rcpsp.build_model(inst)
        cm = m.compile()
        rng = np.random.default_rng(100 + seed)
        lbs, ubs = random_substores(rng, cm, 6)
        failed = _assert_parity(cm, lbs, ubs)
        saw_failed |= bool(failed.any())
        saw_ok |= bool((~failed).any())
    assert saw_ok          # the property must have exercised live lanes


def test_backend_parity_random_models_batched():
    """Random mixed plain/reified models, including failing stores."""
    for seed in range(6):
        rng = np.random.default_rng(seed)
        cm = random_model(rng, n_vars=2 + seed, n_props=3 + 2 * seed) \
            .compile()
        lbs, ubs = random_substores(rng, cm, 5)
        _assert_parity(cm, lbs, ubs)


def test_backend_parity_capped_iters():
    """With a sweep cap the XLA backends stay bit-identical (bounded
    chaotic iteration is deterministic); converged flags must then be
    honest: unconverged lanes may exist."""
    inst = rcpsp.generate(6, n_resources=2, seed=7, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    rng = np.random.default_rng(7)
    lbs, ubs = random_substores(rng, cm, 4)
    lbs, ubs = jnp.asarray(lbs), jnp.asarray(ubs)
    gl, gu, gs, gc = get_backend("gather").fixpoint_batch(cm, lbs, ubs,
                                                          max_iters=1)
    sl, su, ss, sc = get_backend("scatter").fixpoint_batch(cm, lbs, ubs,
                                                           max_iters=1)
    np.testing.assert_array_equal(np.asarray(gl), np.asarray(sl))
    np.testing.assert_array_equal(np.asarray(gu), np.asarray(su))
    assert int(np.asarray(gs).max()) <= 1
    # honesty of the convergence flags (search's §Perf H1 guard depends on
    # it): lanes stopped by the cap must NOT claim a fixed point — and the
    # root stores here genuinely need >1 sweep, so some lane is unconverged
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(sc))
    assert not bool(np.asarray(gc).all())
    # sanity that the cap was the reason: uncapped, all lanes converge
    _, _, _, full_c = get_backend("gather").fixpoint_batch(cm, lbs, ubs)
    assert bool(np.asarray(full_c).all())


def test_batched_matches_vmapped_single_store():
    """fixpoint_batch is bit-identical to vmap(fixpoint) — stores, sweep
    counts and convergence flags (the hoisting is a pure refactor)."""
    rng = np.random.default_rng(42)
    cm = random_model(rng, n_vars=7, n_props=11).compile()
    lbs, ubs = random_substores(rng, cm, 8)
    lbs, ubs = jnp.asarray(lbs), jnp.asarray(ubs)
    vl, vu, vi, vc = jax.vmap(lambda l, u: fixpoint(cm, l, u))(lbs, ubs)
    bl, bu, bi, bc = fixpoint_batch(cm, lbs, ubs)
    np.testing.assert_array_equal(np.asarray(vl), np.asarray(bl))
    np.testing.assert_array_equal(np.asarray(vu), np.asarray(bu))
    np.testing.assert_array_equal(np.asarray(vi), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(bc))


def test_single_store_entry_point():
    """The protocol's single-store fixpoint agrees with the batch of 1."""
    rng = np.random.default_rng(5)
    cm = random_model(rng, n_vars=5, n_props=8).compile()
    lbs, ubs = random_substores(rng, cm, 1)
    for name in ALL:
        be = get_backend(name, **_pallas_kw(name, 1))
        sl, su, _, _ = be.fixpoint(cm, jnp.asarray(lbs[0]),
                                   jnp.asarray(ubs[0]))
        bl, bu, _, _ = be.fixpoint_batch(cm, jnp.asarray(lbs),
                                         jnp.asarray(ubs))
        np.testing.assert_array_equal(np.asarray(sl), np.asarray(bl)[0])
        np.testing.assert_array_equal(np.asarray(su), np.asarray(bu)[0])


def test_registry_roundtrip_and_unknown():
    assert set(ALL) <= set(available_backends())
    for name in ALL:
        be = get_backend(name)
        assert isinstance(be, PropagationBackend)
        assert be.name == name
    with pytest.raises(ValueError, match="unknown propagation backend"):
        get_backend("cuda")
    # registration is open: downstream tuned kernels can claim a name
    class _Probe(type(get_backend("gather"))):
        name = "probe"
    register_backend("probe", _Probe)
    try:
        assert get_backend("probe").name == "probe"
    finally:
        from repro.core import backend as B
        del B._REGISTRY["probe"]


@pytest.mark.parametrize("name", ["pallas", "pallas_resident"])
def test_pallas_backends_refuse_compiled_kernels(name):
    """Without the interpreter the kernels would go to Mosaic, which
    refuses them: construction says so and names the refused gather,
    instead of failing inside lowering or falling back to gather."""
    with pytest.raises(NotImplementedError, match=r"fixpoint\.py:84"):
        get_backend(name, interpret=False)


def test_engine_solves_with_every_backend():
    """solve_session(..., opts=SearchOptions(backend=...)) end-to-end on
    CPU for all three backends, identical optimum and node counts (the
    superstep is deterministic regardless of propagation strategy)."""
    inst = rcpsp.generate(5, n_resources=2, seed=3, edge_prob=0.3)
    m, _ = rcpsp.build_model(inst)
    cm = m.compile()
    results = {}
    for name in ALL:
        opts = S.SearchOptions(
            var_strategy=S.MIN_LB, max_depth=128, backend=name,
            backend_opts=((("lane_tile", 4),) if name == "pallas" else ()))
        results[name] = solve_session(cm, n_lanes=4, n_subproblems=8,
                                     opts=opts, timeout_s=600, chunk=64)
    ref = results["gather"]
    assert ref.status == engine.OPTIMAL
    for name, res in results.items():
        assert res.status == engine.OPTIMAL, name
        assert res.objective == ref.objective, name
        assert res.n_nodes == ref.n_nodes, name


def test_search_propagation_is_batched():
    """Structural guard for the acceptance criterion: the search module
    has no per-lane fixpoint call left — propagation enters only through
    the backend layer's batched entry point."""
    import ast
    import inspect
    from repro.core import search
    tree = ast.parse(inspect.getsource(search))
    calls = [n.func.attr if isinstance(n.func, ast.Attribute) else
             getattr(n.func, "id", None)
             for n in ast.walk(tree) if isinstance(n, ast.Call)]
    assert "fixpoint" not in calls          # single-store form is gone
    assert "fixpoint_batch" in calls        # batched backend call is there
