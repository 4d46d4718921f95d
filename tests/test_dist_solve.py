"""Distributed EPS engine (core/dist_solve.py, DESIGN.md §14).

The multi-device parts run through the `fake_devices` fixture
(conftest.py): a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the flag only
takes effect before jax initializes, so the parent process keeps its
single device.  Host-side pieces (the steal planner, config validation,
a 1-shard mesh on the real device) run in-process.
"""

import json

import numpy as np
import pytest

from repro import solver
from repro.core import dist_solve, eps
from repro.core import models as zoo
from repro.core.api import Solver
from repro.distributed.planner import plan_steal

# ---------------------------------------------------------------------------
# host-side: steal planner properties
# ---------------------------------------------------------------------------


def test_plan_steal_balances_and_preserves_ids():
    rng = np.random.default_rng(0)
    for _ in range(50):
        D = int(rng.integers(1, 6))
        owned = [list(map(int, rng.choice(1000, size=rng.integers(0, 20),
                                          replace=False) + 1000 * d))
                 for d in range(D)]
        before = sorted(x for o in owned for x in o)
        out, moved = plan_steal(owned, D)
        after = sorted(x for o in out for x in o)
        assert after == before                       # nothing lost/invented
        sizes = sorted(len(o) for o in out)
        assert sizes[-1] - sizes[0] <= 1             # balanced to ±1
        assert moved <= len(before)


def test_plan_steal_keeps_local_work_first():
    # a shard under quota keeps everything it had; movement is minimal
    out, moved = plan_steal([[1, 2, 3, 4, 5, 6], []], 2)
    assert set(out[0]) == {1, 2, 3}
    assert set(out[1]) == {4, 5, 6}
    assert moved == 3
    out, moved = plan_steal([[1, 2], [3, 4]], 2)
    assert (out, moved) == ([[1, 2], [3, 4]], 0)


def test_plan_steal_shrink_remesh():
    # the ft path replans D shards' ids over D-1 survivors
    out, _ = plan_steal([[0, 1], [2, 3], [4, 5]], 2)
    assert sorted(x for o in out for x in o) == [0, 1, 2, 3, 4, 5]
    assert [len(o) for o in out] == [3, 3]


# ---------------------------------------------------------------------------
# host-side: config plumbing
# ---------------------------------------------------------------------------


def test_mesh_shards_config_validation():
    with pytest.raises(ValueError, match="mesh_shards"):
        solver.SolveConfig(mesh_shards=0)
    with pytest.raises(ValueError, match="pallas_resident"):
        solver.SolveConfig(mesh_shards=2, backend="pallas_resident")
    with pytest.raises(ValueError, match="mutually exclusive"):
        import jax
        mesh = jax.make_mesh((1,), ("lanes",))
        solver.SolveConfig(mesh=mesh, lane_axes=("lanes",), mesh_shards=2)


def test_mesh_shards_needs_devices():
    import jax
    if jax.device_count() >= 64:
        pytest.skip("process already has many devices")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        dist_solve._mesh_for(64)


def test_solve_many_rejects_mesh_shards():
    m, _ = zoo.ZOO["knapsack"].build_model(zoo.small_instance("knapsack"))
    cm = m.compile()
    with pytest.raises(ValueError, match="single-device"):
        Solver(solver.SolveConfig.preset(
            "prove", mesh_shards=1)).solve_many([cm])


def test_mesh_shards_one_matches_plain_solve():
    """A 1-shard mesh runs the whole dist path (shard_map over one
    device, host chunk loop, incumbent checkpoint) on the real device
    and must reproduce the plain engine bit-for-bit in
    status/objective."""
    for name in ("knapsack", "nqueens"):
        m, _ = zoo.ZOO[name].build_model(zoo.small_instance(name, seed=0))
        cm = m.compile()
        cfg0 = solver.SolveConfig.preset("prove", n_lanes=4, eps_target=16)
        ref = Solver(cfg0).solve(cm)
        res, tr = dist_solve.solve_dist(cm, cfg0.replace(mesh_shards=1))
        assert (res.status, res.objective) == (ref.status, ref.objective)
        assert tr.n_chunks >= 1
        assert tr.n_bound_syncs == tr.n_chunks


def test_solver_session_delegates_and_caches():
    m, _ = zoo.ZOO["knapsack"].build_model(zoo.small_instance("knapsack"))
    cm = m.compile()
    sess = Solver(solver.SolveConfig.preset("prove", n_lanes=4,
                                            eps_target=16, mesh_shards=1))
    evs = list(sess.solve_iter(cm))
    assert evs[-1].final and evs[-1].result is not None
    builds = sess.stats["runner_builds"]
    res2 = sess.solve(cm)
    assert sess.stats["runner_builds"] == builds      # warm: cached runner
    assert sess.stats["runner_hits"] >= 1
    assert res2.status == evs[-1].result.status


# ---------------------------------------------------------------------------
# multi-device: parity matrix, invariants, stealing, device loss
# ---------------------------------------------------------------------------

_PARITY_CODE = r"""
import json
from repro import solver
from repro.core import dist_solve
from repro.core import models as zoo
from repro.core.api import Solver

out = []
for name in ("knapsack", "coloring", "rcpsp"):
    m, _ = zoo.ZOO[name].build_model(zoo.small_instance(name, seed=0))
    cm = m.compile()
    for backend in ("gather", "pallas"):
        cfg0 = solver.SolveConfig.preset("prove", n_lanes=4, eps_target=16,
                                         backend=backend)
        ref = Solver(cfg0).solve(cm)
        for D in (1, 2, 4, 8):
            cfg = cfg0.replace(mesh_shards=D)
            res, tr = dist_solve.solve_dist(cm, cfg, session=Solver(cfg))
            g = tr.gbest_per_chunk
            out.append(dict(
                model=name, backend=backend, mesh=D,
                status=res.status, ref_status=ref.status,
                objective=res.objective, ref_objective=ref.objective,
                monotone=all(a >= b for a, b in zip(g, g[1:])),
                chunks=tr.n_chunks, syncs=tr.n_bound_syncs))
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_mesh_parity_matrix(fake_devices):
    """mesh ∈ {1,2,4,8} × {gather, pallas} × 3 zoo models: bit-equal
    status/objective vs the single-device solve, monotone bound trace,
    one host bound-sync per chunk."""
    out = fake_devices(_PARITY_CODE)
    recs = json.loads(out.split("RESULT ", 1)[1])
    assert len(recs) == 3 * 2 * 4
    for r in recs:
        cell = f"{r['model']}/{r['backend']}/mesh={r['mesh']}"
        assert r["status"] == r["ref_status"], (cell, r)
        assert r["objective"] == r["ref_objective"], (cell, r)
        assert r["status"] in ("OPTIMAL", "SAT"), (cell, r)
        assert r["monotone"], (cell, r)
        assert r["syncs"] == r["chunks"], (cell, r)


_STEAL_CODE = r"""
import json
import numpy as np
from repro import solver
from repro.core import dist_solve, eps
from repro.core import models as zoo
from repro.core.api import Solver

# engineered imbalance: the id space splits contiguously across shards,
# so failing the second half gives shard 1 a frontier that drains almost
# immediately while shard 0 still holds deep subproblems
m, _ = zoo.ZOO["coloring"].build_model(zoo.small_instance("coloring", 0))
cm = m.compile()
cfg0 = solver.SolveConfig.preset("prove", n_lanes=2, eps_target=16)
lb, ub = map(np.asarray, eps.decompose(cm, 16, cfg0.search_options()))
half = (lb.shape[0] + 1) // 2
lb[half:, 0], ub[half:, 0] = 1, 0
ref = Solver(cfg0).solve(cm, subs=(lb, ub))
cfg = cfg0.replace(chunk=1, mesh_shards=2)
res, tr = dist_solve.solve_dist(cm, cfg, subs=(lb, ub), session=Solver(cfg))

ok_partition = True
for owned, consumed in zip(tr.assignments, tr.consumed_per_chunk):
    flat = [i for o in owned for i in o]
    ok_partition &= len(flat) == len(set(flat))          # disjoint shards
    ok_partition &= set(flat).isdisjoint(consumed)       # queue vs consumed
    ok_partition &= set(flat) | set(consumed) == set(tr.all_ids)  # cover
print("RESULT " + json.dumps(dict(
    status=res.status, ref_status=ref.status,
    objective=res.objective, ref_objective=ref.objective,
    steals=tr.n_steals, steal_events=tr.steal_events,
    partition_ok=ok_partition)))
"""


@pytest.mark.slow
def test_steal_fires_and_partition_invariant(fake_devices):
    """A drained shard triggers work stealing, the repartition keeps the
    pool a partition (per-chunk: shard queues pairwise disjoint, queues
    plus consumed ids cover every id ever created), and the result still
    matches the single-device solve."""
    out = fake_devices(_STEAL_CODE)
    r = json.loads(out.split("RESULT ", 1)[1])
    assert r["steals"] >= 1, r
    ev = r["steal_events"][0]
    assert ev["n_moved"] >= 1 and ev["drained_shards"], ev
    assert r["partition_ok"], r
    assert r["status"] == r["ref_status"], r
    assert r["objective"] == r["ref_objective"], r


_LOSS_CODE = r"""
import json
from repro import solver
from repro.core import dist_solve
from repro.core import models as zoo
from repro.core.api import Solver
from repro.ft.fault_tolerance import DeviceLoss

m, _ = zoo.ZOO["coloring"].build_model(zoo.small_instance("coloring", 0))
cm = m.compile()
cfg0 = solver.SolveConfig.preset("prove", n_lanes=2, eps_target=16,
                                 chunk=2)
ref = Solver(cfg0.replace(mesh_shards=1)).solve(cm)
cfg = cfg0.replace(mesh_shards=4)
res, tr = dist_solve.solve_dist(cm, cfg, session=Solver(cfg),
                                fault=DeviceLoss(at_chunk=1, shard=1))
g = tr.gbest_per_chunk
print("RESULT " + json.dumps(dict(
    status=res.status, ref_status=ref.status,
    objective=res.objective, ref_objective=ref.objective,
    complete=res.complete, remesh=tr.remesh_events,
    monotone=all(a >= b for a, b in zip(g, g[1:])))))
"""


@pytest.mark.slow
def test_device_loss_remesh_same_optimum(fake_devices):
    """Losing a shard mid-solve (simulated via the ft heartbeat +
    injector) redistributes its unexplored pool slice over the surviving
    mesh and the solve still terminates with the same proven optimum."""
    out = fake_devices(_LOSS_CODE)
    r = json.loads(out.split("RESULT ", 1)[1])
    assert len(r["remesh"]) == 1, r
    ev = r["remesh"][0]
    assert ev["shards_before"] == 4 and ev["shards_after"] == 3, ev
    assert ev["n_requeued"] >= 1, ev
    assert r["status"] == "OPTIMAL" and r["complete"], r
    assert r["status"] == r["ref_status"], r
    assert r["objective"] == r["ref_objective"], r
    assert r["monotone"], r
