"""Distributed EPS: the lane pool sharded over a device mesh
(DESIGN.md §14).

The paper's EPS design is intrinsically multi-device: the pool of
consistent subproblems produced by `eps.decompose` partitions the root
search space, so shards of the pool can be explored by disjoint device
lane blocks with only two pieces of shared state — the global best
bound (a min, DESIGN.md §9) and the global done flag (an and).  This
module runs that regime on a 1-D ``solve`` mesh axis:

* **Sharding** — the `[S, V]` pool and the `[D·L, …]` lane state shard
  over ``solve`` with specs derived from `distributed/sharding.py`'s
  `SOLVE_RULES`; model tables and the scalar bound/flags replicate.
  Each device runs the existing four-phase superstep
  (`search.lanes_step`) on its shard, unchanged, under `shard_map`.
* **Bound sharing** — every superstep inside the sharded chunk ends
  with `distributed/collectives.solver_bound_sync` (pmin of the
  incumbent bound, AND of done, OR of has-solution), so all lanes on
  all devices prune against the best objective found anywhere; the host
  additionally folds the bound into its incumbent checkpoint once per
  chunk (the anytime stream).
* **Work stealing** — at host-chunk granularity: when a shard's
  frontier drains (some lane done, no undispatched entries) while work
  remains elsewhere, `distributed/planner.plan_steal` deterministically
  repartitions the undispatched pool ids (minimal movement, balanced to
  within one entry) and the drained shard's lanes are revived.
* **Elastic device loss** — a simulated loss (`ft.DeviceLoss`) is
  detected by the same Heartbeat/FailureInjector pair the training
  supervisor uses and recovered by `ft.solver_shard_loss`: everyone
  rolls back to the last chunk-boundary snapshot (the failed chunk's
  collective never completed), the lost shard's undispatched slice and
  the *root* stores of its in-flight subproblems are requeued, the
  survivors re-mesh over ``D-1`` devices via `ft.elastic_remesh`, and
  the solve continues to the same proven optimum.  The incumbent
  survives because the host checkpoints (objective, solution) every
  chunk — never the lost device's memory.

**Completeness** (§14): the pool partitions the root space (eps.py);
steals move only *undispatched* entries, so at every chunk boundary the
per-shard undispatched id sets are pairwise disjoint and, together with
the consumed ids, cover the pool — the invariant
`tests/test_dist_solve.py` asserts.  Device loss requeues a superset of
the lost shard's unexplored work (re-exploring part of a subtree only
repeats nodes), and the post-loss bound is recomputed from surviving
lanes plus the host checkpoint, never taken on faith from the failed
chunk.  Hence status/objective equal the single-device solve for every
mesh size and any single loss.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import search as S
from repro.core.api import (CompiledRunner, Improvement, Progress,
                            SolveConfig, SolveResult, _bucket, _chunk_runner,
                            _init_carry, derive_result, shape_signature)
from repro.core.compile import CompiledModel
from repro.distributed import planner
from repro.distributed.sharding import SOLVE_RULES, dist_solve_specs
from repro.ft.fault_tolerance import (DeviceLoss, elastic_remesh,
                                      solver_heartbeat, solver_shard_loss)

AXIS = "solve"


@dataclasses.dataclass
class DistTrace:
    """Host-side observability for one distributed solve — what the
    tests assert on and what `bench_solver --dist-bench` records."""
    n_chunks: int = 0
    n_bound_syncs: int = 0              # chunk-boundary host bound folds
    n_supersteps: int = 0               # per-superstep device all-reduces
    gbest_per_chunk: List[int] = dataclasses.field(default_factory=list)
    steal_events: List[dict] = dataclasses.field(default_factory=list)
    remesh_events: List[dict] = dataclasses.field(default_factory=list)
    # per chunk boundary: per-shard undispatched id lists + consumed ids
    assignments: List[List[List[int]]] = dataclasses.field(
        default_factory=list)
    consumed_per_chunk: List[List[int]] = dataclasses.field(
        default_factory=list)
    all_ids: List[int] = dataclasses.field(default_factory=list)

    @property
    def n_steals(self) -> int:
        return len(self.steal_events)


class _Pool:
    """Host bookkeeping for the sharded EPS pool.

    Identity lives in integer *ids* (rows of the original decomposition,
    plus fresh ids for roots requeued by device-loss recovery); layout
    (which contiguous device slice a row occupies) is recomputed on
    every steal/remesh while ids are stable — that is what makes the
    disjointness/cover invariant checkable.
    """

    def __init__(self, subs_lb: np.ndarray, subs_ub: np.ndarray,
                 n_shards: int, pad_bucket: bool):
        self.store: Dict[int, Tuple[np.ndarray, np.ndarray]] = {
            i: (subs_lb[i].copy(), subs_ub[i].copy())
            for i in range(subs_lb.shape[0])}
        self.next_id = subs_lb.shape[0]
        self.consumed: set = set()
        self.n_shards = n_shards
        self.pad_bucket = pad_bucket
        self.template = (subs_lb[0].copy(), subs_ub[0].copy())
        self.owned, _ = planner.plan_steal([sorted(self.store)], n_shards)
        self.shard_size = self._shard_size()
        self.heads = np.zeros(n_shards, np.int64)
        self._layout()

    def _shard_size(self) -> int:
        need = max(max((len(o) for o in self.owned), default=1), 1)
        return _bucket(need) if self.pad_bucket else need

    def _layout(self):
        """Materialize `owned` into contiguous per-shard slices, padding
        with explicitly-failed stores (popped and failed in one
        superstep — `eps.pad_pool` semantics)."""
        D, Ssh = self.n_shards, self.shard_size
        V = self.template[0].shape[0]
        lb = np.empty((D * Ssh, V), self.template[0].dtype)
        ub = np.empty((D * Ssh, V), self.template[1].dtype)
        ids = np.full(D * Ssh, -1, np.int64)
        pad_lb, pad_ub = self.template[0].copy(), self.template[1].copy()
        pad_lb[0], pad_ub[0] = 1, 0
        for d in range(D):
            for k in range(Ssh):
                row = d * Ssh + k
                if k < len(self.owned[d]):
                    i = self.owned[d][k]
                    lb[row], ub[row] = self.store[i]
                    ids[row] = i
                else:
                    lb[row], ub[row] = pad_lb, pad_ub
        self.lb, self.ub, self.ids = lb, ub, ids
        self.heads = np.zeros(D, np.int64)

    def advance(self, heads: np.ndarray):
        """Consume the entries dispatched to lanes since the last chunk
        boundary (everything below the new per-shard cursor)."""
        Ssh = self.shard_size
        for d in range(self.n_shards):
            lo, hi = int(self.heads[d]), min(int(heads[d]), Ssh)
            for pos in range(lo, hi):
                i = int(self.ids[d * Ssh + pos])
                if i >= 0:
                    self.consumed.add(i)
                    self.store.pop(i, None)
            self.heads[d] = hi
        self.owned = [
            [int(i) for i in self.ids[d * Ssh + int(self.heads[d]):
                                      (d + 1) * Ssh] if i >= 0]
            for d in range(self.n_shards)]

    def remaining(self) -> int:
        return sum(len(o) for o in self.owned)

    def steal(self) -> int:
        """Repartition the undispatched ids (planner.plan_steal) and
        re-layout.  Returns the number of entries that moved."""
        self.owned, moved = planner.plan_steal(self.owned, self.n_shards)
        self._layout()
        return moved

    def requeue(self, ids: List[int],
                roots: Tuple[np.ndarray, np.ndarray]) -> List[int]:
        """Device-loss recovery: `ids` come back verbatim (their rows
        are still in `store`); in-flight roots get fresh ids."""
        new_ids = list(ids)
        r_lb, r_ub = roots
        for k in range(r_lb.shape[0]):
            i = self.next_id
            self.next_id += 1
            self.store[i] = (r_lb[k].copy(), r_ub[k].copy())
            new_ids.append(i)
        return new_ids

    def remesh(self, owned: List[List[int]], extra: List[int]):
        """Shrink to ``len(owned)`` shards, folding ``extra`` (the lost
        shard's requeued work) into a balanced repartition."""
        self.n_shards = len(owned)
        self.owned, _ = planner.plan_steal(owned + [extra], self.n_shards)
        self.shard_size = self._shard_size()
        self._layout()

    def all_ids(self) -> List[int]:
        return sorted(self.consumed | set(self.store))


def _mesh_for(n_shards: int, devices=None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n_shards:
        raise RuntimeError(
            f"mesh_shards={n_shards} but only {len(devs)} JAX device(s) "
            f"are visible; on CPU-only hosts fake them with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} (set before the process starts)")
    return Mesh(np.asarray(devs[:n_shards]), (AXIS,))


def _build_runner(session, cm: CompiledModel, cfg: SolveConfig,
                  mesh: Mesh, state0, n_pool: int) -> CompiledRunner:
    """One sharded chunk runner per (model shape, config, mesh size),
    cached in the session's runner cache like every other runner."""
    session._decomposer_for(cm, cfg)
    n_dev = int(mesh.shape[AXIS])
    key = (shape_signature(cm), cfg.compile_key(), ("dist", n_dev))
    runner = session._runners.get(key)
    if runner is not None:
        session.stats["runner_hits"] += 1
        return runner
    opts = cfg.search_options()
    pool_spec, carry_spec = dist_solve_specs(state0, n_pool, mesh)
    cm_spec = jax.tree.map(lambda _: P(), cm)
    dev_fn = _chunk_runner(opts, cfg.stop_on_first, cfg.chunk, (AXIS,))
    fn = jax.jit(jax.shard_map(dev_fn, mesh=mesh,
                               in_specs=(cm_spec, pool_spec, pool_spec,
                                         carry_spec),
                               out_specs=carry_spec, check_vma=False))
    runner = CompiledRunner(fn, aot=False)
    session._runners[key] = runner
    session.stats["runner_builds"] += 1
    return runner


def _place_state(state, mesh: Mesh):
    """Re-place a host lane-state pytree (leaves ``[D·L, …]``) onto the
    mesh via the ft elastic-remesh path: shardings are recomputed from
    the logical SOLVE_RULES, device_put moves the bytes."""
    def shardings_fn(m):
        def leaf(x):
            from repro.distributed.sharding import spec_for
            axes = ("lanes",) + (None,) * (np.asarray(x).ndim - 1)
            return NamedSharding(m, spec_for(np.asarray(x).shape, axes,
                                             SOLVE_RULES, m))
        return jax.tree.map(leaf, state)
    return elastic_remesh(state, mesh, shardings_fn)


class _Incumbent:
    """The host-side incumbent checkpoint: streamed once per chunk, and
    the only thing that survives a device loss."""

    def __init__(self, cm: CompiledModel):
        self.cm = cm
        self.big = int(np.iinfo(cm.jdtype).max // 4)
        self.obj = self.big
        self.sol: Optional[np.ndarray] = None
        self.has_sol = False

    def fold(self, st) -> None:
        has = np.asarray(st.has_sol).reshape(-1)
        if not has.any():
            return
        if self.cm.obj_var >= 0:
            best = np.asarray(st.best_obj).reshape(-1)
            i = int(best.argmin())
            if int(best[i]) < self.obj or not self.has_sol:
                self.obj = int(best[i])
                self.sol = np.asarray(st.best_sol).reshape(
                    -1, self.cm.n_vars)[i].copy()
        elif not self.has_sol:
            i = int(has.argmax())
            self.sol = np.asarray(st.best_sol).reshape(
                -1, self.cm.n_vars)[i].copy()
        self.has_sol = True

    def rows(self, V: int):
        """One extra lane row carrying the checkpoint, appended to the
        terminal device state before derive_result."""
        sol = self.sol if self.sol is not None else np.zeros(V, np.int64)
        return (np.asarray([self.obj]), np.asarray([self.has_sol]),
                np.asarray(sol).reshape(1, V))


def solve_iter_dist(session, cm: CompiledModel, cfg: SolveConfig, *,
                    subs: Optional[tuple] = None,
                    fault: Optional[DeviceLoss] = None,
                    trace: Optional[DistTrace] = None
                    ) -> Iterator[Progress]:
    """Anytime distributed solve over ``cfg.mesh_shards`` devices;
    yields the same `Progress` stream as the single-device engine (one
    event per host chunk), final event carrying the `SolveResult`."""
    trace = trace if trace is not None else DistTrace()
    opts = cfg.search_options()
    t0 = time.time()
    D = int(cfg.mesh_shards or 1)
    mesh = _mesh_for(D)
    hb, injector = solver_heartbeat(D, fault)

    # -- pool ---------------------------------------------------------------
    if subs is None:
        subs_lb, subs_ub = session.decompose(cm, config=cfg)
    else:
        subs_lb, subs_ub = np.asarray(subs[0]), np.asarray(subs[1])
    pool = _Pool(np.asarray(subs_lb), np.asarray(subs_ub), D,
                 pad_bucket=cfg.pad_pool)
    trace.all_ids = pool.all_ids()

    # -- carry --------------------------------------------------------------
    carry = _init_carry(cm, cfg.n_lanes * D, opts, n_heads=D)
    runner = _build_runner(session, cm, cfg, mesh, carry[0],
                           pool.lb.shape[0])
    inc = _Incumbent(cm)
    improvements: List[Improvement] = []
    best_seen = inc.big
    lost_totals = dict(n_nodes=0, n_fails=0, n_sols=0, n_sweeps=0)
    snapshot: Optional[dict] = None
    chunk_idx = 0
    stop, exhausted = False, False

    def host_state(st):
        return jax.tree.map(lambda x: np.asarray(x), st)

    def boundary_snapshot(st_h):
        """Checkpoint for ft recovery: per-shard lane state, owned ids
        and in-flight subproblem roots (only kept when a fault is
        scheduled — real deployments would persist this instead)."""
        L = cfg.n_lanes
        Dn = pool.n_shards
        inflight = []
        for d in range(Dn):
            sl = slice(d * L, (d + 1) * L)
            mask = (~st_h.done[sl]) & (~st_h.fresh[sl])
            inflight.append((st_h.root_lb[sl][mask].copy(),
                             st_h.root_ub[sl][mask].copy()))
        state = jax.tree.map(lambda x: x.reshape((Dn, L) + x.shape[1:]),
                             st_h)
        return dict(state=state, owned=[list(o) for o in pool.owned],
                    inflight=inflight,
                    heads=pool.heads.copy())

    while not stop:
        # -- failure detection + elastic remesh (ft/) ----------------------
        hb.clock.t = float(chunk_idx)
        injector.advance(chunk_idx, hb)
        dead = hb.dead_hosts()
        if dead and pool.n_shards > 1 and snapshot is not None:
            lostd = int(dead[0].replace("shard", ""))
            rec = solver_shard_loss(snapshot, lostd)
            requeued = pool.requeue(rec["requeue_ids"],
                                    rec["requeue_roots"])
            # roll everyone back to the checkpoint: the failed chunk's
            # collective never completed on a real mesh
            st_prev = rec["state"]
            # the checkpoint (host memory) keeps the lost shard's search
            # *counters*; its incumbents need no special handling — the
            # host folded them into `inc` when the snapshot was taken
            lost_state = jax.tree.map(
                lambda x: np.asarray(x)[lostd], snapshot["state"])
            for k in lost_totals:
                lost_totals[k] += int(np.asarray(
                    getattr(lost_state, k)).sum())
            Dn = pool.n_shards - 1
            pool.remesh([list(o) for o in rec["owned"]], requeued)
            mesh = _mesh_for(Dn, devices=[
                d for i, d in enumerate(mesh.devices.reshape(-1))
                if i != lostd])
            st_h = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), st_prev)
            # revive drained survivor lanes so they pick up requeued work
            st_h = st_h._replace(done=np.zeros_like(st_h.done))
            state_dev = _place_state(st_h, mesh)
            # bound restart: the host incumbent checkpoint (whose
            # solution vector we hold) plus the survivors' own
            # incumbents — never the failed epoch's all-reduced value
            gbest = jnp.asarray(
                min(inc.obj, int(np.asarray(st_h.best_obj).min()))
                if cm.obj_var >= 0 else inc.big, cm.jdtype)
            # scalars re-materialize on the host: the old carry's arrays
            # are committed to the dead mesh and must not leak in
            it_h = jnp.asarray(int(np.asarray(carry[3])), jnp.int32)
            carry = (state_dev, gbest, jnp.asarray(False), it_h,
                     jnp.zeros((Dn,), jnp.int32))
            runner = _build_runner(session, cm, cfg, mesh, carry[0],
                                   pool.lb.shape[0])
            # fresh heartbeat AND injector: shards renumber after the
            # remesh, so the old failed-host name must not shadow a
            # survivor (the single scheduled loss is consumed)
            hb, injector = solver_heartbeat(Dn, None)
            trace.remesh_events.append(dict(
                chunk=chunk_idx, lost_shard=lostd,
                n_requeued=len(requeued), shards_before=Dn + 1,
                shards_after=Dn))
            snapshot = None

        # -- one sharded chunk ---------------------------------------------
        carry = jax.block_until_ready(
            runner(cm, jnp.asarray(pool.lb), jnp.asarray(pool.ub), carry))
        st, gbest, gdone, it, heads = carry
        chunk_idx += 1
        trace.n_chunks += 1
        trace.n_bound_syncs += 1
        st_h = host_state(st)
        pool.advance(np.asarray(heads).reshape(-1))
        inc.fold(st_h)
        superstep = int(np.asarray(it))
        trace.n_supersteps = superstep
        wall = time.time() - t0
        trace.gbest_per_chunk.append(inc.obj)
        trace.assignments.append([list(o) for o in pool.owned])
        trace.consumed_per_chunk.append(sorted(pool.consumed))
        if fault is not None:
            snapshot = boundary_snapshot(st_h)

        # -- anytime event --------------------------------------------------
        n_nodes = int(st_h.n_nodes.sum()) + lost_totals["n_nodes"]
        n_sols = int(st_h.n_sols.sum()) + lost_totals["n_sols"]
        has = bool(st_h.has_sol.any()) or inc.has_sol
        obj = None
        incumbent = None
        if cm.obj_var >= 0 and has:
            obj = inc.obj
            if obj < best_seen:
                best_seen = obj
                improvements.append(Improvement(superstep, wall, obj))
                incumbent = inc.sol

        # -- termination / stealing ----------------------------------------
        gdone_h = bool(np.asarray(gdone))
        if gdone_h:
            if cfg.stop_on_first and has:
                stop = True
            else:
                stop = pool.remaining() == 0
                exhausted = stop and bool(st_h.done.all())
        if not stop and cfg.steal and pool.n_shards > 1:
            L = cfg.n_lanes
            done_by_shard = st_h.done.reshape(pool.n_shards, L)
            drained = [d for d in range(pool.n_shards)
                       if done_by_shard[d].any()
                       and len(pool.owned[d]) == 0]
            if drained and pool.remaining() > 0:
                before = [len(o) for o in pool.owned]
                moved = pool.steal()
                st_h = st_h._replace(done=np.zeros_like(st_h.done))
                carry = (jax.tree.map(jnp.asarray, st_h), gbest,
                         jnp.asarray(False), it,
                         jnp.zeros((pool.n_shards,), jnp.int32))
                trace.steal_events.append(dict(
                    chunk=chunk_idx, drained_shards=drained,
                    n_moved=moved, owned_before=before,
                    owned_after=[len(o) for o in pool.owned]))
        if cfg.timeout_s is not None and wall > cfg.timeout_s:
            stop = True
        if (cfg.max_supersteps is not None
                and superstep >= cfg.max_supersteps):
            stop = True

        if not stop:
            yield Progress(superstep=superstep, best_objective=obj,
                           has_solution=has, incumbent=incumbent,
                           n_nodes=n_nodes, n_sols=n_sols, wall_s=wall,
                           t_host=t0 + wall)
            continue

        # -- terminal result ------------------------------------------------
        totals = S.lane_totals(st_h)
        for k, v in lost_totals.items():
            totals[k] += v
        xo, xh, xs = inc.rows(cm.n_vars)
        best_obj = np.concatenate([st_h.best_obj.reshape(-1), xo])
        has_sol = np.concatenate([st_h.has_sol.reshape(-1), xh])
        best_sol = np.concatenate(
            [np.asarray(st_h.best_sol).reshape(-1, cm.n_vars), xs])
        res = derive_result(
            cm, best_obj, has_sol, best_sol, st_h.incomplete,
            exhausted, totals["n_nodes"], totals["n_fails"],
            totals["n_sols"], totals["n_sweeps"], superstep,
            time.time() - t0, tuple(improvements),
            n_sweep_rounds=totals["n_sweep_rounds"],
            n_lanes=totals["n_lanes"])
        yield Progress(superstep=superstep, best_objective=res.objective,
                       has_solution=has, incumbent=res.solution,
                       n_nodes=res.n_nodes, n_sols=res.n_sols,
                       wall_s=res.wall_s, final=True, result=res,
                       t_host=t0 + res.wall_s)
        return


def solve_dist(cm: CompiledModel, config: Optional[SolveConfig] = None, *,
               subs: Optional[tuple] = None,
               fault: Optional[DeviceLoss] = None,
               session=None, **overrides
               ) -> Tuple[SolveResult, DistTrace]:
    """Blocking distributed solve; returns ``(result, trace)``.  The
    trace carries the per-chunk bound history, steal events, remesh
    events and pool-assignment snapshots (tests + dist bench)."""
    from repro.core.api import Solver
    cfg = (config or SolveConfig(mesh_shards=jax.device_count()))
    if overrides:
        cfg = cfg.replace(**overrides)
    if cfg.mesh_shards is None:
        cfg = cfg.replace(mesh_shards=jax.device_count())
    sess = session if session is not None else Solver(cfg)
    trace = DistTrace()
    res = None
    for ev in solve_iter_dist(sess, cm, cfg, subs=subs, fault=fault,
                              trace=trace):
        if ev.final:
            res = ev.result
    return res, trace
