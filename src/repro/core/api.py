"""Session-oriented public solver API (DESIGN.md §11) — ``repro.solver``.

The paper's TURBO solves one instance per launch; the ROADMAP north-star
is a serving system, which needs three things a blocking ten-kwarg
``engine.solve`` cannot give:

* **amortized compilation** — `Solver` is a session owning a
  compiled-runner cache keyed by ``(model shape signature, config)``, so
  repeated ``solver.solve(cm)`` calls on same-shape instances skip
  jit/lowering entirely (the warm path);
* **batched dispatch** — ``solver.solve_many([cm...])`` stacks N
  same-shape instances into ONE device dispatch (instances are a vmapped
  leading axis over the whole chunk runner: per-instance lane blocks,
  per-instance EPS pools, per-instance B&B bounds), the throughput
  scenario (instances/s);
* **anytime answers** — ``solver.solve_iter(cm)`` is a generator
  yielding `Progress` events after every host chunk (superstep, best
  bound, incumbent, node counters), so a timeout degrades to the best
  incumbent instead of nothing; `SolveResult.improvements` records the
  bound trace.

Configuration is one frozen `SolveConfig` dataclass with named presets
(``prove`` — the default full B&B proof profile, ``first_solution`` —
stop at the first solution, ``fast`` — capped fixpoint sweeps, §Perf
P0/H1), replacing the flag recipes previously duplicated across
`launch/solve.py`, `benchmarks/bench_solver.py` and the tests.

`engine.solve` remains as a thin deprecation shim over this module.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import (Any, Dict, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.compile import CompiledModel
from repro.core import eps
from repro.core import search as S

# terminal statuses (re-exported by repro.core.engine for back-compat)
OPTIMAL = "OPTIMAL"
SAT = "SAT"
UNSAT = "UNSAT"
UNKNOWN = "UNKNOWN"


class Improvement(NamedTuple):
    """One incumbent improvement in a solve's anytime trace."""
    superstep: int
    wall_s: float
    objective: int


@dataclasses.dataclass
class SolveResult:
    status: str
    objective: Optional[int]
    solution: Optional[np.ndarray]
    n_nodes: int
    n_fails: int
    n_sols: int
    n_sweeps: int
    n_supersteps: int
    wall_s: float
    complete: bool
    # anytime trace: every (superstep, wall_s, objective) at which the
    # global incumbent improved, observed at scheduler-quantum
    # granularity (DESIGN.md §11): per host chunk for unfused backends,
    # per K-superstep launch for pallas_resident — improvements landing
    # within one quantum collapse into a single trace entry whose
    # `superstep` is the quantum's end.
    improvements: Tuple[Improvement, ...] = ()
    # lockstep cost of the sweeps: the superstep fixpoints' loop rounds
    # summed (the slowest lane's sweeps each superstep), over the lanes
    # searched; 1 - n_sweeps / (n_lanes * n_sweep_rounds) is the share of
    # lane-sweeps spent on lanes that had already converged
    n_sweep_rounds: int = 0
    n_lanes: int = 0
    # optional Cumulative members (DESIGN.md §18), summed over lanes:
    # branching decisions on a presence literal, and presence literals
    # fixed by a superstep fixpoint (0 on models without them)
    n_presence_branched: int = 0
    n_presence_propagated: int = 0
    # host phases of a `Solver.solve_iter` solve (None where not timed):
    # seconds preparing the pool (EPS decomposition, padding, transfer);
    # the decomposition's device calls (the root's fixpoint and the split
    # loop), splits and the lockstep sweep rounds of its pair fixpoints
    # (0 for a given pool); seconds in chunk-runner calls, each until its
    # result is ready
    decompose_s: Optional[float] = None
    n_decompose_dispatches: Optional[int] = None
    n_decompose_splits: Optional[int] = None
    n_decompose_sweep_rounds: Optional[int] = None
    search_s: Optional[float] = None

    @property
    def nodes_per_sec(self) -> float:
        return self.n_nodes / max(self.wall_s, 1e-9)


@dataclasses.dataclass
class Progress:
    """One anytime event from `Solver.solve_iter`, emitted per scheduler
    quantum — i.e. once per `_run_chunk` return to the host: every
    ``chunk`` supersteps for the unfused backends, every
    ``supersteps_per_launch`` (K) supersteps for ``pallas_resident``
    (whose megakernel only re-enters the host per launch, DESIGN.md
    §13).  Anytime consumers should key off ``superstep``/``wall_s``,
    not event counts.

    The last event has ``final=True`` and carries the terminal
    `SolveResult` in ``result``; earlier events report the running
    incumbent (``best_objective`` is None for satisfaction models or
    while no solution exists yet).

    Timing contract (the ONE timing source, shared by the serving
    metrics and the superstep bench): ``t_host`` is the absolute host
    wall clock (``time.time()``) at event emission, ``wall_s`` is the
    elapsed time since the solve started (so ``t_host - wall_s`` is the
    solve's start stamp), and ``superstep`` is the cumulative superstep
    counter — downstream consumers must not re-time chunks themselves.
    """
    superstep: int
    best_objective: Optional[int]
    has_solution: bool
    incumbent: Optional[np.ndarray]
    n_nodes: int
    n_sols: int
    wall_s: float
    final: bool = False
    result: Optional[SolveResult] = None
    t_host: float = 0.0


# --------------------------------------------------------------------------
# SolveConfig: one frozen config object + named presets
# --------------------------------------------------------------------------

_VAR_STRATEGIES = (S.INPUT_ORDER, S.MIN_DOM, S.MIN_LB)
_VAL_STRATEGIES = (S.VAL_MIN, S.VAL_SPLIT, S.VAL_MIDDLE_OUT)

# named flag recipes (DESIGN.md §11). `prove` is the proof profile used
# by every benchmark table; `fast` is the §Perf P0/H1 capped-sweep
# profile (identical optima, bounded chaotic iteration); `first_solution`
# is the satisfaction/anytime profile.
PRESETS: Dict[str, Dict[str, Any]] = {
    "prove": dict(var_strategy=S.MIN_LB, max_depth=1024),
    "first_solution": dict(var_strategy=S.MIN_LB, max_depth=1024,
                           stop_on_first=True),
    "fast": dict(var_strategy=S.MIN_LB, max_depth=1024,
                 max_fixpoint_iters=4),
}


@dataclasses.dataclass(frozen=True)
class SolveConfig:
    """Everything `Solver` needs besides the model itself.

    Consolidates the former ``engine.solve`` kwarg sprawl; validated on
    construction, hashable (it is half of the session cache key), and
    buildable from a named preset: ``SolveConfig.preset("fast",
    backend="pallas", n_lanes=128)``.
    """

    # lanes / EPS decomposition (DESIGN.md §9)
    n_lanes: int = 64
    eps_target: Optional[int] = None          # None → 4 * n_lanes
    # host chunking / budgets
    chunk: int = 256
    timeout_s: Optional[float] = None
    max_supersteps: Optional[int] = None
    # propagation backend (core/backend.py)
    backend: str = "gather"
    backend_opts: Tuple[Tuple[str, Any], ...] = ()
    # pallas_resident only: supersteps fused per megakernel launch (K in
    # DESIGN.md §13); merged into backend_opts, so it is part of the
    # compile key.  None → the backend default (16).
    supersteps_per_launch: Optional[int] = None
    # search strategy (core/search.py)
    var_strategy: str = S.INPUT_ORDER
    val_strategy: str = S.VAL_MIN
    max_depth: int = 2048
    max_fixpoint_iters: Optional[int] = None
    stop_on_first: bool = False
    # multi-device engine (explicit-mesh legacy path)
    mesh: Optional[jax.sharding.Mesh] = None
    lane_axes: Tuple[str, ...] = ()
    # distributed EPS engine (core/dist_solve.py, DESIGN.md §14): shard
    # the lane pool over a 1-D `solve` mesh of this many devices, with
    # per-superstep bound all-reduce, chunk-granularity work stealing
    # (`steal`) and elastic device-loss recovery.  None → single-device;
    # the CLI spelling is `launch/solve.py --mesh N`.
    mesh_shards: Optional[int] = None
    steal: bool = True
    # pad EPS pools to the next power of two with explicitly-failed
    # stores so the compiled runner re-lowers per size *bucket*, not per
    # exact pool size (DESIGN.md §11 cache-key discussion)
    pad_pool: bool = True
    # provenance tag only — excluded from equality/hash so a preset and
    # its hand-rolled equivalent share one cache entry
    preset_name: Optional[str] = dataclasses.field(default=None,
                                                   compare=False)

    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"SolveConfig: {msg}")

        if isinstance(self.backend_opts, dict):
            object.__setattr__(self, "backend_opts",
                               tuple(sorted(self.backend_opts.items())))
        else:
            object.__setattr__(self, "backend_opts",
                               tuple(tuple(kv) for kv in self.backend_opts))
        object.__setattr__(self, "lane_axes", tuple(self.lane_axes))

        for name in ("n_lanes", "chunk", "max_depth"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                bad(f"{name} must be a positive int, got {v!r}")
        for name in ("eps_target", "max_supersteps", "max_fixpoint_iters",
                     "supersteps_per_launch"):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, int) or v < 1):
                bad(f"{name} must be None or a positive int, got {v!r}")
        if self.supersteps_per_launch is not None:
            if self.backend != "pallas_resident":
                bad("supersteps_per_launch is only meaningful with "
                    "backend='pallas_resident'")
            opts = dict(self.backend_opts)
            opts.setdefault("supersteps_per_launch",
                            self.supersteps_per_launch)
            object.__setattr__(self, "backend_opts",
                               tuple(sorted(opts.items())))
        if self.timeout_s is not None and not self.timeout_s > 0:
            bad(f"timeout_s must be None or > 0, got {self.timeout_s!r}")

        from repro.core.backend import available_backends
        if self.backend not in available_backends():
            bad(f"unknown backend {self.backend!r}; "
                f"available: {', '.join(available_backends())}")
        for kv in self.backend_opts:
            if len(kv) != 2 or not isinstance(kv[0], str):
                bad(f"backend_opts must be (name, value) pairs, got "
                    f"{self.backend_opts!r}")
        if self.var_strategy not in _VAR_STRATEGIES:
            bad(f"var_strategy {self.var_strategy!r} not in "
                f"{_VAR_STRATEGIES}")
        if self.val_strategy not in _VAL_STRATEGIES:
            bad(f"val_strategy {self.val_strategy!r} not in "
                f"{_VAL_STRATEGIES}")
        if self.mesh_shards is not None:
            if not isinstance(self.mesh_shards, int) or self.mesh_shards < 1:
                bad(f"mesh_shards must be None or a positive int, got "
                    f"{self.mesh_shards!r}")
            if self.mesh is not None:
                bad("mesh_shards (the dist_solve engine) and mesh (the "
                    "explicit-mesh path) are mutually exclusive")
        if ((self.mesh is not None or self.mesh_shards is not None)
                and self.backend == "pallas_resident"):
            bad("backend 'pallas_resident' does not support mesh "
                "sharding: the EPS pool cursor is per-device VMEM state "
                "inside the megakernel (use backend='pallas' on meshes)")
        if self.lane_axes and self.mesh is None:
            bad("lane_axes given without a mesh")
        if self.mesh is not None:
            if not self.lane_axes:
                bad("mesh given without lane_axes (which mesh axes shard "
                    "the lanes?)")
            missing = [a for a in self.lane_axes
                       if a not in self.mesh.axis_names]
            if missing:
                bad(f"lane_axes {missing} not in mesh axes "
                    f"{tuple(self.mesh.axis_names)}")

    @classmethod
    def preset(cls, name: str, **overrides) -> "SolveConfig":
        """Build a named preset (``prove`` | ``first_solution`` |
        ``fast``), optionally overriding any field."""
        try:
            base = PRESETS[name]
        except KeyError:
            raise ValueError(
                f"unknown preset {name!r}; available: "
                f"{', '.join(sorted(PRESETS))}") from None
        kw = dict(base)
        kw.update(overrides)
        kw.setdefault("preset_name", name)
        return cls(**kw)

    def replace(self, **overrides) -> "SolveConfig":
        if "preset_name" not in overrides:
            overrides["preset_name"] = None if overrides else self.preset_name
        return dataclasses.replace(self, **overrides)

    def search_options(self) -> S.SearchOptions:
        return S.SearchOptions(
            var_strategy=self.var_strategy, val_strategy=self.val_strategy,
            max_depth=self.max_depth,
            max_fixpoint_iters=self.max_fixpoint_iters,
            stop_on_first=self.stop_on_first, backend=self.backend,
            backend_opts=self.backend_opts)

    def resolved_eps_target(self) -> int:
        return (self.eps_target if self.eps_target is not None
                else 4 * self.n_lanes)

    def compile_key(self) -> tuple:
        """The config half of the session cache key: exactly the fields
        that shape the traced/compiled chunk runner.  Budget fields
        (timeout_s, max_supersteps) and eps_target are host-side only —
        two configs differing only there share one compiled runner."""
        return (self.n_lanes, self.chunk, self.backend, self.backend_opts,
                self.supersteps_per_launch,
                self.var_strategy, self.val_strategy, self.max_depth,
                self.max_fixpoint_iters, self.stop_on_first, self.mesh,
                self.lane_axes, self.mesh_shards)


def shape_signature(cm: CompiledModel) -> tuple:
    """The model half of the session cache key: every static field and
    array shape of the compiled tables that participates in tracing
    (incl. the branch-var count).  Two instances with equal signatures
    (e.g. zoo generator outputs across seeds) reuse one compiled
    runner; the table *contents* are runtime arguments."""
    return (cm.n_vars, cm.n_props, cm.k_terms, cm.d_occ,
            cm.n_alldiff, cm.ad_width, cm.ad_docc,
            cm.n_cumulative, cm.cu_width, cm.cu_docc, cm.horizon,
            cm.ad_layout, cm.ad_packed, cm.cu_layout, cm.cu_optional,
            # §17 extensional bank layout + bitset word count: mixed
            # table/bounds models (and different table geometries) must
            # never collide in the compiled-runner cache
            cm.n_table, cm.ct_arity, cm.ct_words, cm.ct_docc, cm.n_words,
            int(cm.branch_vars.shape[0]), cm.obj_var, cm.dtype)


def _canonical(cm: CompiledModel) -> CompiledModel:
    """Blank the (static) model name so same-shape instances share one
    jit trace — the name is display metadata, never computed on."""
    return cm if cm.name == "" else dataclasses.replace(cm, name="")


def _bucket(n: int) -> int:
    """Pool-size padding bucket: next power of two ≥ n up to 1024, then
    the next multiple of 1024.  Uncapped pow2 growth would let a
    large-instance ``eps_target`` silently allocate a pool of padded
    (explicitly failed, but still swept-over) stores up to ~2× the
    request; the 1024-step cap bounds the overhead to < 1024 lanes while
    keeping the bucket count — and thus the number of cached runner
    traces — small (DESIGN.md §16)."""
    if n <= 1:
        return 1
    if n <= 1024:
        return 1 << (n - 1).bit_length()
    return ((n + 1023) // 1024) * 1024


# --------------------------------------------------------------------------
# The jitted chunk runner (moved here from engine.py; engine re-exports)
# --------------------------------------------------------------------------

def _chunk_body(opts: S.SearchOptions, stop_on_first: bool, axis_names,
                cm: CompiledModel, subs_lb, subs_ub, carry):
    st, gbest, gdone, it, pool_head = carry
    st, new_head = S.lanes_step(cm, subs_lb, subs_ub, opts, st, gbest,
                                pool_head[0])
    pool_head = new_head[None].astype(jnp.int32)
    best = jnp.min(st.best_obj)
    done = jnp.all(st.done)
    any_sol = jnp.any(st.has_sol)
    if axis_names:
        from repro.distributed.collectives import solver_bound_sync
        best, done, any_sol = solver_bound_sync(best, done, any_sol,
                                                axis_names)
    gbest = jnp.minimum(gbest, best)
    # guard the counter on the *incoming* done flag: inside the plain
    # while_loop the body never runs once done (no-op guard), but under
    # solve_many's instance-vmap finished instances keep executing the
    # batched body — their superstep count must freeze
    it = it + jnp.where(gdone, 0, 1).astype(jnp.int32)
    gdone = gdone | done
    if stop_on_first:
        gdone = gdone | any_sol
    return st, gbest, gdone, it, pool_head


def _chunk_runner(opts: S.SearchOptions, stop_on_first: bool, chunk: int,
                  axis_names):
    """`_run_chunk` bound to its statics, named so its executable reads
    ``jit_run_chunk`` in a device trace (a bare partial reads
    ``jit__unknown``)."""
    fn = partial(_run_chunk, opts, stop_on_first, chunk, axis_names)
    fn.__name__ = "run_chunk"
    return fn


def _run_chunk(opts: S.SearchOptions, stop_on_first: bool, chunk: int,
               axis_names, cm: CompiledModel, subs_lb, subs_ub, carry):
    """One scheduler quantum — the unit of jit compilation and of host
    control (timeouts, anytime progress events).

    * unfused backends: a `while_loop` of up to `chunk` supersteps, each
      one `lanes_step` (four XLA dispatches per superstep);
    * ``pallas_resident``: ONE megakernel launch covering K =
      ``supersteps_per_launch`` supersteps (DESIGN.md §13) — `chunk` is
      not consulted; the kernel derives the global-done flag from state
      each fused superstep and runs identity steps once stopped, so the
      launch is idempotent and safe to re-issue (solve_many's vmap
      relies on this to freeze finished instances).
    """
    if opts.backend == "pallas_resident":
        from repro.core.backend import get_backend
        be = get_backend(opts.backend, **dict(opts.backend_opts))
        st, gbest, gdone, it, pool_head = carry
        st, gbest, it, pool_head, stopped = be.superstep_launch(
            cm, subs_lb, subs_ub, st, gbest, it, pool_head, opts=opts)
        return st, gbest, gdone | stopped, it, pool_head

    it0 = carry[3]

    def body(c):
        return _chunk_body(opts, stop_on_first, axis_names, cm,
                           subs_lb, subs_ub, c)

    def cond(c):
        return (~c[2]) & (c[3] - it0 < chunk)

    return lax.while_loop(cond, body, carry)


def _carry_heads(cfg: "SolveConfig", cm: CompiledModel,
                 pool_size: int) -> int:
    """Pool-cursor slots in the carry: one per resident-megakernel grid
    cell (`PallasResidentBackend.n_tiles`, usually 1), one otherwise.
    Mesh configs size per-device heads separately (see solve_iter)."""
    if cfg.backend != "pallas_resident":
        return 1
    from repro.core.backend import get_backend
    be = get_backend(cfg.backend, **dict(cfg.backend_opts))
    return be.n_tiles(cm, cfg.n_lanes, max_depth=cfg.max_depth,
                      pool_size=pool_size)


def _init_carry(cm: CompiledModel, n_lanes: int, opts: S.SearchOptions,
                n_heads: int = 1):
    dt = cm.jdtype
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)
    state0 = S.init_lanes(cm, n_lanes, opts)
    return (state0, big, jnp.asarray(False), jnp.asarray(0, jnp.int32),
            jnp.zeros((n_heads,), jnp.int32))


# --------------------------------------------------------------------------
# Status derivation — the ONE place a terminal SolveResult is assembled
# (fixes the dead/duplicated logic that lived in engine.solve)
# --------------------------------------------------------------------------

def derive_result(cm: CompiledModel, best_obj, has_sol, best_sol,
                  incomplete, done: bool, n_nodes: int, n_fails: int,
                  n_sols: int, n_sweeps: int, n_supersteps: int,
                  wall_s: float,
                  improvements: Tuple[Improvement, ...] = (), *,
                  n_sweep_rounds: int = 0, n_lanes: int = 0,
                  n_presence_branched: int = 0,
                  n_presence_propagated: int = 0) -> SolveResult:
    """Derive (status, objective, solution) from terminal lane state.

    ``done`` must mean *search exhausted* — every lane drained the pool
    (``st.done.all()``) — NOT merely "the solve loop stopped": a
    ``stop_on_first`` early-out or a budget/timeout is not an
    exhaustiveness proof and must never yield OPTIMAL/UNSAT.

    * optimization (``cm.obj_var >= 0``): the incumbent lane is
      ``best_obj.argmin()``; OPTIMAL iff the search completed, else SAT;
    * satisfaction: the incumbent lane is ``has_sol.argmax()`` — NOT the
      objective argmin, whose all-big tie would always pick lane 0 and
      read a zeroed ``best_sol`` row — and the status is SAT;
    * no solution anywhere: UNSAT iff complete, else UNKNOWN.
    """
    best_obj = np.asarray(best_obj).reshape(-1)
    has_sol = np.asarray(has_sol).reshape(-1)
    best_sol = np.asarray(best_sol).reshape(-1, cm.n_vars)
    complete = bool(done) and not bool(np.asarray(incomplete).any())

    if has_sol.any():
        if cm.obj_var >= 0:
            i = int(best_obj.argmin())
            obj = int(best_obj[i])
            status = OPTIMAL if complete else SAT
        else:
            i = int(has_sol.argmax())
            obj = None
            status = SAT
        sol = best_sol[i]
    else:
        sol, obj = None, None
        status = UNSAT if complete else UNKNOWN

    return SolveResult(status=status, objective=obj, solution=sol,
                       n_nodes=int(n_nodes), n_fails=int(n_fails),
                       n_sols=int(n_sols), n_sweeps=int(n_sweeps),
                       n_supersteps=int(n_supersteps), wall_s=wall_s,
                       complete=complete,
                       improvements=tuple(improvements),
                       n_sweep_rounds=int(n_sweep_rounds),
                       n_lanes=int(n_lanes),
                       n_presence_branched=int(n_presence_branched),
                       n_presence_propagated=int(n_presence_propagated))


LANE_COUNTERS = ("n_sweep_rounds", "n_lanes", "n_presence_branched",
                 "n_presence_propagated")


def lane_counters(totals: dict) -> dict:
    """The `search.lane_totals` entries `derive_result` takes by
    keyword."""
    return {k: totals[k] for k in LANE_COUNTERS}


# --------------------------------------------------------------------------
# Compiled-runner cache
# --------------------------------------------------------------------------

def _aval_key(args) -> tuple:
    leaves, treedef = jax.tree.flatten(args)
    from jax.api_util import shaped_abstractify
    return (treedef, tuple(shaped_abstractify(x) for x in leaves))


class CompiledProgram:
    """A jitted function plus its AOT-compiled executables keyed by
    argument avals.

    Compilation is explicit (`fn.lower(...).compile()`) so the session
    can *count* compiles and *time* them — `n_compiles` staying flat
    across a second solve is the warm-path proof the tests assert on.
    The EPS split loop (`Solver._decomposer_for`) is one; the chunk
    runners are the `CompiledRunner` subclass, so a wrapper of runner
    calls sees search only.
    """

    def __init__(self, fn):
        self.fn = fn
        self._execs: Dict[tuple, Any] = {}
        self.n_compiles = 0
        self.n_calls = 0
        self.compile_s = 0.0

    def compile(self, *args):
        """The executable for ``args``' avals, lowered and compiled on
        first sight."""
        key = _aval_key(args)
        exe = self._execs.get(key)
        if exe is None:
            t0 = time.time()
            exe = self.fn.lower(*args).compile()
            self.compile_s += time.time() - t0
            self.n_compiles += 1
            self._execs[key] = exe
        return exe

    def __call__(self, *args):
        self.n_calls += 1
        return self.compile(*args)(*args)


class CompiledRunner(CompiledProgram):
    """One cache slot: a jitted chunk runner (pool-size buckets land in
    its executables).  `placement` records where the last call's first
    output leaf (the lane stores) lives: ``(device, shard shape)`` per
    addressable shard.
    """

    def __init__(self, fn, aot: bool = True):
        super().__init__(fn)
        self.aot = aot
        self.placement: Tuple[Tuple[str, tuple], ...] = ()

    def __call__(self, *args):
        if self.aot:
            out = super().__call__(*args)
        else:   # mesh path: plain jit (counters track builds)
            self.n_calls += 1
            out = self.fn(*args)
        self.placement = tuple(
            (str(s.device), tuple(s.data.shape))
            for s in jax.tree.leaves(out)[0].addressable_shards)
        return out


class Solver:
    """A solving session: one `SolveConfig` (overridable per call) plus a
    compiled-runner cache keyed by ``(shape_signature(cm),
    config.compile_key(), batched?)``.

    Construct once, solve many::

        solver = Solver(SolveConfig.preset("prove", backend="pallas"))
        res = solver.solve(cm)              # cold: lower + compile
        res2 = solver.solve(cm2)            # warm: same shapes, no compile
        many = solver.solve_many(cms)       # one batched device dispatch
        for ev in solver.solve_iter(cm):    # anytime incumbent stream
            ...
    """

    def __init__(self, config: Optional[SolveConfig] = None, **overrides):
        base = config if config is not None else SolveConfig.preset("prove")
        self.config = base.replace(**overrides) if overrides else base
        self._runners: Dict[tuple, CompiledRunner] = {}
        self._decomposers: Dict[tuple, CompiledProgram] = {}
        self.stats: Dict[str, Any] = {
            "solves": 0, "runner_builds": 0, "runner_hits": 0,
            "last_solve_cold": None,
        }

    # -- cache ------------------------------------------------------------

    def _config_for(self, config: Optional[SolveConfig],
                    overrides: dict) -> SolveConfig:
        cfg = config if config is not None else self.config
        return cfg.replace(**overrides) if overrides else cfg

    def _runner_for(self, cm: CompiledModel, cfg: SolveConfig,
                    batched: bool) -> CompiledRunner:
        self._decomposer_for(cm, cfg)
        key = (shape_signature(cm), cfg.compile_key(), batched)
        runner = self._runners.get(key)
        if runner is not None:
            self.stats["runner_hits"] += 1
            return runner
        opts = cfg.search_options()
        if cfg.mesh is not None:
            axes = cfg.lane_axes
            dev_fn = _chunk_runner(opts, cfg.stop_on_first, cfg.chunk, axes)
            spec = P(axes)
            state0 = S.init_lanes(cm, cfg.n_lanes * self._n_dev(cfg), opts)
            state_spec = jax.tree.map(lambda _: spec, state0)
            carry_spec = (state_spec, P(), P(), P(), spec)
            cm_spec = jax.tree.map(lambda _: P(), cm)
            fn = jax.jit(jax.shard_map(
                dev_fn, mesh=cfg.mesh,
                in_specs=(cm_spec, spec, spec, carry_spec),
                out_specs=carry_spec, check_vma=False))
            runner = CompiledRunner(fn, aot=False)
        else:
            fn = _chunk_runner(opts, cfg.stop_on_first, cfg.chunk, ())
            if batched:
                fn = jax.vmap(fn)
            runner = CompiledRunner(jax.jit(fn), aot=True)
        self._runners[key] = runner
        self.stats["runner_builds"] += 1
        return runner

    def _decomposer_for(self, cm: CompiledModel,
                        cfg: SolveConfig) -> CompiledProgram:
        """The compiled EPS split loop (`eps.split_program`) for ``cm``'s
        shapes and the config's target and branching rule.  Every chunk
        runner lookup (`_runner_for`) builds it, so a solve given its
        pool leaves the decomposition of that shape compiled too."""
        target = cfg.resolved_eps_target()
        key = (shape_signature(cm), target, cfg.var_strategy,
               cfg.val_strategy)
        prog = self._decomposers.get(key)
        if prog is None:
            prog = CompiledProgram(jax.jit(eps.split_program(
                target, cfg.var_strategy, cfg.val_strategy)))
            prog.compile(cm, cm.lb0, cm.ub0)
            self._decomposers[key] = prog
        return prog

    def decompose(self, cm: CompiledModel, *,
                  config: Optional[SolveConfig] = None,
                  stats: Optional[dict] = None):
        """``cm``'s EPS pool (`eps.decompose`) under the config's target
        and branching rule, on the session's compiled split loop."""
        cfg = config if config is not None else self.config
        cm = _canonical(cm)
        return eps.decompose(cm, cfg.resolved_eps_target(),
                             cfg.search_options(), stats,
                             program=self._decomposer_for(cm, cfg))

    @staticmethod
    def _n_dev(cfg: SolveConfig) -> int:
        return int(np.prod([cfg.mesh.shape[a] for a in cfg.lane_axes]))

    def session_stats(self) -> Dict[str, Any]:
        """Aggregate cache/compile counters across all cached runners
        and decomposition programs."""
        progs = [*self._runners.values(), *self._decomposers.values()]
        out = dict(self.stats)
        out["n_runners"] = len(self._runners)
        out["n_decomposers"] = len(self._decomposers)
        out["n_compiles"] = sum(r.n_compiles for r in progs)
        out["compile_s"] = sum(r.compile_s for r in progs)
        out["placement"] = [r.placement for r in self._runners.values()]
        return out

    def clear_cache(self) -> None:
        """Drop every cached runner, decomposition program and compiled
        executable.  The cache is otherwise unbounded (one executable
        per shape-signature × compile-key × pool-bucket) — long-lived
        serving processes that churn through many distinct model shapes
        should evict periodically; counters are kept."""
        self._runners.clear()
        self._decomposers.clear()

    # -- pool preparation -------------------------------------------------

    def _pool_for(self, cm: CompiledModel, cfg: SolveConfig,
                  subs: Optional[tuple], stats: Optional[dict] = None):
        """The padded pool on the device.  ``stats`` is handed to
        `eps.decompose` when the pool is decomposed here."""
        with obs.span("repro.solve.pool"):
            if subs is None:
                subs_lb, subs_ub = self.decompose(cm, config=cfg,
                                                  stats=stats)
            else:
                subs_lb, subs_ub = subs
            subs_lb, subs_ub = np.asarray(subs_lb), np.asarray(subs_ub)
            size = subs_lb.shape[0]
            if cfg.pad_pool:
                size = _bucket(size)
            if cfg.mesh is not None:
                n_dev = self._n_dev(cfg)
                size = size + (-size) % n_dev
            subs_lb, subs_ub = eps.pad_pool(subs_lb, subs_ub, size)
            return jnp.asarray(subs_lb), jnp.asarray(subs_ub)

    # -- solve / solve_iter ----------------------------------------------

    def solve(self, cm: CompiledModel, *, subs: Optional[tuple] = None,
              config: Optional[SolveConfig] = None,
              **overrides) -> SolveResult:
        """Blocking solve; equals the last `solve_iter` event's result."""
        res = None
        for ev in self.solve_iter(cm, subs=subs, config=config, **overrides):
            if ev.final:
                res = ev.result
        return res

    def solve_iter(self, cm: CompiledModel, *,
                   subs: Optional[tuple] = None,
                   config: Optional[SolveConfig] = None,
                   **overrides) -> Iterator[Progress]:
        """Anytime solve: yields a `Progress` event after every
        scheduler quantum (host chunk; one K-superstep megakernel launch
        under ``backend="pallas_resident"``); the final event
        (``final=True``) carries the `SolveResult` (with its
        `improvements` trace)."""
        cfg = self._config_for(config, overrides)
        with obs.solve():
            if cfg.mesh_shards is not None:
                from repro.core import dist_solve
                self.stats["solves"] += 1
                yield from dist_solve.solve_iter_dist(
                    self, _canonical(cm), cfg, subs=subs)
            else:
                yield from self._solve_iter(cm, cfg, subs)

    def _solve_iter(self, cm: CompiledModel, cfg: SolveConfig,
                    subs: Optional[tuple]) -> Iterator[Progress]:
        opts = cfg.search_options()
        t0 = time.time()
        self.stats["solves"] += 1
        cm = _canonical(cm)
        pool_stats: Dict[str, int] = {}
        t_pool = time.perf_counter()
        subs_lb, subs_ub = self._pool_for(cm, cfg, subs, pool_stats)
        decompose_s = time.perf_counter() - t_pool

        builds0 = self.stats["runner_builds"]
        runner = self._runner_for(cm, cfg, batched=False)
        if cfg.mesh is not None:
            n_dev = self._n_dev(cfg)
            carry = _init_carry(cm, cfg.n_lanes * n_dev, opts,
                                n_heads=n_dev)
        else:
            carry = _init_carry(
                cm, cfg.n_lanes, opts,
                n_heads=_carry_heads(cfg, cm, int(subs_lb.shape[0])))
        compiles0 = runner.n_compiles
        self.stats["last_solve_cold"] = None  # set after first chunk

        improvements: List[Improvement] = []
        dt = cm.jdtype
        big = int(np.iinfo(dt).max // 4)
        best_seen = big
        search_s = 0.0
        while True:
            t_chunk = time.perf_counter()
            with obs.span("repro.solve.chunk"):
                carry = jax.block_until_ready(runner(cm, subs_lb, subs_ub,
                                                     carry))
            search_s += time.perf_counter() - t_chunk
            if self.stats["last_solve_cold"] is None:
                self.stats["last_solve_cold"] = (
                    runner.n_compiles > compiles0
                    or self.stats["runner_builds"] > builds0)
            with obs.span("repro.solve.poll"):
                st, gbest, gdone, it, _ = carry
                wall = time.time() - t0
                superstep = int(np.asarray(it).max())
                n_nodes = int(np.asarray(st.n_nodes).sum())
                n_sols = int(np.asarray(st.n_sols).sum())
                has = bool(np.asarray(st.has_sol).any())
                obj = None
                incumbent = None
                if cm.obj_var >= 0 and has:
                    flat = np.asarray(st.best_obj).reshape(-1)
                    i = int(flat.argmin())
                    obj = int(flat[i])
                    if obj < best_seen:
                        best_seen = obj
                        improvements.append(Improvement(superstep, wall, obj))
                        incumbent = np.asarray(st.best_sol).reshape(
                            -1, cm.n_vars)[i]
                stop = bool(np.asarray(gdone).all())
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
            with obs.span("repro.solve.poll"):
                totals = S.lane_totals(st)
                # exhaustion, not gdone: a stop_on_first early-out sets
                # gdone without draining the pool and must not claim
                # OPTIMAL/UNSAT
                exhausted = bool(np.asarray(st.done).all())
                res = derive_result(
                    cm, st.best_obj, st.has_sol, st.best_sol, st.incomplete,
                    exhausted, totals["n_nodes"],
                    totals["n_fails"], totals["n_sols"], totals["n_sweeps"],
                    superstep, time.time() - t0, tuple(improvements),
                    **lane_counters(totals))
                res = dataclasses.replace(
                    res, decompose_s=decompose_s, search_s=search_s,
                    n_decompose_dispatches=pool_stats.get("dispatches", 0),
                    n_decompose_splits=pool_stats.get("splits", 0),
                    n_decompose_sweep_rounds=pool_stats.get(
                        "sweep_rounds", 0))
            yield Progress(superstep=superstep, best_objective=res.objective,
                           has_solution=has, incumbent=res.solution,
                           n_nodes=res.n_nodes, n_sols=res.n_sols,
                           wall_s=res.wall_s, final=True, result=res,
                           t_host=t0 + res.wall_s)
            return

    # -- solve_many -------------------------------------------------------

    def solve_many(self, cms: Sequence[CompiledModel], *,
                   config: Optional[SolveConfig] = None,
                   **overrides) -> List[SolveResult]:
        """Solve N same-shape instances in ONE batched device dispatch.

        Instances become a vmapped leading axis over the whole chunk
        runner: each gets its own ``n_lanes`` lane block, its own EPS
        pool (pools are padded to a common bucket with explicitly-failed
        stores and stacked ``[N, S, V]``), its own B&B bound and its own
        done flag — so statuses/objectives are identical to N sequential
        `solve` calls, while compilation, dispatch overhead and device
        occupancy are shared.  Single-device only (use the mesh engine
        for scale-out of ONE instance).

        Returns one `SolveResult` per instance, in input order.
        ``wall_s`` is the shared batch wall clock.

        Implemented as the degenerate case of the lane-owning `LaneBatch`
        scheduler core (DESIGN.md §15): splice every instance into a
        width-N batch up front, step until all slots are done, retire
        each slot.  The serving scheduler (`repro.serve`) drives the same
        class with continuous admission instead.
        """
        cms = list(cms)
        if not cms:
            return []
        cfg = self._config_for(config, overrides)
        if cfg.mesh is not None or cfg.mesh_shards is not None:
            raise ValueError("solve_many is single-device; it cannot be "
                             "combined with a mesh config")
        t0 = time.time()
        self.stats["solves"] += 1
        cms = [_canonical(cm) for cm in cms]
        sig = shape_signature(cms[0])
        for k, cm in enumerate(cms[1:], 1):
            if shape_signature(cm) != sig:
                raise ValueError(
                    f"solve_many needs same-shape instances: instance {k} "
                    f"has signature {shape_signature(cm)} != {sig}")
        N = len(cms)

        pools = [self.decompose(cm, config=cfg) for cm in cms]
        smax = max(p[0].shape[0] for p in pools)
        size = _bucket(smax) if cfg.pad_pool else smax

        builds_before = self.stats["runner_builds"]
        batch = LaneBatch(self, cms[0], cfg, width=N, pool_size=size)
        compiles0 = batch.runner.n_compiles
        for i, (cm, (pl, pu)) in enumerate(zip(cms, pools)):
            batch.splice(i, cm, pl, pu, request_id=i)
        while True:
            snap = batch.step()
            wall = time.time() - t0
            if snap.gdone.all():
                break
            if cfg.timeout_s is not None and wall > cfg.timeout_s:
                break
            if (cfg.max_supersteps is not None
                    and int(snap.superstep.max()) >= cfg.max_supersteps):
                break
        self.stats["last_solve_cold"] = (
            batch.runner.n_compiles > compiles0
            or self.stats["runner_builds"] > builds_before)

        wall = time.time() - t0
        return [batch.retire(i, wall_s=wall) for i in range(N)]

    # -- lane_batch: the continuous-batching scheduler core ---------------

    def lane_batch(self, cm: CompiledModel, *, width: int,
                   pool_size: Optional[int] = None,
                   config: Optional[SolveConfig] = None,
                   **overrides) -> "LaneBatch":
        """A `LaneBatch` of ``width`` slots shaped for instances
        signature-equal to ``cm`` — the lane-owning scheduler core the
        serving layer (`repro.serve`, DESIGN.md §15) admits requests
        into.  ``pool_size`` defaults to the pow2 bucket of the config's
        EPS target, the fixed upper bound on any `eps.decompose` pool
        for that target — so every admitted request's pool fits and the
        bucket compiles at most once."""
        cfg = self._config_for(config, overrides)
        if pool_size is None:
            tgt = cfg.resolved_eps_target()
            pool_size = _bucket(tgt) if cfg.pad_pool else tgt
        return LaneBatch(self, cm, cfg, width=width, pool_size=pool_size)


# --------------------------------------------------------------------------
# LaneBatch: the lane-owning continuous-batching core (DESIGN.md §15)
# --------------------------------------------------------------------------

_IDLE = object()          # slot-empty sentinel (request ids may be None)


class BatchSnapshot(NamedTuple):
    """Host-visible per-slot view of a `LaneBatch` after one quantum."""
    superstep: np.ndarray    # i32[B] per-slot cumulative superstep counters
    gdone: np.ndarray        # bool[B] per-slot global-done flags
    best_obj: np.ndarray     # [B] per-slot incumbent bound (min over lanes)
    has_sol: np.ndarray      # bool[B]
    n_nodes: np.ndarray      # i[B] per-slot node totals
    n_sols: np.ndarray       # i[B]
    t_host: float            # host wall clock (time.time()) at snapshot


class LaneBatch:
    """A fixed-width batch of same-shape instance *slots* driven through
    ONE vmapped chunk runner — the lane-owning scheduler core that
    `_run_chunk`'s host loop became (DESIGN.md §15).

    Each slot owns an ``n_lanes`` lane block, its own EPS pool rows
    (``[pool_size, V]``), its own B&B bound and its own done flag; the
    slot's ``request_id`` is what threads lane ownership back to a
    serving request.  Slots **join** (`splice`) and **leave** (`retire`)
    at chunk boundaries at *fixed compiled shape*: width ``B`` and pool
    bucket ``pool_size`` never change after construction, so admission
    and retirement never recompile — the vLLM-style continuous-batching
    property the serving scheduler (`repro.serve`) relies on.

    An idle slot is frozen: its ``gdone`` is True (the vmapped
    `while_loop` counter stops), its lanes are all ``done`` (every
    superstep is an idempotent no-op) and its pool rows are explicitly
    failed stores (`eps.failed_pool`), so idle slots cannot explore
    phantom subproblems.  `Solver.solve_many` is the degenerate
    splice-all-then-drain use of this class.  Single-device only.
    """

    def __init__(self, session: Solver, cm0: CompiledModel,
                 cfg: SolveConfig, *, width: int, pool_size: int):
        if cfg.mesh is not None or cfg.mesh_shards is not None:
            raise ValueError("LaneBatch (and solve_many on top of it) is "
                             "single-device; it cannot be combined with a "
                             "mesh config")
        if width < 1 or pool_size < 1:
            raise ValueError(f"LaneBatch needs width >= 1 and pool_size >= "
                             f"1, got {width}, {pool_size}")
        self.session = session
        self.cfg = cfg
        self.width = int(width)
        self.pool_size = int(pool_size)
        self.opts = cfg.search_options()
        cm0 = _canonical(cm0)
        self.signature = shape_signature(cm0)
        self._obj_var, self._n_vars = cm0.obj_var, cm0.n_vars
        self.runner = session._runner_for(cm0, cfg, batched=True)
        # the live-slot template: what a spliced slot's carry is reset to
        self._carry1 = _init_carry(cm0, cfg.n_lanes, self.opts,
                                   n_heads=_carry_heads(cfg, cm0, pool_size))
        # idle pool rows: explicitly-failed stores (inert by construction)
        il, iu = eps.failed_pool(np.asarray(cm0.lb0), np.asarray(cm0.ub0),
                                 pool_size)
        self._idle_lb, self._idle_ub = jnp.asarray(il), jnp.asarray(iu)
        B = self.width
        self.cm_b = jax.tree.map(lambda x: jnp.stack([x] * B), cm0)
        self.subs_lb = jnp.stack([self._idle_lb] * B)
        self.subs_ub = jnp.stack([self._idle_ub] * B)
        carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (B,) + x.shape),
            self._carry1)
        st, gbest, gdone, it, heads = carry
        st = st._replace(done=jnp.ones_like(st.done),
                         fresh=jnp.zeros_like(st.fresh))
        self.carry = (st, gbest, jnp.ones_like(gdone), it, heads)
        self.request_ids: List[Any] = [_IDLE] * B
        self._cms: List[Optional[CompiledModel]] = [None] * B
        self._host_st = None
        self.n_spliced = 0
        self.n_retired = 0

    # -- occupancy ---------------------------------------------------------

    def idle_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.request_ids) if r is _IDLE]

    def live_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.request_ids) if r is not _IDLE]

    @property
    def occupancy(self) -> int:
        return self.width - len(self.idle_slots())

    @property
    def obj_var(self) -> int:
        """The bucket's objective column (static across the batch;
        ``< 0`` for satisfaction models)."""
        return self._obj_var

    def request_id(self, i: int):
        rid = self.request_ids[i]
        return None if rid is _IDLE else rid

    # -- join / leave at chunk boundaries ----------------------------------

    def splice(self, i: int, cm: CompiledModel, subs_lb, subs_ub, *,
               request_id=None) -> None:
        """Admit an instance into idle slot ``i`` at fixed shape: its
        tables overwrite the slot's rows of the stacked model pytree, its
        pool is padded to the bucket (`eps.fit_pool`) and its carry slice
        is reset to a fresh live state.  Takes effect at the next
        `step` — the chunk boundary."""
        if self.request_ids[i] is not _IDLE:
            raise ValueError(f"slot {i} is occupied by request "
                             f"{self.request_ids[i]!r}")
        cm = _canonical(cm)
        if shape_signature(cm) != self.signature:
            raise ValueError(
                f"instance signature {shape_signature(cm)} does not match "
                f"this batch's bucket {self.signature}")
        lb, ub = eps.fit_pool(np.asarray(subs_lb), np.asarray(subs_ub),
                              self.pool_size)
        self.cm_b = jax.tree.map(lambda full, one: full.at[i].set(one),
                                 self.cm_b, cm)
        self.subs_lb = self.subs_lb.at[i].set(jnp.asarray(lb))
        self.subs_ub = self.subs_ub.at[i].set(jnp.asarray(ub))
        self.carry = jax.tree.map(lambda full, one: full.at[i].set(one),
                                  self.carry, self._carry1)
        self.request_ids[i] = request_id
        self._cms[i] = cm
        self._host_st = None
        self.n_spliced += 1

    def retire(self, i: int, *, wall_s: float,
               improvements: Tuple[Improvement, ...] = ()) -> SolveResult:
        """Retire slot ``i``: derive its per-request `SolveResult` from
        the slot's lane-state slice (per-slot exhaustion, per-slot
        superstep counter), then freeze the slot idle.  Valid whether
        the slot finished (``gdone``) or is being evicted early (a
        deadline miss) — eviction derives from the live state *before*
        freezing, so an incomplete search never claims OPTIMAL/UNSAT."""
        if self.request_ids[i] is _IDLE:
            raise ValueError(f"slot {i} is idle")
        st = self._host_state()
        sti = jax.tree.map(lambda x: x[i], st)
        totals = S.lane_totals(sti)
        exhausted = bool(np.asarray(sti.done).all())
        superstep = int(np.asarray(self.carry[3])[i])
        res = derive_result(
            self._cms[i], sti.best_obj, sti.has_sol, sti.best_sol,
            sti.incomplete, exhausted, totals["n_nodes"],
            totals["n_fails"], totals["n_sols"], totals["n_sweeps"],
            superstep, wall_s, tuple(improvements),
            **lane_counters(totals))
        self._freeze(i)
        self.request_ids[i] = _IDLE
        self._cms[i] = None
        self.n_retired += 1
        return res

    def _freeze(self, i: int) -> None:
        """Park slot ``i``: gdone, all lanes done, all-failed pool —
        every subsequent superstep on the slot is an idempotent no-op."""
        st, gbest, gdone, it, heads = self.carry
        st = st._replace(done=st.done.at[i].set(True),
                         fresh=st.fresh.at[i].set(False))
        self.carry = (st, gbest, gdone.at[i].set(True), it, heads)
        self.subs_lb = self.subs_lb.at[i].set(self._idle_lb)
        self.subs_ub = self.subs_ub.at[i].set(self._idle_ub)
        self._host_st = None

    # -- stepping ----------------------------------------------------------

    def step(self) -> BatchSnapshot:
        """Run ONE scheduler quantum (up to ``cfg.chunk`` supersteps per
        live slot; one K-superstep launch under ``pallas_resident``) over
        the whole batch and return the host-visible snapshot."""
        with obs.span("repro.solve.chunk"):
            self.carry = jax.block_until_ready(
                self.runner(self.cm_b, self.subs_lb, self.subs_ub,
                            self.carry))
        self._host_st = None
        with obs.span("repro.solve.poll"):
            return self.snapshot()

    def snapshot(self) -> BatchSnapshot:
        st, _, gdone, it, _ = self.carry
        return BatchSnapshot(
            superstep=np.asarray(it),
            gdone=np.asarray(gdone),
            best_obj=np.asarray(st.best_obj).min(axis=1),
            has_sol=np.asarray(st.has_sol).any(axis=1),
            n_nodes=np.asarray(st.n_nodes).sum(axis=1),
            n_sols=np.asarray(st.n_sols).sum(axis=1),
            t_host=time.time())

    def _host_state(self):
        if self._host_st is None:       # one transfer, reused per quantum
            self._host_st = jax.device_get(self.carry[0])
        return self._host_st

    def incumbent(self, i: int):
        """Slot ``i``'s current best ``(objective, solution)`` —
        ``(None, None)`` while no solution exists; objective is None for
        satisfaction models.  Same lane pick as `derive_result`."""
        st = self._host_state()
        has = np.asarray(st.has_sol[i]).reshape(-1)
        if not has.any():
            return None, None
        sols = np.asarray(st.best_sol[i]).reshape(-1, self._n_vars)
        if self._obj_var >= 0:
            objs = np.asarray(st.best_obj[i]).reshape(-1)
            k = int(objs.argmin())
            return int(objs[k]), sols[k]
        return None, sols[int(has.argmax())]


# --------------------------------------------------------------------------
# Module-level convenience: one shared default session
# --------------------------------------------------------------------------

_default_solver: Optional[Solver] = None


def default_solver() -> Solver:
    """The process-wide session used by `repro.solver.solve` and the
    `engine.solve` deprecation shim — so even legacy callers get
    compile caching across calls."""
    global _default_solver
    if _default_solver is None:
        _default_solver = Solver(SolveConfig())
    return _default_solver


def solve(cm: CompiledModel, *, subs=None, config=None,
          **overrides) -> SolveResult:
    return default_solver().solve(cm, subs=subs, config=config, **overrides)


def solve_many(cms: Sequence[CompiledModel], *, config=None,
               **overrides) -> List[SolveResult]:
    return default_solver().solve_many(cms, config=config, **overrides)


def solve_iter(cm: CompiledModel, *, subs=None, config=None,
               **overrides) -> Iterator[Progress]:
    return default_solver().solve_iter(cm, subs=subs, config=config,
                                       **overrides)
