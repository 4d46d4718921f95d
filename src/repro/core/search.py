"""Batched propagate-and-search (paper §TURBO).

A *lane* is the TPU analogue of a TURBO CUDA block: it owns one EPS
subproblem at a time and runs depth-first search on it.  Lanes are a batch
axis (`vmap`), sharded over mesh devices by the engine.

Per the paper's design choices, faithfully kept:
  * two stores per lane: the subproblem **root** store and the current
    store; backtracking copies the root and re-commits the decision path
    (full recomputation, no trail).  Because decisions are `tell`s (joins),
    the whole path is re-joined in one scatter and then a single fixpoint
    runs — recomputation is one propagation, not depth many;
  * eventless propagation (fixpoint.py) — every propagator, every sweep;
  * branch & bound through a shared best objective (global-memory cell in
    the paper; a cross-lane min + `lax.pmin` here).

Branching is (var, m) with left = `x ≤ m`, right = `x ≥ m+1`; value
strategies: `m = lb` (assign-min, the scheduling default) or the domain
midpoint (split).  Variable strategies: input order / min domain / min lb.

All control flow is mask-based so the step functions vmap; a lane that is
`done` keeps sweeping its converged store, which is a no-op by
idempotence (Thm. 2) — correctness never depends on lane divergence.

Superstep structure (the TURBO shape, DESIGN.md §2.3 and §9): propagation
is **hoisted out of the per-lane vmap**.  `lanes_step` runs four phases —
`dispatch_pool` (idle lanes pop the next EPS subproblems off the shared
per-device pool, DESIGN.md §9), then `lane_load_tile` (subproblem load +
B&B bound tell), then **one lane-batched backend fixpoint over the whole
[n_lanes, V] store tensor** (`SearchOptions.backend` picks
gather / scatter / pallas / pallas_resident), then `lane_commit_tile`
(solution recording, backtrack-or-branch bookkeeping).  The pool itself
comes from `eps.decompose` (engine.solve's ``eps_target``); the shared
incumbent `gbest` each lane prunes against is min-reduced across lanes
and mesh devices by the engine between supersteps (DESIGN.md §9 bound
sharing).

All four phases are **pure-array tile functions** over ``[L, …]``
batches (no `CompiledModel`, no vmap — the same discipline as
`fixpoint.sweep_tile`), so the resident search megakernel
(`kernels/fixpoint_kernel.search_pallas`, DESIGN.md §13) runs the exact
same branch/commit math on VMEM refs that the unfused path runs as XLA
ops — one implementation of the search semantics, two execution
strategies.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset as B
from repro.core.compile import CompiledModel
from repro.core.backend import get_backend

# variable-selection strategies
INPUT_ORDER = "input_order"
MIN_DOM = "min_dom"
MIN_LB = "min_lb"

# sentinel: lane has no assigned subproblem (shared-queue dispatch)
UNASSIGNED = np.iinfo(np.int32).max // 2
# value-selection strategies
VAL_MIN = "min"       # m = lb  (assign lower bound)
VAL_SPLIT = "split"   # m = (lb+ub)//2
# m = remaining domain value nearest the interval midpoint (ties low);
# branches left x = m, right x ≠ m (a bitset-store tell — the strategy
# activates the bitset domain even on pure-bounds models, DESIGN.md §17)
VAL_MIDDLE_OUT = "middle_out"


@dataclasses.dataclass(frozen=True)
class SearchOptions:
    var_strategy: str = INPUT_ORDER
    val_strategy: str = VAL_MIN
    max_depth: int = 2048
    max_fixpoint_iters: Optional[int] = None
    stop_on_first: bool = False      # satisfaction: stop at first solution
    # propagation backend for the superstep's lane-batched fixpoint:
    # "gather" | "scatter" | "pallas" (see core/backend.py)
    backend: str = "gather"
    # backend construction options (e.g. lane_tile/interpret for pallas);
    # must be hashable — a tuple of (key, value) pairs
    backend_opts: Tuple = ()


class LaneState(NamedTuple):
    # current + root stores (the paper's two stores per block)
    lb: jax.Array            # i[V]
    ub: jax.Array            # i[V]
    root_lb: jax.Array       # i[V]
    root_ub: jax.Array       # i[V]
    # decision path
    dec_var: jax.Array       # i32[MD]
    dec_val: jax.Array       # i[MD]   branch point m
    dec_flip: jax.Array      # bool[MD] True once on the right branch
    depth: jax.Array         # i32
    # subproblem queue cursor (static round-robin over the shard)
    next_sub: jax.Array      # i32
    fresh: jax.Array         # bool — needs to load a new subproblem
    done: jax.Array          # bool — queue exhausted
    incomplete: jax.Array    # bool — hit depth limit (search not exhaustive)
    # incumbent
    best_obj: jax.Array      # i
    best_sol: jax.Array      # i[V]
    has_sol: jax.Array       # bool
    # stats
    n_nodes: jax.Array       # i32
    n_fails: jax.Array       # i64
    n_sols: jax.Array        # i64
    n_sweeps: jax.Array      # i64
    # lockstep sweep rounds: each superstep adds the slowest lane's
    # sweeps (the batched fixpoint loop's iteration count), the same to
    # every lane of the batch
    n_sweep_rounds: jax.Array  # i32
    # bitset domain stores (DESIGN.md §17) — None unless the model has
    # tables or the value strategy is middle_out (None is an empty pytree
    # leaf set, so inactive states keep the legacy carry structure)
    dom: Optional[jax.Array] = None        # u32[L, V, W]
    root_dom: Optional[jax.Array] = None   # u32[L, V, W]


def use_dom(cm: CompiledModel, opts: SearchOptions) -> bool:
    """Whether search must carry the bitset store: extensional models
    always (Compact-Table filters value sets), and `middle_out` value
    ordering on any model (its right branch x ≠ m is a bitset tell)."""
    return cm.n_table > 0 or opts.val_strategy == VAL_MIDDLE_OUT


def init_lanes(cm: CompiledModel, n_lanes: int, opts: SearchOptions) -> LaneState:
    V = cm.n_vars
    dt = cm.jdtype
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    dom = (jnp.zeros((n_lanes, V, cm.n_words), jnp.uint32)
           if use_dom(cm, opts) else None)
    return LaneState(
        dom=dom, root_dom=dom,
        lb=jnp.zeros((n_lanes, V), dt), ub=jnp.zeros((n_lanes, V), dt),
        root_lb=jnp.zeros((n_lanes, V), dt), root_ub=jnp.zeros((n_lanes, V), dt),
        dec_var=jnp.zeros((n_lanes, opts.max_depth), jnp.int32),
        dec_val=jnp.zeros((n_lanes, opts.max_depth), dt),
        dec_flip=jnp.zeros((n_lanes, opts.max_depth), bool),
        depth=jnp.zeros((n_lanes,), jnp.int32),
        next_sub=jnp.full((n_lanes,), UNASSIGNED, jnp.int32),
        fresh=jnp.ones((n_lanes,), bool),
        done=jnp.zeros((n_lanes,), bool),
        incomplete=jnp.zeros((n_lanes,), bool),
        best_obj=jnp.full((n_lanes,), big, dt),
        best_sol=jnp.zeros((n_lanes, V), dt),
        has_sol=jnp.zeros((n_lanes,), bool),
        n_nodes=z(n_lanes), n_fails=z(n_lanes), n_sols=z(n_lanes),
        n_sweeps=z(n_lanes), n_sweep_rounds=z(n_lanes),
    )


def dispatch_pool_tile(st: LaneState, pool_head, n_subs: int,
                       tile_id=0, n_tiles: int = 1):
    """Shared subproblem queue (the paper's dynamic EPS, DESIGN.md §9):
    fresh lanes pop the next pool indices; when the pool is drained they
    are marked done.  Replaces static round-robin — no straggler lane can
    sit on a long private queue while others idle.  Runs as phase 0 of
    every superstep, so a lane that exhausts its subproblem is
    replenished on the very next superstep.

    With ``n_tiles > 1`` (a resident megakernel auto-shrunk into several
    VMEM grid cells, DESIGN.md §13) the pool is strided across tiles:
    tile ``t`` owns indices ``t, t + n_tiles, t + 2·n_tiles, …`` and
    ``pool_head`` is its private cursor into that shard — complete (the
    shards partition the pool) but without cross-tile work stealing
    inside a launch.  ``n_tiles == 1`` is exactly the shared-queue
    semantics of the unfused path."""
    want = st.fresh & ~st.done & (st.next_sub >= n_subs)
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    slot = pool_head + rank
    idx = tile_id + n_tiles * slot if n_tiles > 1 else slot
    got = want & (idx < n_subs)
    next_sub = jnp.where(got, idx.astype(jnp.int32), st.next_sub)
    done = st.done | (want & (idx >= n_subs))
    shard = (n_subs if n_tiles == 1
             else -((n_subs - tile_id) // -n_tiles))     # ceil shard size
    new_head = jnp.minimum(pool_head + want.astype(jnp.int32).sum(),
                           shard)
    return st._replace(next_sub=next_sub, done=done), new_head


def dispatch_pool(st: LaneState, pool_head, n_subs: int):
    """Single-queue view of `dispatch_pool_tile` (the unfused path)."""
    return dispatch_pool_tile(st, pool_head, n_subs)


def apply_path_tile(root_lb, root_ub, dec_var, dec_val, dec_flip, depth, *,
                    val_strategy: str = VAL_MIN, root_dom=None,
                    dom_off=None, dom_track=None):
    """Full recomputation for a ``[L, V]`` tile: root ⊔ all decision
    tells, in one flat scatter-min/max (per-lane duplicate indices are
    handled by the associative scatter join).  Pure-array form shared
    verbatim by the unfused commit and the resident megakernel.

    Interval strategies branch left x ≤ m / right x ≥ m+1.  Under
    `middle_out` the left branch is the assignment x = m (both bounds
    tell) and the right branch is x ≠ m — a *bitset* tell: the flipped
    decisions' value bits are cleared from `root_dom` via one flat
    scatter-add of their one-hot word masks (exact because a well-formed
    path never flips the same (var, value) twice, so the added masks are
    disjoint).  Decisions on *untracked* vars (dom_track == 0 — wider
    than the 32·W bitset) fall back per-decision to the interval split
    x ≤ m / x ≥ m+1, matching `select_branch_tile`'s degradation.
    Returns (lb, ub) — plus the recomputed dom when `root_dom` is
    carried.
    """
    L, V = root_lb.shape
    md = dec_var.shape[1]
    lvl = jnp.arange(md)
    on = lvl[None, :] < depth[:, None]
    dt = root_lb.dtype
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)
    if val_strategy == VAL_MIDDLE_OUT:
        trk = jnp.take(dom_track, dec_var.astype(jnp.int32)) != 0  # [L, MD]
        ub_tell = jnp.where(on & ~dec_flip, dec_val, big)      # left: x = m
        lb_tell = jnp.where(on & ~dec_flip & trk, dec_val,     # (x ≤ m wide)
                            jnp.where(on & dec_flip & ~trk,    # wide right:
                                      dec_val + 1, -big))      # x ≥ m+1
    else:
        ub_tell = jnp.where(on & ~dec_flip, dec_val, big)      # left: x ≤ m
        lb_tell = jnp.where(on & dec_flip, dec_val + 1, -big)  # right: x ≥ m+1
    rows = jnp.arange(L, dtype=jnp.int32)[:, None] * V
    flat = (rows + dec_var.astype(jnp.int32)).reshape(-1)
    ub = root_ub.reshape(L * V).at[flat].min(ub_tell.reshape(-1))
    lb = root_lb.reshape(L * V).at[flat].max(lb_tell.reshape(-1))
    lb, ub = lb.reshape(L, V), ub.reshape(L, V)
    if root_dom is None:
        return lb, ub
    dom = root_dom
    if val_strategy == VAL_MIDDLE_OUT:
        # right branches: clear bit (dec_val - off) of the decision var
        W = root_dom.shape[-1]
        bit = (dec_val - jnp.take(dom_off, dec_var.astype(jnp.int32))
               ).astype(jnp.int32)                             # [L, MD]
        hit = on & dec_flip & trk & (bit >= 0) & (bit < W * B.WORD_BITS)
        word = jnp.clip(bit >> 5, 0, W - 1)
        mask = jnp.where(hit,
                         np.uint32(1) << (bit & 31).astype(jnp.uint32),
                         np.uint32(0))
        flat_w = (rows * W + dec_var.astype(jnp.int32) * W + word
                  ).reshape(-1)
        acc = jnp.zeros((L * V * W,), jnp.uint32
                        ).at[flat_w].add(mask.reshape(-1))
        dom = dom & ~acc.reshape(L, V, W)
    return lb, ub, dom


def select_branch_tile(lb, ub, branch_vars, *, var_strategy: str,
                       val_strategy: str, dom=None, dom_off=None):
    """Pick (var, m) for each lane's next decision over a ``[L, V]``
    tile.  Returns (var[L], m[L], any_unfixed[L]).  Pure-array form
    shared verbatim by the unfused commit and the resident megakernel.

    `middle_out` (requires the carried bitset `dom`) picks the remaining
    domain value nearest the interval midpoint, ties to the lower value
    — the fail-first ordering the ROADMAP flags as blocking dense
    nqueens backtracking."""
    bv = branch_vars
    blb = jnp.take(lb, bv, axis=1)                          # [L, B]
    bub = jnp.take(ub, bv, axis=1)
    unfixed = blb < bub
    width = bub - blb
    big = jnp.iinfo(lb.dtype).max // 4
    if var_strategy == INPUT_ORDER:
        pos = jnp.argmax(unfixed, axis=1)                   # first True
    elif var_strategy == MIN_DOM:
        pos = jnp.argmin(jnp.where(unfixed, width, big), axis=1)
    elif var_strategy == MIN_LB:
        pos = jnp.argmin(jnp.where(unfixed, blb, big), axis=1)
    else:
        raise ValueError(var_strategy)
    var = jnp.take(bv, pos)                                 # [L]
    idx = var.astype(jnp.int32)[:, None]
    vlb = jnp.take_along_axis(lb, idx, axis=1)[:, 0]
    vub = jnp.take_along_axis(ub, idx, axis=1)[:, 0]
    if val_strategy == VAL_MIN:
        m = vlb
    elif val_strategy == VAL_SPLIT:
        m = (vlb + vub) // 2
    elif val_strategy == VAL_MIDDLE_OUT:
        if dom is None:
            raise ValueError("middle_out value ordering needs the bitset "
                             "domain store (search carries it whenever "
                             "the strategy is selected)")
        L, _, W = dom.shape
        K32 = W * B.WORD_BITS
        vdom = jnp.take_along_axis(
            dom, var.astype(jnp.int32)[:, None, None], axis=1)[:, 0]
        bits = ((vdom[:, :, None]
                 >> jnp.arange(B.WORD_BITS, dtype=jnp.uint32))
                & np.uint32(1)).reshape(L, K32)              # [L, 32W]
        voff = jnp.take(dom_off, var.astype(jnp.int32))       # [L]
        vals = voff[:, None] + jnp.arange(K32, dtype=lb.dtype)[None, :]
        mid = (vlb + vub) // 2
        ok = (bits != 0) & (vals >= vlb[:, None]) & (vals <= vub[:, None])
        # 2·distance + 1 for the upper side: nearest wins, ties go low
        score = 2 * jnp.abs(vals - mid[:, None]) + (vals > mid[:, None])
        pos = jnp.argmin(jnp.where(ok, score, big), axis=1)
        m = voff + pos.astype(lb.dtype)
    else:
        raise ValueError(val_strategy)
    return var, m, jnp.any(unfixed, axis=1)


class LanePrep(NamedTuple):
    """Lane-batched carry between `lane_load_tile` and `lane_commit_tile`
    — everything the post-propagation bookkeeping needs besides the
    propagated store.  All fields carry a leading ``[L]`` lane axis."""
    lb: jax.Array            # i[L, V] store with decision + bound tells
    ub: jax.Array            # i[L, V]
    root_lb: jax.Array       # i[L, V]
    root_ub: jax.Array       # i[L, V]
    depth: jax.Array         # i32[L]
    next_sub: jax.Array      # i32[L]
    fresh: jax.Array         # bool[L]
    active: jax.Array        # bool[L] — lane participates this superstep
    dom: Optional[jax.Array] = None        # u32[L, V, W] (bitset store)
    root_dom: Optional[jax.Array] = None


def lane_load_tile(subs_lb, subs_ub, st: LaneState, gbest, *,
                   obj_var: int, dom_off=None, dom_track=None,
                   n_words: int = 1) -> LanePrep:
    """Pre-propagation phase over a lane tile: subproblem load + B&B tell.

    `subs_lb/ub`: the (tile-visible) subproblem pool [S, V] (assignment
    happens in dispatch_pool_tile — the shared queue, TURBO's dynamic
    EPS; `done` is also decided there).
    `gbest`: scalar global incumbent bound (already cross-lane/device
    min'd).  Pure-array over ``[L, V]`` — no vmap, no `CompiledModel` —
    so the resident megakernel runs this exact function on VMEM refs.
    """
    S, V = subs_lb.shape
    dt = subs_lb.dtype
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)

    # -- 1. load the dispatcher-assigned subproblem when fresh -------------
    can_load = st.next_sub < S
    load = st.fresh & can_load
    sub = jnp.clip(st.next_sub, 0, S - 1)
    loadc = load[:, None]
    root_lb = jnp.where(loadc, jnp.take(subs_lb, sub, axis=0), st.root_lb)
    root_ub = jnp.where(loadc, jnp.take(subs_ub, sub, axis=0), st.root_ub)
    lb = jnp.where(loadc, root_lb, st.lb)
    ub = jnp.where(loadc, root_ub, st.ub)
    depth = jnp.where(load, 0, st.depth)
    next_sub = jnp.where(load, UNASSIGNED, st.next_sub)  # consumed
    fresh = st.fresh & ~load & ~st.done
    active = ~st.done & ~fresh
    dom = root_dom = None
    if st.dom is not None:
        # the EPS pool is interval-only (eps.decompose splits boxes), so
        # the subproblem's root bitset is exactly its box — lossless
        fresh_dom = B.from_bounds(root_lb, root_ub, dom_off, n_words,
                                  track=dom_track)
        root_dom = jnp.where(loadc[..., None], fresh_dom, st.root_dom)
        dom = jnp.where(loadc[..., None], root_dom, st.dom)

    # -- 2. branch & bound tell ------------------------------------------
    if obj_var >= 0:
        inc = jnp.minimum(gbest, st.best_obj)      # global ⊓ own incumbent
        bound = jnp.where(inc < big, inc - 1, big)
        tell = jnp.where(active, bound, big)                       # [L]
        vcols = jnp.arange(V)
        ub = jnp.where(vcols[None, :] == obj_var,
                       jnp.minimum(ub, tell[:, None]), ub)
    return LanePrep(lb=lb, ub=ub, root_lb=root_lb, root_ub=root_ub,
                    depth=depth, next_sub=next_sub, fresh=fresh,
                    active=active, dom=dom, root_dom=root_dom)


def lane_commit_tile(st: LaneState, pre: LanePrep, lb, ub, sweeps,
                     converged, branch_vars, *, obj_var: int,
                     var_strategy: str, val_strategy: str,
                     dom=None, dom_off=None, dom_track=None) -> LaneState:
    """Post-propagation phase over a lane tile: record / backtrack-or-
    branch.  `lb`, `ub`, `sweeps`, `converged` are the batched backend
    fixpoint outputs.  Pure-array over ``[L, V]`` (shared verbatim by the
    resident megakernel); the path depth limit is the static ``MD`` of
    the decision arrays."""
    L, V = lb.shape
    md = st.dec_var.shape[1]
    dt = lb.dtype
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)
    root_lb, root_ub = pre.root_lb, pre.root_ub
    depth, next_sub = pre.depth, pre.next_sub
    fresh, active, done = pre.fresh, pre.active, st.done

    failed = jnp.any(lb > ub, axis=1)
    # a fully-fixed store is only a SOLUTION at a (per-lane) fixed point:
    # with capped sweeps (§Perf H1), unconverged lanes keep propagating on
    # the next superstep instead of branching/recording (soundness guard).
    solved = active & converged & ~failed & jnp.all(lb == ub, axis=1)
    failed = active & failed

    # a node = one propagate-to-completion event (failed counts; an
    # unconverged capped superstep is a partial node, not counted)
    n_nodes = st.n_nodes + (failed | (active & converged)).astype(jnp.int32)
    n_fails = st.n_fails + failed.astype(jnp.int32)
    n_sols = st.n_sols + solved.astype(jnp.int32)
    n_sweeps = st.n_sweeps + jnp.asarray(sweeps, jnp.int32)
    n_sweep_rounds = st.n_sweep_rounds + jnp.max(sweeps).astype(jnp.int32)

    # -- 3. record incumbent ------------------------------------------------
    if obj_var >= 0:
        better = solved & (lb[:, obj_var] < st.best_obj)
        best_obj = jnp.where(better, lb[:, obj_var], st.best_obj)
    else:
        better = solved & ~st.has_sol
        best_obj = jnp.where(better, big, st.best_obj)
    best_sol = jnp.where(better[:, None], lb, st.best_sol)
    has_sol = st.has_sol | solved

    # -- 4. backtrack or branch ---------------------------------------------
    bt = failed | solved
    lvl = jnp.arange(md)
    open_mask = (~st.dec_flip) & (lvl[None, :] < depth[:, None])
    has_open = jnp.any(open_mask, axis=1)
    bt_level = jnp.max(jnp.where(open_mask, lvl[None, :], -1), axis=1)
    exhausted = active & bt & ~has_open

    do_bt = active & bt & has_open
    # pop everything deeper than bt_level, flip bt_level to its right branch
    dec_flip = jnp.where(
        do_bt[:, None],
        (st.dec_flip & (lvl[None, :] < bt_level[:, None]))
        | (lvl[None, :] == bt_level[:, None]),
        st.dec_flip)
    depth_bt = (bt_level + 1).astype(jnp.int32)

    # full recomputation for backtracking lanes
    root_dom = pre.root_dom
    if dom is None:
        rlb, rub = apply_path_tile(root_lb, root_ub, st.dec_var,
                                   st.dec_val, dec_flip, depth_bt,
                                   val_strategy=val_strategy,
                                   dom_track=dom_track)
    else:
        rlb, rub, rdom = apply_path_tile(root_lb, root_ub, st.dec_var,
                                         st.dec_val, dec_flip, depth_bt,
                                         val_strategy=val_strategy,
                                         root_dom=root_dom,
                                         dom_off=dom_off,
                                         dom_track=dom_track)

    # branching lanes (only at per-lane fixed points: unconverged lanes
    # do nothing this superstep and propagate further on the next)
    var, m, any_unfixed = select_branch_tile(
        lb, ub, branch_vars, var_strategy=var_strategy,
        val_strategy=val_strategy, dom=dom, dom_off=dom_off)
    do_branch = active & ~bt & converged & any_unfixed
    overflow = do_branch & (depth >= md)
    do_branch = do_branch & ~overflow
    at_lvl = lvl[None, :] == jnp.clip(depth, 0, md - 1)[:, None]  # [L, MD]
    upd = do_branch[:, None] & at_lvl
    dec_var = jnp.where(upd, var.astype(jnp.int32)[:, None], st.dec_var)
    dec_val = jnp.where(upd, m[:, None], st.dec_val)
    dec_flip = jnp.where(upd, False, dec_flip)
    vcols = jnp.arange(V)
    btell = jnp.where(do_branch, m, big)                          # [L]
    bub = jnp.where(vcols[None, :] == var[:, None],               # left: x ≤ m
                    jnp.minimum(ub, btell[:, None]), ub)
    if val_strategy == VAL_MIDDLE_OUT:                    # left: x = m
        trk_var = jnp.take(dom_track, var.astype(jnp.int32)) != 0
        btell_lo = jnp.where(do_branch & trk_var, m, -big)  # wide: x ≤ m
        blb = jnp.where(vcols[None, :] == var[:, None],
                        jnp.maximum(lb, btell_lo[:, None]), lb)
    else:
        blb = lb

    # -- 5. commit per-lane outcome ------------------------------------------
    new_lb = jnp.where(do_bt[:, None], rlb, blb)
    new_ub = jnp.where(do_bt[:, None], rub, bub)
    new_depth = jnp.where(do_bt, depth_bt,
                          jnp.where(do_branch, depth + 1, depth))
    fresh = fresh | exhausted | overflow
    incomplete = st.incomplete | overflow
    new_dom = (None if dom is None
               else jnp.where(do_bt[:, None, None], rdom, dom))

    return LaneState(
        lb=new_lb, ub=new_ub, root_lb=root_lb, root_ub=root_ub,
        dec_var=dec_var, dec_val=dec_val, dec_flip=dec_flip,
        depth=new_depth, next_sub=next_sub, fresh=fresh, done=done,
        incomplete=incomplete, best_obj=best_obj, best_sol=best_sol,
        has_sol=has_sol, n_nodes=n_nodes, n_fails=n_fails, n_sols=n_sols,
        n_sweeps=n_sweeps, n_sweep_rounds=n_sweep_rounds, dom=new_dom,
        root_dom=root_dom)


def lanes_step(cm: CompiledModel, subs_lb, subs_ub, opts: SearchOptions,
               st: LaneState, gbest, pool_head):
    """One superstep over all lanes: pool dispatch (idle-lane
    replenishment) → tile load → **one** lane-batched backend fixpoint
    over the whole [n_lanes, V] store tensor → tile commit.  Every phase
    is a pure-array tile function; propagation is a single batched call
    (one kernel invocation per superstep — the TURBO shape, DESIGN.md
    §9).  The `pallas_resident` backend fuses K of these supersteps into
    one kernel launch by running the same tile functions inside Pallas
    (DESIGN.md §13).

    `pool_head` is the device-local cursor into the EPS pool; the updated
    cursor is returned alongside the new lane state.
    """
    with jax.named_scope("superstep.dispatch_pool"):
        st, pool_head = dispatch_pool(st, pool_head, subs_lb.shape[0])
    with jax.named_scope("superstep.lane_load"):
        pre = lane_load_tile(subs_lb, subs_ub, st, gbest,
                             obj_var=cm.obj_var, dom_off=cm.dom_off,
                             dom_track=cm.dom_track, n_words=cm.n_words)
    backend = get_backend(opts.backend, **dict(opts.backend_opts))
    with jax.named_scope("superstep.fixpoint"):
        if pre.dom is not None:
            lb, ub, dom, sweeps, converged = backend.fixpoint_batch(
                cm, pre.lb, pre.ub, dom=pre.dom,
                max_iters=opts.max_fixpoint_iters)
        else:
            dom = None
            lb, ub, sweeps, converged = backend.fixpoint_batch(
                cm, pre.lb, pre.ub, max_iters=opts.max_fixpoint_iters)
    with jax.named_scope("superstep.lane_commit"):
        st = lane_commit_tile(st, pre, lb, ub, sweeps, converged,
                              cm.branch_vars, obj_var=cm.obj_var,
                              var_strategy=opts.var_strategy,
                              val_strategy=opts.val_strategy,
                              dom=dom, dom_off=cm.dom_off,
                              dom_track=cm.dom_track)
    return st, pool_head


def lanes_best(st: LaneState, dt):
    """Cross-lane incumbent (the shared global-memory bound of the paper)."""
    return jnp.min(st.best_obj)


def all_done(st: LaneState) -> jax.Array:
    return jnp.all(st.done)


def lane_totals(st: LaneState) -> dict:
    """Cross-lane counter totals, as host ints — the stats block every
    terminal `SolveResult` is assembled from (api.derive_result).  Works
    on device lane states and on host-side (numpy) slices alike.
    ``n_sweep_rounds`` is the largest lane's (every lane of one batched
    fixpoint holds the same; lanes split over devices or kernel tiles
    count their own rounds), ``n_lanes`` the lanes counted."""
    rounds = np.asarray(st.n_sweep_rounds)
    return dict(n_nodes=int(np.asarray(st.n_nodes).sum()),
                n_fails=int(np.asarray(st.n_fails).sum()),
                n_sols=int(np.asarray(st.n_sols).sum()),
                n_sweeps=int(np.asarray(st.n_sweeps).sum()),
                n_sweep_rounds=int(rounds.max(initial=0)),
                n_lanes=int(rounds.size))
