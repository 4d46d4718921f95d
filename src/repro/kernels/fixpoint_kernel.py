"""Pallas TPU kernels: propagation fixpoint and resident search in VMEM.

GPU→TPU mapping (DESIGN.md §2): one grid cell ↔ one TURBO CUDA block ↔ a
*tile of lanes* whose stores live in VMEM for the entire kernel — the
analogue of TURBO keeping both stores in the SM's shared memory.  The
propagator/occurrence tables are broadcast to every grid cell (index_map
pins them to block 0), mirroring the constant problem tables in GPU
constant/global memory.

Two kernels share one semantics implementation:

* `fixpoint_pallas` — the *unfused* propagation kernel: one grid cell
  iterates its lane tile to the least fixed point.  The loop body is
  `fixpoint.fixpoint_tile`, the **same** per-lane-masked sweep loop the
  XLA gather backend runs — one implementation, two execution
  strategies.

* `search_pallas` — the *resident search megakernel* (DESIGN.md §13):
  the whole four-phase superstep — EPS pool dispatch, subproblem load +
  B&B bound tell, fixpoint sweeps, solution/backtrack/branch commit —
  fused into one `pl.pallas_call` that keeps every piece of lane state
  (both stores, the decision path, status flags, the pool cursor and the
  tile-best bound) resident in VMEM across ``supersteps`` supersteps,
  via a `lax.fori_loop` over `search.lane_load_tile` /
  `fixpoint.fixpoint_tile` / `search.lane_commit_tile` — the *same*
  pure-array tile functions `search.lanes_step` composes as separate XLA
  dispatches.  The host is re-entered only once per K supersteps (global
  best all-reduce, incumbent streaming, pool refill — see
  `core/api._run_chunk`).

VMEM budget: `vmem_budget` promotes the DESIGN.md §2 table into code —
per-grid-cell bytes for tables, stores, resident search state and the
dominant sweep intermediates — and `fixpoint_pallas`/`search_pallas`
auto-shrink their lane tile (with a warning) instead of dying in a
Mosaic OOM.

Both kernels run only in the Pallas interpreter today: Mosaic, the TPU
Pallas compiler, refuses them (`MOSAIC_REFUSAL`; ROADMAP A2 lists every
refusal in the order the compiler reports them), so ``interpret=False``
raises before lowering instead of failing deep inside it.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.compile import (alldiff_dense_tile_bytes,
                                alldiff_sparse_tile_bytes,
                                ct_tile_bytes,
                                cumulative_dense_tile_bytes,
                                cumulative_sparse_tile_bytes)
from repro.core.fixpoint import (TABLE_NAMES, fixpoint_tile, model_statics,
                                 presence_mask)
from repro.core import search as S

# TPU v5e per-core VMEM (DESIGN.md §2); the budget leaves headroom for
# double-buffering and compiler temporaries by charging the dominant
# sweep intermediates explicitly instead of reserving a blanket margin.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

N_TABLES = len(TABLE_NAMES)    # positional args of fixpoint.sweep_tile
# model_tables positions the search kernel reads back out (§17, §18)
_I_DOM_OFF = TABLE_NAMES.index("dom_off")
_I_DOM_TRACK = TABLE_NAMES.index("dom_track")
_I_CU_PRES = TABLE_NAMES.index("cu_pres")
_BOOL_FIELDS = ("dec_flip", "fresh", "done", "incomplete", "has_sol")

# What Mosaic reports when the kernels are compiled for a TPU v5e
# (JAX 0.9.0).  It stops at the first refusal; the next ones are found by
# avoiding each in turn (ROADMAP A2).
MOSAIC_REFUSAL = (
    "the Pallas kernels do not lower on a TPU: Mosaic first refuses the "
    "rank-1 per-lane blocks (`lane1d` in fixpoint_pallas, `cell1` in "
    "search_pallas), which are neither the whole array nor a multiple of "
    "128 lanes; with those avoided it refuses the gather "
    "`jnp.take(lb, vidx, axis=1)` at core/fixpoint.py:84 (\"Shape "
    "mismatch in input, indices and output\"). Use backend='gather' on a "
    "TPU, or interpret=True for the Pallas interpreter.")


def _require_interpret(interpret: bool) -> None:
    if not interpret:
        raise NotImplementedError(MOSAIC_REFUSAL)


def _nbytes(a) -> int:
    return int(a.size) * a.dtype.itemsize


def vmem_budget(cm, lane_tile: int, *, resident: bool = False,
                max_depth: int = 0, pool_size: int = 0) -> dict:
    """Per-grid-cell VMEM byte footprint (the DESIGN.md §2/§13 budget
    table, in code).

    Returns a breakdown dict with a ``total`` key:

    * ``tables``  — the broadcast propagator/occurrence banks;
    * ``stores``  — lane-tile store I/O (in + out);
    * ``state``   — resident-only: the full `LaneState` beyond the
      stores (decision path [TL, MD]·3, best_sol [TL, V], per-lane
      scalars), in + out, plus the broadcast EPS pool [S, V]·2;
    * ``scratch`` — the dominant sweep intermediates per lane: the
      [P1, K+1] linear candidate tensors, the per-bank tile scratch
      **for the compiled layout** (dense: [A1, N³] Hall tensor /
      [C1, T, H] time-table grid; sparse: the [M, M] packed pairwise
      tensors / the O(M) event arrays — estimators shared with
      `compile.py`'s crossover guard), plus the [V, D] occurrence
      gathers.

    `fixpoint_pallas`/`search_pallas` compare ``total`` against
    `VMEM_LIMIT_BYTES` and halve the lane tile instead of handing Mosaic
    an un-allocatable kernel.
    """
    it = jnp.dtype(cm.jdtype).itemsize
    V = cm.n_vars
    from repro.core.fixpoint import model_tables
    tables = sum(_nbytes(a) for a in model_tables(cm))

    P1, K = cm.vidx.shape
    D = cm.occ_prop.shape[1]
    A1, N = cm.ad_vars.shape
    Dad = cm.ad_occ_inst.shape[1]
    C1, T = cm.cu_svar.shape
    Dcu = cm.cu_occ_inst.shape[1]
    per_lane = 8 * P1 * (K + 1) + 2 * V * (D + Dad + Dcu)
    scratch = lane_tile * per_lane * it
    if cm.n_alldiff:
        scratch += lane_tile * (
            alldiff_sparse_tile_bytes(cm.ad_packed, it)
            if cm.ad_layout == "sparse"
            else alldiff_dense_tile_bytes(cm.n_alldiff, N, it))
    if cm.n_cumulative:
        scratch += lane_tile * (
            cumulative_sparse_tile_bytes(cm.n_cumulative, T, it,
                                         cm.cu_optional)
            if cm.cu_layout == "sparse"
            else cumulative_dense_tile_bytes(cm.n_cumulative, T,
                                             cm.horizon, it,
                                             cm.cu_optional))
    if cm.n_table:
        scratch += lane_tile * ct_tile_bytes(cm.n_table, cm.ct_arity,
                                             cm.n_words, cm.ct_words)

    stores = 4 * lane_tile * V * it          # lb/ub in + out
    if cm.n_table:
        # the carried bitset store (dom in + out); middle_out on a pure
        # bounds model also carries one, but that is V words/lane of
        # headroom the budget's explicit-scratch margins absorb
        stores += 2 * lane_tile * V * cm.n_words * 4
    state = 0
    if resident:
        tables += _nbytes(cm.branch_vars)
        # root stores + best_sol (in+out), decision path, lane scalars
        state += 2 * (3 * lane_tile * V * it          # root_lb/ub, best_sol
                      + 3 * lane_tile * max_depth * 4  # dec_var/val/flip
                      + 12 * lane_tile * 4)            # flags + counters
        state += 2 * pool_size * V * it                # broadcast EPS pool
        if cm.n_table:
            state += 2 * lane_tile * V * cm.n_words * 4   # root_dom in+out
    else:
        stores += 2 * lane_tile * 4                    # sweeps/conv out
    total = tables + stores + state + scratch
    return dict(tables=tables, stores=stores, state=state, scratch=scratch,
                total=total)


def fit_lane_tile(cm, lane_tile: int, n_lanes: int, *,
                  resident: bool = False, max_depth: int = 0,
                  pool_size: int = 0, limit_bytes: int = None,
                  interpret: bool = True) -> int:
    """Clamp `lane_tile` to `n_lanes` and halve it until the
    `vmem_budget` fits `limit_bytes` (default `VMEM_LIMIT_BYTES`,
    warning on each shrink); raise a clear error when even a single
    lane per cell does not fit.  A compiled kernel (``interpret=False``)
    raises instead of shrinking: the TPU's tiling refuses the 4-, 2- and
    1-lane blocks that halving produces."""
    if limit_bytes is None:
        limit_bytes = VMEM_LIMIT_BYTES
    kernel = "search_pallas" if resident else "fixpoint_pallas"
    tile = max(1, min(lane_tile, n_lanes))
    while True:
        b = vmem_budget(cm, tile, resident=resident, max_depth=max_depth,
                        pool_size=pool_size)
        if b["total"] <= limit_bytes:
            return tile
        if not interpret:
            raise ValueError(
                f"{kernel}: lane_tile={tile} needs "
                f"{b['total'] / 2**20:.1f} MB of VMEM (> "
                f"{limit_bytes / 2**20:.1f} MB) and a compiled kernel "
                f"cannot shrink it: pass a smaller lane_tile that the "
                f"TPU's tiling accepts, or use the gather backend")
        if tile == 1:
            raise ValueError(
                f"{kernel}: model {cm.name or '<unnamed>'} does not fit "
                f"VMEM even at lane_tile=1: "
                f"{b['total'] / 2**20:.1f} MB needed "
                f"(tables {b['tables'] / 2**20:.1f} MB, scratch "
                f"{b['scratch'] / 2**20:.1f} MB, state "
                f"{b['state'] / 2**20:.1f} MB) vs "
                f"{limit_bytes / 2**20:.1f} MB VMEM — shrink the model "
                f"(horizon/occurrence widths) or use the gather backend")
        new = max(1, tile // 2)
        warnings.warn(
            f"{kernel}: lane_tile={tile} needs {b['total'] / 2**20:.1f} MB "
            f"of VMEM (> {limit_bytes / 2**20:.1f} MB); shrinking to "
            f"{new}", stacklevel=3)
        tile = new


# --------------------------------------------------------------------------
# Unfused propagation kernel (one fixpoint per launch)
# --------------------------------------------------------------------------

def _fixpoint_kernel(*refs, max_sweeps: int, have_dom: bool, statics: dict):
    """``statics`` are the model's `fixpoint.model_statics`."""
    table_refs = refs[:N_TABLES]
    k = N_TABLES
    lb_ref, ub_ref = refs[k], refs[k + 1]
    dom_ref = refs[k + 2] if have_dom else None
    outs = refs[k + 2 + int(have_dom):]
    tables = tuple(r[...] for r in table_refs)
    if have_dom:
        out_lb_ref, out_ub_ref, out_dom_ref, sweeps_ref, conv_ref = outs
        lb, ub, dom, sweeps, conv = fixpoint_tile(
            lb_ref[...], ub_ref[...], *tables, **statics,
            dom=dom_ref[...], max_iters=max_sweeps)
        out_dom_ref[...] = dom
    else:
        out_lb_ref, out_ub_ref, sweeps_ref, conv_ref = outs
        lb, ub, sweeps, conv = fixpoint_tile(
            lb_ref[...], ub_ref[...], *tables, **statics,
            max_iters=max_sweeps)
    out_lb_ref[...] = lb
    out_ub_ref[...] = ub
    sweeps_ref[...] = sweeps
    conv_ref[...] = conv.astype(jnp.int32)


def _whole(*shape):
    """A BlockSpec broadcasting a whole array to every grid cell."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _table_specs(cm):
    """BlockSpecs broadcasting the full propagator banks to every cell."""
    from repro.core.fixpoint import model_tables
    return [_whole(*a.shape) for a in model_tables(cm)]


def fixpoint_pallas(cm, lb, ub, dom=None, *, lane_tile: int = 8,
                    max_sweeps: int = 16384, interpret: bool = True):
    """Run the VMEM fixpoint kernel over lane-batched stores [L, V].

    Grid = ceil(L / lane_tile); each cell iterates its tile to fixpoint
    with the shared per-lane-masked loop (`fixpoint.fixpoint_tile`), so
    sweep counts and convergence flags are bit-identical to the XLA
    backends.  The tile auto-shrinks (with a warning) when the
    `vmem_budget` exceeds VMEM.  Returns (lb', ub', sweeps[L],
    converged[L]); with `dom` (the ``[L, V, W]`` bitset store, DESIGN.md
    §17) it rides in VMEM next to the interval stores and the return
    gains dom' before the counters.
    """
    _require_interpret(interpret)
    from repro.core.fixpoint import model_tables
    L, V = lb.shape
    lane_tile = fit_lane_tile(cm, lane_tile, L, interpret=interpret)
    pad = (-L) % lane_tile
    if pad:
        lb = jnp.concatenate([lb, jnp.broadcast_to(lb[-1:], (pad, V))])
        ub = jnp.concatenate([ub, jnp.broadcast_to(ub[-1:], (pad, V))])
        if dom is not None:
            dom = jnp.concatenate(
                [dom, jnp.broadcast_to(dom[-1:], (pad,) + dom.shape[1:])])
    Lp = lb.shape[0]
    grid = (Lp // lane_tile,)

    dt = cm.jdtype
    tiled = pl.BlockSpec((lane_tile, V), lambda i: (i, 0))
    lane1d = pl.BlockSpec((lane_tile,), lambda i: (i,))
    have_dom = dom is not None
    W = dom.shape[-1] if have_dom else 0
    tiled3 = (pl.BlockSpec((lane_tile, V, W), lambda i: (i, 0, 0))
              if have_dom else None)

    outs = pl.pallas_call(
        functools.partial(_fixpoint_kernel, max_sweeps=max_sweeps,
                          have_dom=have_dom, statics=model_statics(cm)),
        grid=grid,
        in_specs=(_table_specs(cm) + [tiled, tiled]
                  + ([tiled3] if have_dom else [])),
        out_specs=([tiled, tiled] + ([tiled3] if have_dom else [])
                   + [lane1d, lane1d]),
        out_shape=(
            [jax.ShapeDtypeStruct((Lp, V), dt),
             jax.ShapeDtypeStruct((Lp, V), dt)]
            + ([jax.ShapeDtypeStruct((Lp, V, W), jnp.uint32)]
               if have_dom else [])
            + [jax.ShapeDtypeStruct((Lp,), jnp.int32),
               jax.ShapeDtypeStruct((Lp,), jnp.int32)]),
        interpret=interpret,
    )(*model_tables(cm), lb, ub, *([dom] if have_dom else []))
    if have_dom:
        out_lb, out_ub, out_dom, sweeps, conv = outs
        return (out_lb[:L], out_ub[:L], out_dom[:L], sweeps[:L],
                conv[:L].astype(bool))
    out_lb, out_ub, sweeps, conv = outs
    return out_lb[:L], out_ub[:L], sweeps[:L], conv[:L].astype(bool)


# --------------------------------------------------------------------------
# Resident search megakernel (K supersteps per launch, DESIGN.md §13)
# --------------------------------------------------------------------------

def _state_fields(st: S.LaneState):
    """The LaneState fields this state actually carries (the bitset
    stores are None on bounds-only models — skipped, so the kernel ref
    layout matches the pytree exactly)."""
    return tuple(f for f in S.LaneState._fields
                 if getattr(st, f) is not None)


def _pack_state(st: S.LaneState):
    """LaneState → kernel I/O arrays (bools as int32, field order)."""
    return tuple(
        getattr(st, f).astype(jnp.int32) if f in _BOOL_FIELDS
        else getattr(st, f)
        for f in _state_fields(st))


def _unpack_state(arrays, fields) -> S.LaneState:
    return S.LaneState(**{
        f: (a != 0 if f in _BOOL_FIELDS else a)
        for f, a in zip(fields, arrays)})


def _search_kernel(*refs, supersteps: int, max_sweeps: int, statics: dict,
                   state_fields: tuple, obj_var: int,
                   var_strategy: str, val_strategy: str,
                   stop_on_first: bool, max_fixpoint_iters, n_tiles: int):
    """K fused supersteps over one VMEM-resident lane tile.

    The body composes the *same* tile functions the unfused path runs
    as separate XLA dispatches — `dispatch_pool_tile` → `lane_load_tile`
    → `fixpoint_tile` → `lane_commit_tile` — inside a `fori_loop`, with
    each superstep guarded by the derived global-done flag (`done` and
    `has_sol` are monotone, so the carried `gdone` of the host loop is
    recomputable from state: a stopped tile runs K identity steps,
    keeping the launch idempotent).  ``statics`` are the model's
    `fixpoint.model_statics`.
    """
    k = N_TABLES
    n_state = len(state_fields)
    tables = tuple(r[...] for r in refs[:k])
    dom_off = tables[_I_DOM_OFF]
    dom_track = tables[_I_DOM_TRACK]
    n_words = statics["n_words"]
    bv = refs[k][...]
    subs_lb = refs[k + 1][...]
    subs_ub = refs[k + 2][...]
    pres_mask = (presence_mask(tables[_I_CU_PRES], subs_lb.shape[1])
                 if statics["cu_optional"] else None)
    st = _unpack_state([r[...] for r in refs[k + 3:k + 3 + n_state]],
                       state_fields)
    gbest_ref, it_ref, head_ref = refs[k + 3 + n_state:k + 6 + n_state]
    outs = refs[k + 6 + n_state:]
    out_state = outs[:n_state]
    out_gbest_ref, out_head_ref, out_it_ref, out_stop_ref = outs[n_state:]

    gbest = gbest_ref[0]
    it = it_ref[0]
    head = head_ref[0]
    n_subs = subs_lb.shape[0]
    tile_id = pl.program_id(0) if n_tiles > 1 else 0
    cap = max_sweeps if max_fixpoint_iters is None else max_fixpoint_iters

    def gdone_of(st):
        g = jnp.all(st.done)
        if stop_on_first:
            g = g | jnp.any(st.has_sol)
        return g

    def superstep(_, carry):
        st, gbest, it, head = carry

        def run(c):
            st, gbest, it, head = c
            st, head = S.dispatch_pool_tile(st, head, n_subs,
                                            tile_id=tile_id,
                                            n_tiles=n_tiles)
            pre = S.lane_load_tile(subs_lb, subs_ub, st, gbest,
                                   obj_var=obj_var, dom_off=dom_off,
                                   dom_track=dom_track, n_words=n_words)
            if pre.dom is not None:
                lb, ub, dm, sweeps, conv = fixpoint_tile(
                    pre.lb, pre.ub, *tables, **statics, dom=pre.dom,
                    max_iters=cap)
            else:
                dm = None
                lb, ub, sweeps, conv = fixpoint_tile(
                    pre.lb, pre.ub, *tables, **statics, max_iters=cap)
            st = S.lane_commit_tile(st, pre, lb, ub, sweeps, conv, bv,
                                    obj_var=obj_var,
                                    var_strategy=var_strategy,
                                    val_strategy=val_strategy,
                                    dom=dm, dom_off=dom_off,
                                    dom_track=dom_track,
                                    pres_mask=pres_mask)
            gbest = jnp.minimum(gbest, jnp.min(st.best_obj))
            return st, gbest, it + 1, head

        return lax.cond(gdone_of(st), lambda c: c, run,
                        (st, gbest, it, head))

    st, gbest, it, head = lax.fori_loop(0, supersteps, superstep,
                                        (st, gbest, it, head))
    for ref, val in zip(out_state, _pack_state(st)):
        ref[...] = val
    out_gbest_ref[...] = jnp.reshape(gbest, (1,))
    out_head_ref[...] = jnp.reshape(head, (1,)).astype(jnp.int32)
    out_it_ref[...] = jnp.reshape(it, (1,)).astype(jnp.int32)
    out_stop_ref[...] = jnp.reshape(gdone_of(st), (1,)).astype(jnp.int32)


def _pad_lanes(st: S.LaneState, pad: int, dt) -> S.LaneState:
    """Append `pad` inert lanes (done, no subproblem, neutral incumbent)
    so the lane axis tiles evenly; sliced back off after the launch."""
    big = jnp.asarray(jnp.iinfo(dt).max // 4, dt)

    def ext(a, fill):
        tail = jnp.full((pad,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, tail])

    fills = dict(next_sub=S.UNASSIGNED, done=True, best_obj=big)
    return S.LaneState(**{
        f: ext(getattr(st, f), fills.get(f, 0))
        for f in _state_fields(st)})


def search_pallas(cm, subs_lb, subs_ub, st: S.LaneState, gbest, it,
                  pool_head, *, supersteps: int = 16, lane_tile: int = 0,
                  max_sweeps: int = 16384, max_fixpoint_iters=None,
                  var_strategy: str = S.INPUT_ORDER,
                  val_strategy: str = S.VAL_MIN,
                  stop_on_first: bool = False, interpret: bool = True):
    """Launch the resident search megakernel: K = `supersteps` fused
    supersteps with all lane state held in VMEM (DESIGN.md §13).

    ``lane_tile=0`` (the default, and the bit-parity mode) puts ALL
    lanes in one grid cell so the EPS pool is one shared queue —
    exactly `search.lanes_step`'s dispatch semantics.  A smaller tile
    (set explicitly or by VMEM auto-shrink) splits lanes over
    ``n_tiles`` cells with the pool strided across them (cell t owns
    pool indices t, t+NT, …) — still sound and complete, but a
    different (documented) dispatch trajectory; `pool_head` then
    carries one cursor per cell.

    Arguments mirror one `_run_chunk` carry: `st` the LaneState,
    `gbest` the scalar global bound, `it` the scalar superstep counter,
    `pool_head` the ``[n_tiles]`` pool cursor(s).  Returns
    ``(st', gbest', it', pool_head', stopped)`` where `stopped` is the
    derived global-done flag (all lanes drained, or first solution
    under `stop_on_first`) — the host chunk scheduler ORs it into
    `gdone` and stops relaunching.
    """
    _require_interpret(interpret)
    L, V = st.lb.shape
    MD = st.dec_var.shape[1]
    Spool = subs_lb.shape[0]
    dt = cm.jdtype

    tile = L if lane_tile in (0, None) else lane_tile
    tile = fit_lane_tile(cm, tile, L, resident=True, max_depth=MD,
                         pool_size=Spool, interpret=interpret)
    pad = (-L) % tile
    if pad:
        st = _pad_lanes(st, pad, dt)
    Lp = L + pad
    n_tiles = Lp // tile
    pool_head = jnp.broadcast_to(jnp.asarray(pool_head, jnp.int32),
                                 (n_tiles,))

    cell1 = pl.BlockSpec((1,), lambda i: (i,))

    def state_spec(f):
        a = getattr(st, f)
        if a.ndim == 3:
            return pl.BlockSpec((tile,) + a.shape[1:],
                                lambda i: (i, 0, 0))
        if a.ndim == 2:
            return pl.BlockSpec((tile, a.shape[1]), lambda i: (i, 0))
        return pl.BlockSpec((tile,), lambda i: (i,))

    def state_shape(f):
        a = getattr(st, f)
        d = jnp.int32 if a.dtype == jnp.bool_ else a.dtype
        return jax.ShapeDtypeStruct(a.shape, d)

    fields = _state_fields(st)
    n_state = len(fields)
    in_specs = (_table_specs(cm)
                + [_whole(int(cm.branch_vars.shape[0])),
                   _whole(Spool, V), _whole(Spool, V)]
                + [state_spec(f) for f in fields]
                + [_whole(1), _whole(1), cell1])
    out_specs = ([state_spec(f) for f in fields]
                 + [cell1, cell1, cell1, cell1])
    out_shape = ([state_shape(f) for f in fields]
                 + [jax.ShapeDtypeStruct((n_tiles,), dt)]
                 + [jax.ShapeDtypeStruct((n_tiles,), jnp.int32)] * 3)

    from repro.core.fixpoint import model_tables
    outs = pl.pallas_call(
        functools.partial(
            _search_kernel, supersteps=supersteps, max_sweeps=max_sweeps,
            statics=model_statics(cm), state_fields=fields,
            obj_var=cm.obj_var,
            var_strategy=var_strategy, val_strategy=val_strategy,
            stop_on_first=stop_on_first,
            max_fixpoint_iters=max_fixpoint_iters, n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        interpret=interpret,
    )(*model_tables(cm), cm.branch_vars, subs_lb, subs_ub,
      *_pack_state(st),
      jnp.reshape(jnp.asarray(gbest, dt), (1,)),
      jnp.reshape(jnp.asarray(it, jnp.int32), (1,)),
      pool_head)

    st_out = _unpack_state(outs[:n_state], fields)
    if pad:
        st_out = S.LaneState(**{
            f: getattr(st_out, f)[:L] for f in fields})
    gbest_out, head_out, it_out, stop_out = outs[n_state:]
    return (st_out, jnp.min(gbest_out), jnp.max(it_out),
            head_out.astype(jnp.int32), jnp.all(stop_out != 0))
