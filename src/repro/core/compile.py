"""⟦.⟧ — lower a Model to dense guarded-command tables (paper Prop. 4).

Every constraint becomes one row of a *typed propagator table*
(DESIGN.md §12): the table is split into per-kind **banks** —

* ``ReifLinLe``   (vidx/coef/rhs/bidx): reified linear inequalities, the
  paper's guarded-normal-form rows;
* ``AllDifferent`` (ad_vars/ad_offs/ad_mask): one row per alldifferent,
  filtered with Hall-interval bounds(Z) consistency;
* ``Cumulative``  (cu_svar/cu_dur/cu_dem/cu_cap): one row per cumulative,
  filtered with time-table (compulsory-part) reasoning; rows with
  optional members add a presence column (cu_pres, DESIGN.md §18) under
  the static flag ``cu_optional``.

Each bank gets its own variable-centric occurrence tables so every kind
joins into the store by pure gathers (TPU-native, no atomics); each bank
carries one trailing neutral dummy row that occurrence padding points at.

Each native bank also has a *sparse* tile (DESIGN.md §16) that replaces
the O(N³) dense Hall tensor and the dense ``[.., horizon]`` time grid at
scale.  The AllDifferent one runs over a **CSR-style packed view**: all
members of all rows concatenated along one packed axis (``ad_pk_*`` with
a segment id per member and ``ad_ptr`` row pointers).  The Cumulative
one reads the dense bank itself, each row a block of ``cu_width`` member
slots, and scans each row's events alone.  The layout each bank's *tile*
uses is chosen here at compile time (``bank_layout="auto"``): dense below
`DENSE_TILE_MAX_BYTES` of estimated per-lane sweep scratch, sparse above
— and the choice is a static field (`ad_layout`/`cu_layout`) that flows
into `api.shape_signature`, so cached runners never mix layouts.  Both
AllDifferent views are always emitted (the packed tables are O(model
size)); forcing ``bank_layout="dense"`` past `DENSE_TILE_HARD_BYTES`
raises instead of letting XLA/Mosaic OOM opaquely.

For the linear bank, two dual views of the same program are produced:

* **propagator-centric** (`vidx/coef/rhs/bidx`): one row per propagator —
  this is what a CUDA thread would execute; used by the scatter oracle
  (`kernels/ref.py`) and by the sequential baseline.
* **variable-centric** (`occ_prop/occ_slot`): for each variable, the list
  of (propagator, slot) occurrences that may tighten it — the TPU-native
  gather formulation used by the fixpoint engine and the Pallas kernel.
  Joins become per-variable min/max reductions: associativity of ⊔ makes
  the two views compute the same sweep (validated by tests).

Overflow policy: all candidate bounds are clamped into the *initial box*
``[lb0-1, ub0+1]`` (sound: a candidate outside the box still crosses the
opposite bound, so failure is preserved), and compile-time checks ensure
``Σ_j |a_j| · (max(|lb0_j|, |ub0_j|) + 1)`` fits the dtype with headroom.
Models that exceed int32 headroom are auto-promoted to int64.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitset import WORD_BITS, n_words_for
from repro.core.model import Model, ReifLinLe, TRUE_VAR

# slot code for "this occurrence is the reified boolean of the propagator"
# (stored as slot == K, one past the last term slot).


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---- dense-tile scratch estimates & layout crossover (DESIGN.md §16) ----
# Per-lane sweep scratch of the *dense* tiles, in bytes.  These are the
# allocations that explode with instance size (the bank tables themselves
# are O(model) and always emitted).  Above DENSE_TILE_MAX_BYTES the auto
# crossover flips the bank to the packed/segmented tile; a *forced* dense
# bank above DENSE_TILE_HARD_BYTES raises instead of OOMing inside
# XLA/Mosaic.  The same estimators feed `kernels.vmem_budget` and the
# `scale` bench section so guard, budget, and bench agree on one number.
DENSE_TILE_MAX_BYTES = 2 * 1024 * 1024
DENSE_TILE_HARD_BYTES = 64 * 1024 * 1024


def alldiff_dense_tile_bytes(n_alldiff: int, ad_width: int,
                             itemsize: int) -> int:
    """Per-lane scratch of `alldiff_candidates_tile`: the [A+1, N, N, N]
    `inside` tensor plus the cnt/width reductions (~3 live copies)."""
    if not n_alldiff:
        return 0
    return 3 * (n_alldiff + 1) * ad_width ** 3 * itemsize


def cumulative_dense_tile_bytes(n_cumulative: int, cu_width: int,
                                horizon: int, itemsize: int,
                                optional: bool = False) -> int:
    """Per-lane scratch of `cumulative_candidates_tile`: the
    [C+1, T, horizon] run/contrib/feas grids (~4 live copies), plus
    the presence reads and candidates of optional members ([C+1, T],
    ~4 live)."""
    if not n_cumulative:
        return 0
    opt = 4 * (n_cumulative + 1) * cu_width * itemsize if optional else 0
    return 4 * (n_cumulative + 1) * cu_width * horizon * itemsize + opt


def alldiff_sparse_tile_bytes(ad_packed: int, itemsize: int) -> int:
    """Per-lane scratch of `alldiff_candidates_sparse_tile`: a handful of
    [M, M] pairwise tensors over the packed member axis (~6 live)."""
    return 6 * ad_packed ** 2 * itemsize


def cumulative_sparse_tile_bytes(n_cumulative: int, cu_width: int,
                                 itemsize: int, optional: bool = False
                                 ) -> int:
    """Per-lane scratch of `cumulative_candidates_sparse_tile` over its
    M = (C+1)·T member slots, padding included: the row-blocked event
    arrays ([C+1, 2T], ~8 live), each row's events broadcast over its
    slots for the scans ([2T, M], ~3 live), plus the presence reads and
    candidates of optional members (~4 [M])."""
    if not n_cumulative:
        return 0
    slots = (n_cumulative + 1) * cu_width
    opt = 4 * slots * itemsize if optional else 0
    return (3 * 2 * cu_width + 16) * slots * itemsize + opt


def ct_tile_bytes(n_table: int, ct_arity: int, n_words: int,
                  ct_words: int) -> int:
    """Per-lane sweep scratch of `ct_candidates_tile` (DESIGN.md §17):
    the [T+1, R, 32W] member-value bits, the [T+1, R, 32W, TW] survivor
    intersection, and the OR-reduced support words (~3 live u32 copies).
    """
    if not n_table:
        return 0
    return 3 * (n_table + 1) * ct_arity * (32 * n_words) * ct_words * 4


def _resolve_layout(bank_layout: str, dense_bytes: int, kind: str,
                    name: str) -> str:
    """Pick this bank's tile layout; guard forced-dense explosions."""
    if dense_bytes == 0:        # bank absent — layout is inert
        return "dense"
    if bank_layout == "sparse":
        return "sparse"
    if bank_layout == "auto" and dense_bytes > DENSE_TILE_MAX_BYTES:
        return "sparse"
    # dense selected (forced, or auto under the crossover)
    if dense_bytes > DENSE_TILE_HARD_BYTES:
        raise ValueError(
            f"model '{name}': dense {kind} tile needs ~{dense_bytes:,} "
            f"bytes of per-lane sweep scratch (> {DENSE_TILE_HARD_BYTES:,}"
            " hard cap) — compile with bank_layout='sparse' (or 'auto') "
            "to use the packed segmented tile instead (DESIGN.md §16)")
    return "dense"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompiledModel:
    """Dense, fixed-shape program. All arrays are device-ready.

    Shapes: V vars, P props (+1 trailing dummy row), K padded terms,
    D padded occurrences per var, B branch vars.
    """

    # store init
    lb0: jax.Array          # i[V]
    ub0: jax.Array          # i[V]
    box_lo: jax.Array       # i[V]  = lb0 - 1 (clamp floor)
    box_hi: jax.Array       # i[V]  = ub0 + 1 (clamp ceil)
    # propagator-centric tables (row P is the neutral dummy)
    vidx: jax.Array         # i[P+1, K] var index per term (0 for padding)
    coef: jax.Array         # i[P+1, K] coefficient (0 for padding)
    rhs: jax.Array          # i[P+1]
    bidx: jax.Array         # i[P+1]   reif bool var (TRUE_VAR for plain)
    # variable-centric occurrence tables (padding points at dummy row, slot 0)
    occ_prop: jax.Array     # i[V, D]
    occ_slot: jax.Array     # i[V, D]  in [0, K]; K == reif-entailment slot
    # alldifferent bank (row A is the neutral dummy; DESIGN.md §12)
    ad_vars: jax.Array      # i[A+1, N]  member var index (0 for padding)
    ad_offs: jax.Array      # i[A+1, N]  member offset (x_i + off_i distinct)
    ad_mask: jax.Array      # i[A+1, N]  1 = real member, 0 = padding
    ad_occ_inst: jax.Array  # i[V, Dad]  alldiff row per occurrence
    ad_occ_pos: jax.Array   # i[V, Dad]  member position per occurrence
    # cumulative bank (row C is the neutral dummy)
    cu_svar: jax.Array      # i[C+1, T]  start var per task (0 for padding)
    cu_dur: jax.Array       # i[C+1, T]  duration (0 for padding)
    cu_dem: jax.Array       # i[C+1, T]  demand   (0 for padding)
    cu_cap: jax.Array       # i[C+1]     capacity
    # presence literal per member (TRUE_VAR for a mandatory one and for
    # padding); a [1, 1] dummy when no member is optional (cu_optional)
    cu_pres: jax.Array      # i[C+1, T]
    # occurrences of start vars at member positions and, with
    # cu_optional, of presence literals past them (DESIGN.md §18)
    cu_occ_inst: jax.Array  # i[V, Dcu]
    cu_occ_pos: jax.Array   # i[V, Dcu]
    # CSR-style packed view of the AllDifferent bank (DESIGN.md §16):
    # members of all rows concatenated (row-contiguous, so member (a, n)
    # sits at flat index ptr[a] + n and the dense occ tables double as
    # flat indices); padding slots carry seg == n_rows (the dummy).
    ad_ptr: jax.Array       # i[A+2]   row pointers into the packed axis
    ad_pk_var: jax.Array    # i[Mad]   packed member var index
    ad_pk_off: jax.Array    # i[Mad]   packed member offset
    ad_pk_seg: jax.Array    # i[Mad]   owning row; == A for padding
    # compact-table bank (row T is the neutral dummy; DESIGN.md §17):
    # supports are packed tuple bitsets per (member slot, value index k),
    # where k indexes value dom_off[var] + k in the bitset domain layout
    ct_vars: jax.Array      # i[T+1, R]          member var (0 for padding)
    ct_mask: jax.Array      # i[T+1, R]          1 = real member
    ct_supp: jax.Array      # u32[T+1, R, 32W, TW]  tuple bitset per value
    ct_occ_inst: jax.Array  # i[V, Dct]
    ct_occ_pos: jax.Array   # i[V, Dct]
    # bitset domain layout (DESIGN.md §17): value of bit k of var v is
    # dom_off[v] + k; vars wider than 32·n_words are untracked (their
    # words pinned to all-ones and never consulted)
    dom_off: jax.Array      # i[V]   per-var value offset (= lb0)
    dom_track: jax.Array    # u32[V] 1 = domain representable in n_words
    # search
    branch_vars: jax.Array  # i[B] decision vars in branching order
    # static metadata
    n_vars: int = dataclasses.field(metadata=dict(static=True))
    n_props: int = dataclasses.field(metadata=dict(static=True))
    k_terms: int = dataclasses.field(metadata=dict(static=True))
    d_occ: int = dataclasses.field(metadata=dict(static=True))
    n_alldiff: int = dataclasses.field(metadata=dict(static=True))
    ad_width: int = dataclasses.field(metadata=dict(static=True))
    ad_docc: int = dataclasses.field(metadata=dict(static=True))
    n_cumulative: int = dataclasses.field(metadata=dict(static=True))
    cu_width: int = dataclasses.field(metadata=dict(static=True))
    cu_docc: int = dataclasses.field(metadata=dict(static=True))
    horizon: int = dataclasses.field(metadata=dict(static=True))
    # tile layout per native bank ("dense" | "sparse") + packed length;
    # static so the choice shapes the trace and the runner cache key
    ad_layout: str = dataclasses.field(metadata=dict(static=True))
    cu_layout: str = dataclasses.field(metadata=dict(static=True))
    ad_packed: int = dataclasses.field(metadata=dict(static=True))
    # some Cumulative member is optional (has a presence literal): the
    # tiles then run the presence path and the bank carries cu_pres
    cu_optional: bool = dataclasses.field(metadata=dict(static=True))
    # compact-table / bitset statics (DESIGN.md §17)
    n_table: int = dataclasses.field(metadata=dict(static=True))
    ct_arity: int = dataclasses.field(metadata=dict(static=True))
    ct_words: int = dataclasses.field(metadata=dict(static=True))
    ct_docc: int = dataclasses.field(metadata=dict(static=True))
    n_words: int = dataclasses.field(metadata=dict(static=True))
    obj_var: int = dataclasses.field(metadata=dict(static=True))  # -1 if satisfaction
    dtype: str = dataclasses.field(metadata=dict(static=True))
    name: str = dataclasses.field(metadata=dict(static=True))

    @property
    def jdtype(self):
        return np.dtype(self.dtype)

    @property
    def total_props(self) -> int:
        """Propagator-table rows across all kinds (dummies excluded) —
        the count the §12/§17 bench/regression guards compare."""
        return self.n_props + self.n_alldiff + self.n_cumulative + self.n_table


def compile_model(
    m: Model,
    pad_terms_to: int = 8,
    pad_occ_to: int = 8,
    pad_horizon_to: int = 32,
    force_dtype: str | None = None,
    bank_layout: str = "auto",
) -> CompiledModel:
    if bank_layout not in ("auto", "dense", "sparse"):
        raise ValueError(
            f"bank_layout must be 'auto', 'dense' or 'sparse', "
            f"got {bank_layout!r}")
    V = m.n_vars
    props: List[ReifLinLe] = m.props
    P = len(props)
    if P == 0 and not (m.alldiffs or m.cumulatives or m.tables):
        raise ValueError("model has no constraints")

    K = max((len(p.lin.terms) for p in props), default=1)
    K = max(_round_up(K, pad_terms_to), pad_terms_to)

    lb0 = np.asarray(m.lb0, dtype=np.int64)
    ub0 = np.asarray(m.ub0, dtype=np.int64)

    vidx = np.zeros((P + 1, K), dtype=np.int64)
    coef = np.zeros((P + 1, K), dtype=np.int64)
    rhs = np.zeros((P + 1,), dtype=np.int64)
    bidx = np.full((P + 1,), TRUE_VAR, dtype=np.int64)

    occs: List[List[Tuple[int, int]]] = [[] for _ in range(V)]
    for p, rp in enumerate(props):
        terms = rp.lin.terms
        if len(terms) > K:
            raise ValueError("term overflow")
        for k, (v, a) in enumerate(terms):
            vidx[p, k] = v
            coef[p, k] = a
            occs[v].append((p, k))
        rhs[p] = rp.lin.rhs
        bidx[p] = rp.bvar
        if rp.bvar != TRUE_VAR:
            # genuinely reified: b can be tightened by (dis)entailment.
            occs[rp.bvar].append((p, K))
        # plain props (b == TRUE) fail through term tightening alone; we
        # skip their reif occurrence so the TRUE var's degree stays 0.

    # dummy row P: coef 0 everywhere -> all candidates neutral; rhs huge so
    # it is "entailed" but its reif slot is never gathered.
    rhs[P] = int(np.iinfo(np.int32).max // 4)

    D = max(max((len(o) for o in occs), default=1), 1)
    D = max(_round_up(D, pad_occ_to), pad_occ_to)
    occ_prop = np.full((V, D), P, dtype=np.int64)   # pad -> dummy row
    occ_slot = np.zeros((V, D), dtype=np.int64)     # pad -> term slot 0 (coef 0)
    for v, o in enumerate(occs):
        for d, (p, k) in enumerate(o):
            occ_prop[v, d] = p
            occ_slot[v, d] = k

    # ---- alldifferent bank (DESIGN.md §12) -----------------------------
    A = len(m.alldiffs)
    N = max((len(ad.vars) for ad in m.alldiffs), default=2)
    N = max(_round_up(N, 4), 2) if A else 2
    ad_vars = np.zeros((A + 1, N), dtype=np.int64)
    ad_offs = np.zeros((A + 1, N), dtype=np.int64)
    ad_mask = np.zeros((A + 1, N), dtype=np.int64)
    ad_occs: List[List[Tuple[int, int]]] = [[] for _ in range(V)]
    for a, ad in enumerate(m.alldiffs):
        for n, (v, off) in enumerate(zip(ad.vars, ad.offsets)):
            ad_vars[a, n] = v
            ad_offs[a, n] = off
            ad_mask[a, n] = 1
            ad_occs[v].append((a, n))
    Dad = max(max((len(o) for o in ad_occs), default=1), 1)
    Dad = _round_up(Dad, 4) if A else 1
    ad_occ_inst = np.full((V, Dad), A, dtype=np.int64)   # pad -> dummy row
    ad_occ_pos = np.zeros((V, Dad), dtype=np.int64)
    for v, o in enumerate(ad_occs):
        for d, (a, n) in enumerate(o):
            ad_occ_inst[v, d] = a
            ad_occ_pos[v, d] = n

    # packed (CSR) view: row-contiguous members; always ≥ 1 padding slot
    # so the dummy occurrence (inst=A, pos=0) lands at flat ad_ptr[A]
    mad_real = sum(len(ad.vars) for ad in m.alldiffs)
    Mad = max(_round_up(mad_real + 1, 8), 8)
    ad_ptr = np.zeros((A + 2,), dtype=np.int64)
    ad_pk_var = np.zeros((Mad,), dtype=np.int64)
    ad_pk_off = np.zeros((Mad,), dtype=np.int64)
    ad_pk_seg = np.full((Mad,), A, dtype=np.int64)
    k_ = 0
    for a, ad in enumerate(m.alldiffs):
        ad_ptr[a] = k_
        for v, off in zip(ad.vars, ad.offsets):
            ad_pk_var[k_] = v
            ad_pk_off[k_] = off
            ad_pk_seg[k_] = a
            k_ += 1
    ad_ptr[A] = k_          # padding region start
    ad_ptr[A + 1] = Mad

    # ---- cumulative bank (DESIGN.md §12) -------------------------------
    C = len(m.cumulatives)
    T = max((len(cu.starts) for cu in m.cumulatives), default=2)
    T = max(_round_up(T, 4), 2) if C else 2
    cu_svar = np.zeros((C + 1, T), dtype=np.int64)
    cu_dur = np.zeros((C + 1, T), dtype=np.int64)
    cu_dem = np.zeros((C + 1, T), dtype=np.int64)
    cu_cap = np.zeros((C + 1,), dtype=np.int64)
    cu_optional = any(cu.presence is not None
                      and any(p != TRUE_VAR for p in cu.presence)
                      for cu in m.cumulatives)
    cu_pres = np.full((C + 1, T) if cu_optional else (1, 1), TRUE_VAR,
                      dtype=np.int64)
    cu_occs: List[List[Tuple[int, int]]] = [[] for _ in range(V)]
    # presence-literal occurrences (var, row, member), placed after the
    # tile layout is known: they index past the start candidates
    pres_occs: List[Tuple[int, int, int]] = []
    horizon = 1
    for c, cu in enumerate(m.cumulatives):
        if cu.capacity < 0:
            # the segmented profile only inspects event intervals, so a
            # negative cap (0 > cap on empty time) would need the whole
            # grid; dense fails everywhere — reject the degenerate model
            raise ValueError(
                f"cumulative row {c} has negative capacity "
                f"{cu.capacity}; capacities must be >= 0")
        cu_cap[c] = cu.capacity
        pres = cu.presence or (TRUE_VAR,) * len(cu.starts)
        for t, (v, d_, r_, p_) in enumerate(zip(cu.starts, cu.durations,
                                                cu.demands, pres)):
            cu_svar[c, t] = v
            cu_dur[c, t] = d_
            cu_dem[c, t] = r_
            if cu_optional:
                cu_pres[c, t] = p_
            if d_ > 0 and r_ > 0 and p_ != TRUE_VAR:
                pres_occs.append((p_, c, t))
            if d_ > 0 and r_ > 0:
                if int(lb0[v]) < 0:
                    # the time-table grid is [0, horizon); a negative
                    # feasible start would be silently pruned (wrong
                    # UNSAT) — demand a shifted model instead
                    raise ValueError(
                        f"cumulative start var {v} has negative domain "
                        f"({int(lb0[v])}, {int(ub0[v])}); native time-table "
                        "filtering needs nonnegative starts — shift the "
                        "model (or use decompose=True)")
                # only effective tasks are ever tightened by the row
                cu_occs[v].append((c, t))
                horizon = max(horizon, int(ub0[v]) + d_ + 2)
    # bucket the (static, trace-shaping) time grid so same-family
    # instances across seeds keep one shape signature (api.py cache /
    # solve_many; same spirit as the pool pow2 buckets, DESIGN.md §11)
    if C:
        horizon = _round_up(horizon, pad_horizon_to)

    # ---- compact-table bank + bitset domain layout (DESIGN.md §17) ------
    branch = list(m.branch_order) if m.branch_order else list(range(1, V))
    # ensure every non-fixed var is ultimately branchable: append leftovers
    missing = [v for v in range(1, V) if v not in set(branch)]
    branch = branch + missing

    Tn = len(m.tables)
    R = max((len(t.vars) for t in m.tables), default=1)
    widths = ub0 - lb0 + 1
    # With tables, n_words covers every table member AND every branch
    # var (tables need the member domains as bitsets; covering the
    # branch vars too lets middle-out track them for free — table
    # models' bank shapes are instance-dependent anyway).  WITHOUT
    # tables n_words is pinned to 1 so same-shaped instances keep
    # hitting the compiled-runner cache regardless of their bounds;
    # middle-out leaves vars wider than 32 values untracked, where its
    # selection and branching degrade per-var to exactly VAL_SPLIT
    # (pinned all-ones words put the nearest remaining value at the
    # interval midpoint, and apply_path_tile tells x ≥ m+1 instead of
    # a bit clear).
    if Tn:
        dom_vars = sorted({v for t in m.tables for v in t.vars}
                          | set(branch))
        n_words = n_words_for(int(widths[dom_vars].max()))
    else:
        n_words = 1
    K32 = WORD_BITS * n_words
    maxT = max((len(t.tuples) for t in m.tables), default=1)
    TW = max(1, -(-maxT // WORD_BITS))
    ct_vars = np.zeros((Tn + 1, R), dtype=np.int64)
    ct_mask = np.zeros((Tn + 1, R), dtype=np.int64)
    ct_supp = np.zeros((Tn + 1, R, K32, TW), dtype=np.uint32)
    ct_occs: List[List[Tuple[int, int]]] = [[] for _ in range(V)]
    for ti, tb in enumerate(m.tables):
        for r, v in enumerate(tb.vars):
            ct_vars[ti, r] = v
            ct_mask[ti, r] = 1
            ct_occs[v].append((ti, r))
        for j, tup in enumerate(tb.tuples):
            for r, (v, val) in enumerate(zip(tb.vars, tup)):
                k = int(val) - int(lb0[v])  # in [0, width) by Model.table
                ct_supp[ti, r, k, j // WORD_BITS] |= (
                    np.uint32(1) << np.uint32(j % WORD_BITS))
    Dct = max(max((len(o) for o in ct_occs), default=1), 1)
    Dct = _round_up(Dct, 4) if Tn else 1
    ct_occ_inst = np.full((V, Dct), Tn, dtype=np.int64)  # pad -> dummy row
    ct_occ_pos = np.zeros((V, Dct), dtype=np.int64)
    for v, o in enumerate(ct_occs):
        for d, (ti, r) in enumerate(o):
            ct_occ_inst[v, d] = ti
            ct_occ_pos[v, d] = r
    dom_track = (widths <= K32).astype(np.uint32)

    # ---- dtype selection with overflow headroom ------------------------
    absmax = np.maximum(np.abs(lb0), np.abs(ub0)) + 1           # per var
    worst = int((np.abs(coef[:P]) * absmax[vidx[:P]]).sum(axis=1).max()) \
        if P else 0
    worst = max(worst, int(np.abs(rhs[:P]).max()) if P else 0)
    # native banks: shifted alldiff values x+off (±1 Hall push), cumulative
    # time points up to `horizon` and per-row demand sums
    if A:
        worst = max(worst, int((absmax[ad_vars[:A]] + np.abs(ad_offs[:A])
                                ).max()) + 2)
    if C:
        worst = max(worst, horizon + 2,
                    int(cu_dem[:C].sum(axis=1).max()), int(cu_cap[:C].max()))
    # the sparse AllDifferent tile compares member *counts* against
    # interval widths
    worst = max(worst, Mad)
    # bitset hull bridge: an empty tracked domain reads back as
    # (off + 32·n_words, off - 1)
    worst = max(worst, int(np.abs(lb0).max()) + K32 + 2)
    if force_dtype is not None:
        dtype = force_dtype
    elif worst * 4 < np.iinfo(np.int32).max:
        dtype = "int32"
    else:
        dtype = "int64"
    if worst * 4 >= np.iinfo(np.int64).max:
        raise OverflowError("model exceeds int64 headroom")

    if dtype == "int64" and not jax.config.jax_enable_x64:
        raise OverflowError(
            f"model '{m.name}' needs int64 headroom (worst sum {worst}); "
            "set JAX_ENABLE_X64=1 or pass force_dtype after re-scaling")

    # ---- per-bank tile layout (decided after dtype: bytes need itemsize)
    itemsize = np.dtype(dtype).itemsize
    ad_layout = _resolve_layout(
        bank_layout, alldiff_dense_tile_bytes(A, N, itemsize),
        "AllDifferent", m.name)
    cu_layout = _resolve_layout(
        bank_layout, cumulative_dense_tile_bytes(C, T, horizon, itemsize,
                                                 cu_optional),
        "Cumulative", m.name)
    # presence candidates follow the start candidates of either tile, at
    # member position T + t of the row
    for p_, c, t in pres_occs:
        cu_occs[p_].append((c, T + t))
    Dcu = max(max((len(o) for o in cu_occs), default=1), 1)
    Dcu = _round_up(Dcu, 4) if C else 1
    cu_occ_inst = np.full((V, Dcu), C, dtype=np.int64)   # pad -> dummy row
    cu_occ_pos = np.zeros((V, Dcu), dtype=np.int64)
    for v, o in enumerate(cu_occs):
        for d, (c, t) in enumerate(o):
            cu_occ_inst[v, d] = c
            cu_occ_pos[v, d] = t
    # leaves are jnp so the tables work when closed over (not jit args)
    cast = lambda a: jnp.asarray(np.asarray(a, dtype=dtype))  # noqa: E731
    return CompiledModel(
        lb0=cast(lb0), ub0=cast(ub0),
        box_lo=cast(lb0 - 1), box_hi=cast(ub0 + 1),
        vidx=cast(vidx), coef=cast(coef), rhs=cast(rhs), bidx=cast(bidx),
        occ_prop=cast(occ_prop), occ_slot=cast(occ_slot),
        ad_vars=cast(ad_vars), ad_offs=cast(ad_offs), ad_mask=cast(ad_mask),
        ad_occ_inst=cast(ad_occ_inst), ad_occ_pos=cast(ad_occ_pos),
        cu_svar=cast(cu_svar), cu_dur=cast(cu_dur), cu_dem=cast(cu_dem),
        cu_cap=cast(cu_cap), cu_pres=cast(cu_pres),
        cu_occ_inst=cast(cu_occ_inst), cu_occ_pos=cast(cu_occ_pos),
        ad_ptr=cast(ad_ptr), ad_pk_var=cast(ad_pk_var),
        ad_pk_off=cast(ad_pk_off), ad_pk_seg=cast(ad_pk_seg),
        ct_vars=cast(ct_vars), ct_mask=cast(ct_mask),
        ct_supp=jnp.asarray(ct_supp),
        ct_occ_inst=cast(ct_occ_inst), ct_occ_pos=cast(ct_occ_pos),
        dom_off=cast(lb0), dom_track=jnp.asarray(dom_track),
        branch_vars=cast(np.asarray(branch)),
        n_vars=V, n_props=P, k_terms=K, d_occ=D,
        n_alldiff=A, ad_width=N, ad_docc=Dad,
        n_cumulative=C, cu_width=T, cu_docc=Dcu, horizon=horizon,
        ad_layout=ad_layout, cu_layout=cu_layout,
        ad_packed=Mad, cu_optional=cu_optional,
        n_table=Tn, ct_arity=R, ct_words=TW, ct_docc=Dct, n_words=n_words,
        obj_var=(m.objective if m.objective is not None else -1),
        dtype=dtype, name=m.name,
    )
