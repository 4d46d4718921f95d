"""Eventless parallel fixpoint engine (paper §"Fixed point loop").

One *sweep* executes **every** propagator once and joins all their tells
into the store — this is the denotational parallel composition
``D(P₁) ⊔ … ⊔ D(Pₙ)`` realized as one bulk-synchronous tensor program
(the TPU analogue of the paper's AC-1-style loop; the `lax.while_loop`
carry of a single `changed` flag replaces the rotating ``has_changed[3]``
+ ``__syncthreads()`` scheme, because a BSP step *is* a barrier).

The sweep is *variable-centric* (gather form): each variable reduces over
the candidate bounds of all its occurrences.  Associativity/commutativity
of ⊔ makes this equal to the propagator-centric scatter form
(`kernels/ref.py` oracle), which is itself equal to any fair sequential
chaotic iteration by the paper's Prop. 3 / Thm. 6 — both equalities are
property-tested in `tests/test_semantics.py`.

Propagator semantics for row  b ⇔ Σ_j a_j·x_j ≤ c :

  ask  lb(b) ≥ 1  (b told true):   for each term k,
       slack_k = c - (Smin - min(a_k x_k));
       a_k > 0 → tell x_k ≤ ⌊slack_k / a_k⌋
       a_k < 0 → tell x_k ≥ ⌈slack_k / a_k⌉
  ask  ub(b) ≤ 0  (b told false):  propagate Σ -a_j x_j ≤ -c-1 (negation)
  entailment:   Smax ≤ c  → tell b ≥ 1  ;  Smin > c → tell b ≤ 0
       (paper's `entailed` function, via Lemma 1 monotonicity)

Candidates are clamped into the initial box (see compile.py) so all
arithmetic provably stays in dtype range.

There is exactly **one** implementation of the propagator semantics per
*kind* (the typed propagator table, DESIGN.md §12): `candidates_tile`
(ReifLinLe), `alldiff_candidates_tile` (Hall-interval bounds(Z)
consistency) and `cumulative_candidates_tile` (time-table filtering),
all written over raw tables and lane-batched ``[L, V]`` stores and
dispatched by `sweep_tile` in a fixed kind order.  Everything else — the
single-store `sweep`, the scatter oracle, the lane-batched
`fixpoint_batch` used by the search superstep, and the Pallas VMEM
kernel (`kernels/fixpoint_kernel.py` imports `sweep_tile`) — is a thin
wrapper around these tiles (DESIGN.md §2.3), so all three backends run
the same kind semantics verbatim and stay bit-identical.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitset as B
from repro.core.compile import CompiledModel
from repro.core.model import TRUE_VAR


def _neutrals(dtype):
    big = jnp.asarray(jnp.iinfo(dtype).max // 4, dtype)
    return big, -big   # NEU_UB, NEU_LB


def _fdiv(p, q):
    return jnp.floor_divide(p, q)


def _cdiv(p, q):
    return -jnp.floor_divide(-p, q)


def candidates_tile(lb: jax.Array, ub: jax.Array, vidx, coef, rhs, bidx
                    ) -> Tuple[jax.Array, jax.Array]:
    """All tells of one sweep for a ``[L, V]`` tile of stores.

    Pure-array form (no `CompiledModel`) so the Pallas kernel body can call
    it on VMEM refs; every other propagation path wraps it.  Returns
    (cand_lb, cand_ub), each ``[L, P+1, K+1]``; slot K is the
    reified-boolean (entailment) slot.  Neutral candidates are ±big so
    they vanish under the min/max joins.
    """
    dt = lb.dtype
    a = coef[None, :, :]                                  # [1, P1, K]
    c = rhs[None, :, None]                                # [1, P1, 1]
    xl = jnp.take(lb, vidx, axis=1)                       # [L, P1, K]
    xu = jnp.take(ub, vidx, axis=1)
    tl = jnp.where(a > 0, a * xl, a * xu)     # min of a_k x_k (0 when a==0)
    tu = jnp.where(a > 0, a * xu, a * xl)     # max of a_k x_k
    smin = tl.sum(-1)                                     # [L, P1]
    smax = tu.sum(-1)

    btrue = (jnp.take(lb, bidx, axis=1) >= 1)[:, :, None]     # ask b
    bfalse = (jnp.take(ub, bidx, axis=1) <= 0)[:, :, None]    # ask ¬b

    neu_ub, neu_lb = _neutrals(dt)
    safe_a = jnp.where(a == 0, 1, a)

    # direction 1: Σ a x ≤ c (guard: b true)
    slack1 = c - (smin[:, :, None] - tl)
    ub1 = jnp.where((a > 0) & btrue, _fdiv(slack1, safe_a), neu_ub)
    lb1 = jnp.where((a < 0) & btrue, _cdiv(slack1, safe_a), neu_lb)

    # direction 2: Σ -a x ≤ -c-1 (guard: b false); with a' = -a:
    #   min(a' x) = -max(a x) = -tu ;  S'min = -smax
    na = -a
    safe_na = jnp.where(na == 0, 1, na)
    slack2 = (-c - 1) - (-smax[:, :, None] + tu)
    ub2 = jnp.where((na > 0) & bfalse, _fdiv(slack2, safe_na), neu_ub)
    lb2 = jnp.where((na < 0) & bfalse, _cdiv(slack2, safe_na), neu_lb)

    term_ub = jnp.minimum(ub1, ub2)           # [L, P1, K]
    term_lb = jnp.maximum(lb1, lb2)

    # entailment slot (tells on the reified boolean)
    one = jnp.asarray(1, dt)
    zero = jnp.asarray(0, dt)
    reif_lb = jnp.where(smax <= rhs[None, :], one, neu_lb)   # entailed → b≥1
    reif_ub = jnp.where(smin > rhs[None, :], zero, neu_ub)   # disent. → b≤0

    cand_ub = jnp.concatenate([term_ub, reif_ub[:, :, None]], axis=2)
    cand_lb = jnp.concatenate([term_lb, reif_lb[:, :, None]], axis=2)
    return cand_lb, cand_ub


def alldiff_candidates_tile(lb, ub, ad_vars, ad_offs, ad_mask
                            ) -> Tuple[jax.Array, jax.Array]:
    """Bounds(Z)-consistency tells for the AllDifferent bank
    (kind-dispatched sweep variant, DESIGN.md §12).

    Pure-array form over a ``[L, V]`` tile; shared verbatim by all three
    backends.  Hall-interval reasoning on the shifted views
    ``y_k = x_k + off_k``: for every endpoint pair (i, j) the interval
    ``I = [yl_i, yu_j]`` is tested —

      |{k : dom(y_k) ⊆ I}| > |I|  →  fail (some member pushed past its
                                     box, which crosses its bounds);
      |{k : dom(y_k) ⊆ I}| = |I|  →  I is a Hall interval: every other
                                     member's bound inside I is pushed
                                     out (lb → sup I + 1, ub → inf I - 1).

    Iterated to fixpoint this is exactly bounds(Z) consistency (all
    candidate Hall intervals have lb endpoints as infima and ub endpoints
    as suprema).  Returns (cand_lb, cand_ub), each ``[L, A1, N]``, in
    *unshifted* variable space; padded members and the dummy row A are
    neutral.
    """
    dt = lb.dtype
    neu_ub, neu_lb = _neutrals(dt)
    msk = (ad_mask[None] != 0)                              # [1, A1, N]
    off = ad_offs[None]
    yl = jnp.take(lb, ad_vars, axis=1) + off                # [L, A1, N]
    yu = jnp.take(ub, ad_vars, axis=1) + off
    a = yl[:, :, :, None]                    # interval inf from i  [L,A1,N,1]
    b = yu[:, :, None, :]                    # interval sup from j  [L,A1,1,N]
    pair_ok = msk[:, :, :, None] & msk[:, :, None, :] & (a <= b)
    inside = (msk[:, :, None, None, :]
              & (yl[:, :, None, None, :] >= a[..., None])
              & (yu[:, :, None, None, :] <= b[..., None]))  # [L,A1,N,N,N]
    cnt = inside.sum(-1).astype(dt)                         # [L, A1, N, N]
    width = b - a + 1
    overflow = pair_ok & (cnt > width)
    hall = pair_ok & (cnt == width)

    # Hall pruning: member k outside I with a bound inside I is pushed out
    out_k = msk[:, :, None, None, :] & ~inside
    a5, b5 = a[..., None], b[..., None]
    klb, kub = yl[:, :, None, None, :], yu[:, :, None, None, :]
    push = hall[..., None]
    lb_cand = jnp.where(push & out_k & (klb >= a5) & (klb <= b5),
                        b5 + 1, neu_lb)                     # [L,A1,N,N,N]
    ub_cand = jnp.where(push & out_k & (kub >= a5) & (kub <= b5),
                        a5 - 1, neu_ub)
    cand_lb = lb_cand.max(axis=(2, 3))                      # [L, A1, N]
    cand_ub = ub_cand.min(axis=(2, 3))

    # pigeonhole overflow: the row is unsatisfiable — fail every member
    # (lb pushed to +big; the box clamp keeps it at box_hi, crossing ub)
    fail = overflow.any(axis=(2, 3))                        # [L, A1]
    cand_lb = jnp.where(fail[:, :, None] & msk, -neu_lb, cand_lb)
    # back to unshifted variable space (neutrals stay effectively neutral)
    return cand_lb - off, cand_ub - off


def _presence(lb, ub, pres, act):
    """(present, undecided) masks of Cumulative members from their
    presence literals: present when ``lb(p) = 1``, undecided while
    ``lb(p) = 0 < ub(p)``; only effective members (``act``) count."""
    with jax.named_scope("tile.cumulative.optional"):
        plb = jnp.take(lb, pres, axis=1)
        pub = jnp.take(ub, pres, axis=1)
        return act & (plb >= 1), act & (plb < 1) & (pub >= 1)


def _presence_tells(undecided, empty, neu_lb, neu_ub):
    """Presence candidates: ``ub(p) = 0`` for an undecided member whose
    start window holds no feasible start (DESIGN.md §18); neutral
    otherwise."""
    with jax.named_scope("tile.cumulative.optional"):
        zero = jnp.zeros((), neu_ub.dtype)
        return (jnp.broadcast_to(neu_lb, undecided.shape),
                jnp.where(undecided & empty, zero, neu_ub))


def cumulative_candidates_tile(lb, ub, cu_svar, cu_dur, cu_dem, cu_cap,
                               horizon: int, cu_pres=None
                               ) -> Tuple[jax.Array, jax.Array]:
    """Time-table tells for the Cumulative bank (kind-dispatched sweep
    variant, DESIGN.md §12).

    Pure-array form over a ``[L, V]`` tile; shared verbatim by all three
    backends.  Classic compulsory-part reasoning on the dense time grid
    ``t ∈ [0, horizon)`` (horizon is a compile-time static):

      * task t's compulsory part is ``[lst_t, est_t + d_t)`` (nonempty
        iff lst_t < est_t + d_t);
      * profile(τ) = Σ demands of compulsory parts covering τ;
        profile(τ) > cap → fail the row;
      * task t cannot *start* at s if some τ ∈ [s, s+d_t) has
        profile₋t(τ) + r_t > cap; its lb (ub) moves to the first (last)
        feasible start ≥ est_t (≤ lst_t).

    Returns (cand_lb, cand_ub), each ``[L, C1, T]``; zero-duration /
    zero-demand tasks and the dummy row C are neutral.  Monotone: shrink
    the domains and compulsory parts only grow, so feasible starts only
    shrink (a propagator in the paper's Lemma-1 sense).

    With ``cu_pres`` (``[C1, T]`` presence literals, DESIGN.md §18) a
    member adds its compulsory part and has its start pruned only once
    present; an undecided member whose window ``[est, lst]`` holds no
    feasible start gets ``ub(p) = 0``.  The candidates then come back
    ``[L, C1, 2T]``: the presence tells at ``T + t``.
    """
    dt = lb.dtype
    neu_ub, neu_lb = _neutrals(dt)
    est = jnp.take(lb, cu_svar, axis=1)                     # [L, C1, T]
    lst = jnp.take(ub, cu_svar, axis=1)
    d = cu_dur[None]
    q = cu_dem[None]
    act = (d > 0) & (q > 0)
    present = act
    if cu_pres is not None:
        present, undecided = _presence(lb, ub, cu_pres, act)
    cap = cu_cap[None, :, None]                             # [1, C1, 1]
    tgrid = jnp.arange(horizon, dtype=dt)                   # [H]
    run = (present[..., None] & (lst[..., None] <= tgrid)
           & (tgrid < (est + d)[..., None]))                # [L, C1, T, H]
    contrib = jnp.where(run, q[..., None], jnp.asarray(0, dt))
    profile = contrib.sum(axis=2)                           # [L, C1, H]
    overload = (profile > cap).any(-1)                      # [L, C1]

    # per-task residual profile and forbidden time points
    bad = (act[..., None]
           & (profile[:, :, None, :] - contrib + q[..., None] > cap[..., None]))
    csum = jnp.cumsum(bad.astype(dt), axis=-1)
    csum = jnp.concatenate(
        [jnp.zeros_like(csum[..., :1]), csum], axis=-1)     # [L, C1, T, H+1]
    ends = jnp.clip(tgrid[None, None, None, :] + d[..., None], 0, horizon)
    wbad = (jnp.take_along_axis(csum, ends.astype(jnp.int32), axis=-1)
            - csum[..., :-1])                               # [L, C1, T, H]
    feas = wbad == 0                                        # start grid feas.

    first = jnp.where(feas & (tgrid >= est[..., None]), tgrid,
                      -neu_lb).min(-1)                      # first feasible
    cand_ub = jnp.where(feas & (tgrid <= lst[..., None]), tgrid,
                        -neu_ub).max(-1)                    # last feasible
    cand_lb = jnp.where(present, first, neu_lb)
    cand_ub = jnp.where(present, cand_ub, neu_ub)
    # overload: fail every present task of the row
    cand_lb = jnp.where(overload[:, :, None] & present, -neu_lb, cand_lb)
    if cu_pres is None:
        return cand_lb, cand_ub
    p_lb, p_ub = _presence_tells(undecided, first > lst, neu_lb, neu_ub)
    return (jnp.concatenate([cand_lb, p_lb], axis=2),
            jnp.concatenate([cand_ub, p_ub], axis=2))


def alldiff_candidates_sparse_tile(lb, ub, ad_pk_var, ad_pk_off, ad_pk_seg,
                                   n_alldiff: int
                                   ) -> Tuple[jax.Array, jax.Array]:
    """Segmented (packed/CSR) Hall-interval pass — the scale variant of
    `alldiff_candidates_tile` (DESIGN.md §16).

    Same bounds(Z) semantics, O(M²) scratch instead of O(A·N³): members
    of ALL rows live on one packed axis of length M with a segment id
    each (padding slots carry seg == n_alldiff and stay inert).  Members
    are lexsorted by (segment, lb endpoint); the count
    ``|{k : dom(y_k) ⊆ [a_i, b_j]}|`` then becomes a reversed-cumsum
    suffix lookup: with T[p, j] = [seg_p = seg_j ∧ yu_p ≤ yu_j] and
    S = suffix-sum of T over p, cnt(i, j) = S[first_pos(i), j] where
    first_pos counts strictly-smaller (seg, yl) keys — tie-invariant, so
    the (unstable) sort cannot affect results and every backend stays
    bit-identical.  Hall intervals are folded to two O(M) extremal
    tables (min inf per sup; max sup per inf) before the push pass, so
    no O(M³) tensor is ever built.  Bit-equal to the dense tile per
    member on non-failed stores (the only stores the engines sweep).

    Returns (cand_lb, cand_ub), each ``[L, M]`` over the packed axis in
    *unshifted* variable space.
    """
    dt = lb.dtype
    neu_ub, neu_lb = _neutrals(dt)
    off = ad_pk_off[None]                                   # [1, M]
    yl = jnp.take(lb, ad_pk_var, axis=1) + off              # [L, M]
    yu = jnp.take(ub, ad_pk_var, axis=1) + off
    segb = jnp.broadcast_to(ad_pk_seg[None], yl.shape)

    perm = jnp.lexsort((yl, segb), axis=-1)                 # seg-major, then yl
    inv = jnp.argsort(perm, axis=-1)
    syl = jnp.take_along_axis(yl, perm, axis=1)
    syu = jnp.take_along_axis(yu, perm, axis=1)
    sseg = jnp.take_along_axis(segb, perm, axis=1)
    sact = sseg < n_alldiff

    same = sseg[:, :, None] == sseg[:, None, :]             # [L, M, M]
    a_i = syl[:, :, None]               # interval inf from i (axis 1)
    b_j = syu[:, None, :]               # interval sup from j (axis 2)

    # suffix count: S[p, j] = |{x ≥ p : seg_x = seg_j ∧ yu_x ≤ yu_j}|
    T = (same & (syu[:, :, None] <= syu[:, None, :])).astype(dt)
    S = jnp.flip(jnp.cumsum(jnp.flip(T, axis=1), axis=1), axis=1)
    # first sorted position of i's key = |{p : (seg_p, yl_p) < (seg_i, yl_i)}|
    lt = ((sseg[:, None, :] < sseg[:, :, None])
          | (same & (syl[:, None, :] < syl[:, :, None])))   # [L, i, p]
    fp = lt.sum(axis=2).astype(jnp.int32)                   # [L, M]
    cnt = jnp.take_along_axis(
        S, jnp.broadcast_to(fp[:, :, None], S.shape), axis=1)  # [L, i, j]

    pair_ok = same & sact[:, :, None] & sact[:, None, :] & (a_i <= b_j)
    width = b_j - a_i + 1
    overflow = pair_ok & (cnt > width)
    hall = pair_ok & (cnt == width)

    # extremal Hall data: tightest inf per sup endpoint j, and widest sup
    # per inf endpoint i — all O(M) per lane after the fold
    min_inf = jnp.where(hall, jnp.broadcast_to(a_i, hall.shape),
                        neu_ub).min(axis=1)                 # [L, M] per j
    max_sup = jnp.where(hall, jnp.broadcast_to(b_j, hall.shape),
                        neu_lb).max(axis=2)                 # [L, M] per i

    # lb push for member k: ∃ Hall I = [a_i, b_j] with a_i ≤ yl_k ≤ b_j < yu_k
    #   ⇔ ∃j same-seg: min_inf_j ≤ yl_k ≤ b_j < yu_k   → yl_k ↦ b_j + 1
    yl_k, yu_k = syl[:, :, None], syu[:, :, None]           # k on axis 1
    s_lb = jnp.where(same & sact[:, :, None]
                     & (min_inf[:, None, :] <= yl_k)
                     & (yl_k <= b_j) & (b_j < yu_k),
                     b_j + 1, neu_lb).max(axis=2)           # [L, M]
    # ub push, mirrored: yl_k < a_i ≤ yu_k ≤ max_sup_i  → yu_k ↦ a_i - 1
    a_i2 = syl[:, None, :]                                  # i on axis 2
    s_ub = jnp.where(same & sact[:, :, None]
                     & (yl_k < a_i2) & (a_i2 <= yu_k)
                     & (yu_k <= max_sup[:, None, :]),
                     a_i2 - 1, neu_ub).min(axis=2)

    # pigeonhole overflow fails every member of the affected row
    rowfail = overflow.any(axis=2)                          # [L, M] per i
    failk = jnp.any(same & rowfail[:, None, :], axis=2)     # [L, M] per k
    s_lb = jnp.where(failk & sact, -neu_lb, s_lb)

    # unsort to packed order, then back to unshifted variable space
    cand_lb = jnp.take_along_axis(s_lb, inv, axis=1) - off
    cand_ub = jnp.take_along_axis(s_ub, inv, axis=1) - off
    return cand_lb, cand_ub


def cumulative_candidates_sparse_tile(lb, ub, cu_svar, cu_dur, cu_dem,
                                      cu_cap, cu_pres=None
                                      ) -> Tuple[jax.Array, jax.Array]:
    """Event-based time-table pass — the scale variant of
    `cumulative_candidates_tile` (DESIGN.md §16).

    Same compulsory-part semantics over the same ``[C1, T]`` bank, but
    never materialises the ``[.., T, horizon]`` grid.  Each row is a
    block of T member slots.  Every slot emits two events, +q at lst
    and −q at ect (0 without a compulsory part), and each row's 2T
    events are sorted by time within the row, the row's ineffective
    slots (padding, zero duration or demand) last.  The row's cumsum
    is then its piecewise-constant profile, and consecutive effective
    events bound disjoint constant-profile intervals [u, v); the row's
    last effective event and every ineffective one own an empty
    interval, which the u < v guard disables.  Events at one time need
    no order, ends and starts alike: all but the last of them own empty
    [t, t) intervals, and the last carries the same prefix sum whatever
    the order.

    Overload is tested per row.  A member's forbidden windows come only
    from its own row's events, so the first (last) feasible start of
    every member is one forward (backward) scan of 2T steps with a
    monotone jump carry — single-pass exact because the intervals are
    disjoint and sorted.  All rows scan at once on the flat ``[L, C1·T]``
    slot axis, each row's event broadcast over its T slots, and both
    scans share one loop.  Bit-equal to the dense tile per task on
    non-failed stores.

    Returns (cand_lb, cand_ub) of the dense tile's shapes, each
    ``[L, C1, T]``; with ``cu_pres`` (presence literals, DESIGN.md §18)
    each ``[L, C1, 2T]``, the presence tells at ``T + t`` — the same
    optional semantics as the dense tile.
    """
    dt = lb.dtype
    neu_ub, neu_lb = _neutrals(dt)
    zero = jnp.asarray(0, dt)
    L = lb.shape[0]
    C1, T = cu_svar.shape
    M = C1 * T                                              # slots

    def rows(a):                                    # [.., M] → [.., C1, T]
        return a.reshape(a.shape[:-1] + (C1, T))

    d = cu_dur.reshape(1, M)
    q = cu_dem.reshape(1, M)
    act = (d > 0) & (q > 0)
    present = act
    if cu_pres is not None:
        present, undecided = _presence(lb, ub, cu_pres.reshape(-1), act)
    cap = jnp.repeat(cu_cap, T)[None]                       # [1, M] per slot
    est = jnp.take(lb, cu_svar.reshape(-1), axis=1)         # [L, M]
    lst = jnp.take(ub, cu_svar.reshape(-1), axis=1)
    ect = est + d
    has_cp = present & (lst < ect)                          # compulsory part

    times = jnp.concatenate([rows(lst), rows(ect)], axis=2)  # [L, C1, 2T]
    delta = jnp.concatenate([rows(jnp.where(has_cp, q, zero)),
                             rows(jnp.where(has_cp, -q, zero))], axis=2)
    eff = jnp.broadcast_to(
        jnp.concatenate([rows(act)] * 2, axis=2), times.shape)
    perm = jnp.lexsort((times, (~eff).astype(jnp.int32)), axis=-1)
    stime = jnp.take_along_axis(times, perm, axis=2)
    sdelta = jnp.take_along_axis(delta, perm, axis=2)
    seff = jnp.take_along_axis(eff, perm, axis=2)
    prof = jnp.cumsum(sdelta, axis=2)                       # [L, C1, 2T]

    # an effective event owns [u, v) up to the next effective event of
    # its row; the row's last one, and every ineffective one, u == v
    nxt_t = jnp.concatenate([stime[..., 1:], stime[..., -1:]], axis=2)
    nxt_e = jnp.concatenate(
        [seff[..., 1:], jnp.zeros_like(seff[..., :1])], axis=2)
    u_t = stime
    v_t = jnp.where(seff & nxt_e, nxt_t, stime)
    over = (u_t < v_t) & (prof > cu_cap[None, :, None])
    ovl = jnp.repeat(over.any(axis=2), T, axis=1)          # [L, M] per row

    # forbidden windows: member t cannot run through [u, v) of its row
    # if profile₋t + q_t > cap there (profile₋t removes t's own
    # compulsory part, tested at u only — CP endpoints are events, so
    # coverage is constant on [u, v)).  An interval that forbids nothing
    # gets u = +big, so no start can reach it.
    def on_slots(a):                                        # → [2T, L, M]
        a = jnp.moveaxis(a, 2, 0)[..., None]
        return jnp.broadcast_to(a, a.shape[:-1] + (T,)).reshape(2 * T, L, M)

    u_e, v_e, p_e = on_slots(u_t), on_slots(v_t), on_slots(prof)
    cov = has_cp & (u_e >= lst) & (u_e < ect)
    bad = act & (u_e < v_e) & (p_e + jnp.where(cov, zero, q) > cap)
    u_e = jnp.where(bad, u_e, neu_ub)

    def scan(e, s):
        s_f, s_b = s                       # first ≥ est, last ≤ lst
        u_f, v_f = u_e[e], v_e[e]
        u_b, v_b = u_e[2 * T - 1 - e], v_e[2 * T - 1 - e]
        s_f = jnp.where((s_f < v_f) & (s_f + d > u_f), v_f, s_f)
        s_b = jnp.where((s_b < v_b) & (s_b + d > u_b), u_b - d, s_b)
        return s_f, s_b

    s_est, s_lst = lax.fori_loop(0, 2 * T, scan, (est, lst))

    cand_lb = s_est
    # no feasible start ≥ 0 ⇒ dense's max over an empty set = −big
    cand_ub = jnp.where(s_lst >= 0, s_lst, -neu_ub + zero)
    # a lone task over capacity: every start is forbidden (dense marks the
    # whole grid bad; events only cover [first, last) — special-case it)
    qbig = act & (q > cap)
    cand_lb = jnp.where(qbig, -neu_lb + zero, cand_lb)
    cand_ub = jnp.where(qbig, -neu_ub + zero, cand_ub)
    first = cand_lb
    cand_lb = jnp.where(present, cand_lb, neu_lb + zero)
    cand_ub = jnp.where(present, cand_ub, neu_ub + zero)
    # overload: fail every present task of the row
    cand_lb = jnp.where(ovl & present, -neu_lb + zero, cand_lb)
    cand_lb, cand_ub = rows(cand_lb), rows(cand_ub)
    if cu_pres is None:
        return cand_lb, cand_ub
    p_lb, p_ub = _presence_tells(rows(undecided), rows(first > lst),
                                 neu_lb + zero, neu_ub + zero)
    return (jnp.concatenate([cand_lb, p_lb], axis=2),
            jnp.concatenate([cand_ub, p_ub], axis=2))


def _gather_join(cand_lb, cand_ub, occ_inst, occ_pos, L):
    """Variable-centric join of one bank's candidates: each var reduces
    over its occurrence list (pure gather — no scatter, no atomics)."""
    width = cand_ub.shape[2]
    flat_ub = cand_ub.reshape(L, -1)
    flat_lb = cand_lb.reshape(L, -1)
    occ = (occ_inst * width + occ_pos).reshape(-1)          # [V*D]
    V, D = occ_inst.shape
    g_ub = jnp.take(flat_ub, occ, axis=1).reshape(L, V, D).min(-1)
    g_lb = jnp.take(flat_lb, occ, axis=1).reshape(L, V, D).max(-1)
    return g_lb, g_ub


def _gather_join_flat(cand_lb, cand_ub, occ, L):
    """`_gather_join` for packed-axis candidates: `occ` ``[V, D]`` already
    holds flat indices into the ``[L, M]`` candidate arrays (built as
    ptr[occ_inst] + occ_pos — the CSR row-contiguity invariant)."""
    V, D = occ.shape
    idx = occ.reshape(-1)
    g_ub = jnp.take(cand_ub, idx, axis=1).reshape(L, V, D).min(-1)
    g_lb = jnp.take(cand_lb, idx, axis=1).reshape(L, V, D).max(-1)
    return g_lb, g_ub


def ct_candidates_tile(lb, ub, dom, ct_vars, ct_mask, ct_supp, dom_off,
                       n_table: int):
    """Compact-Table tells for the extensional bank (DESIGN.md §17).

    Pure-array form over a ``[L, V]`` bounds tile plus its ``[L, V, W]``
    bitset domain; shared verbatim by all four backends.  The *reset*
    variant of Compact-Table, stateless per sweep:

      1. gather each member's remaining value bits from `dom`;
      2. per member, OR the supports of its remaining values — the sum
         of disjoint tuple bitsets (each tuple has exactly ONE value per
         position, so the masked supports never share a bit and integer
         SUM is exact OR);
      3. AND the per-member words into the current table; an all-zero
         current table fails the row (every member's lb is pushed past
         its box);
      4. a value survives iff its support intersects the current table:
         the surviving bits give each member a filtered domain word mask
         and a [min, max] hull candidate.

    Monotone: shrink `dom` and the masked supports only shrink, so the
    current table and the surviving sets shrink (a propagator in the
    paper's Lemma-1 sense).  Returns (cand_lb, cand_ub, cand_dom) of
    shapes ``[L, T1, R]`` ×2 and ``[L, T1, R, W]``; padded member slots
    and the dummy row T are neutral (±big bounds, all-ones words).
    """
    dt = lb.dtype
    neu_ub, neu_lb = _neutrals(dt)
    L = lb.shape[0]
    T1, R, K32, TW = ct_supp.shape
    W = K32 // B.WORD_BITS
    # 1. member value bits, unpacked to the [K32] value axis
    mdom = jnp.take(dom, ct_vars.reshape(-1), axis=1
                    ).reshape(L, T1, R, W)                  # [L,T1,R,W]
    shifts = jnp.arange(B.WORD_BITS, dtype=jnp.uint32)
    vb = (mdom[..., None] >> shifts) & np.uint32(1)         # [L,T1,R,W,32]
    vb = vb.reshape(L, T1, R, K32)
    # 2. OR of supports of remaining values == SUM of disjoint bitsets
    supp_on = vb[..., None] * ct_supp[None]                 # [L,T1,R,K32,TW]
    mor = supp_on.sum(axis=3)                               # [L,T1,R,TW]
    # 3. current table = AND over real members (padding slots all-ones)
    real = (ct_mask[None] != 0)                             # [1,T1,R]
    mor = jnp.where(real[..., None], mor, B.FULL)
    curr = mor[:, :, 0, :]
    for r in range(1, R):                       # R is static & small
        curr = curr & mor[:, :, r, :]
    fail = jnp.all(curr == 0, axis=-1)                      # [L,T1]
    # 4. surviving values = supports intersecting the current table
    surv = jnp.any((ct_supp[None] & curr[:, :, None, None, :]) != 0,
                   axis=-1)                                 # [L,T1,R,K32]
    ks = jnp.arange(K32, dtype=dt)
    kmin = jnp.where(surv, ks, neu_ub).min(axis=-1)         # [L,T1,R]
    kmax = jnp.where(surv, ks, neu_lb).max(axis=-1)
    omem = jnp.take(dom_off, ct_vars.reshape(-1)).reshape(T1, R)
    cand_lb = jnp.where(real, omem[None] + kmin, neu_lb)
    cand_ub = jnp.where(real, omem[None] + kmax, neu_ub)
    # row failure: push every real member past its box (like the other
    # kinds, the box clamp turns -neu_lb into box_hi, crossing ub)
    cand_lb = jnp.where(fail[:, :, None] & real, -neu_lb, cand_lb)
    # pack the surviving bits back into domain words
    weights = np.uint32(1) << shifts
    cand_dom = (surv.astype(jnp.uint32).reshape(L, T1, R, W, B.WORD_BITS)
                * weights).sum(axis=-1)                     # [L,T1,R,W]
    cand_dom = jnp.where(real[..., None], cand_dom, B.FULL)
    return cand_lb, cand_ub, cand_dom


def _gather_join_dom(cand_dom, occ_inst, occ_pos, dom):
    """Variable-centric join of the CT bank's domain-word candidates:
    each var ANDs the masks of its occurrences into its words (the
    bitset-lattice ⊔).  Both join strategies use this same gather form —
    there is no scatter-AND primitive, and ⊔-associativity makes the
    strategy irrelevant to the result."""
    L, _, R, W = cand_dom.shape
    V, D = occ_inst.shape
    occ = (occ_inst * R + occ_pos).reshape(-1)
    g = jnp.take(cand_dom.reshape(L, -1, W), occ, axis=1
                 ).reshape(L, V, D, W)
    for d in range(D):                          # D is static & small
        dom = dom & g[:, :, d]
    return dom


def dom_normalize_tile(lb, ub, dom, dom_off, dom_track, box_lo, box_hi,
                       n_words: int):
    """Re-sync the two lattices after a sweep's joins (DESIGN.md §17):
    the bitset loses the values outside [lb, ub], and the bounds tighten
    to the bitset's hull.  Untracked vars (dom_track == 0) pass through
    on both sides.  An empty tracked domain reads back as the crossed
    hull (off + 32W, off - 1), which the box clamp keeps crossed — so
    bitset wipeout is bounds failure, the one failure signal every
    engine layer already watches."""
    trk = (dom_track != 0)[None, :]
    rng = B.from_bounds(lb, ub, dom_off, n_words)
    dom = jnp.where(trk[..., None], dom & rng, dom)
    lo, hi = B.to_bounds(dom, dom_off)
    nlb = jnp.maximum(lb, jnp.minimum(lo, box_hi[None, :]))
    nub = jnp.minimum(ub, jnp.maximum(hi, box_lo[None, :]))
    nlb = jnp.where(trk, nlb, lb)
    nub = jnp.where(trk, nub, ub)
    return nlb, nub, dom


def sweep_tile(lb, ub, vidx, coef, rhs, bidx, occ_prop, occ_slot,
               ad_vars, ad_offs, ad_mask, ad_occ_inst, ad_occ_pos,
               ad_ptr, ad_pk_var, ad_pk_off, ad_pk_seg,
               cu_svar, cu_dur, cu_dem, cu_cap, cu_pres, cu_occ_inst,
               cu_occ_pos,
               ct_vars, ct_mask, ct_supp, ct_occ_inst, ct_occ_pos,
               dom_off, dom_track,
               box_lo, box_hi, *, horizon: int, n_alldiff: int = 0,
               n_cumulative: int = 0, ad_layout: str = "dense",
               cu_layout: str = "dense", cu_optional: bool = False,
               n_table: int = 0, n_words: int = 1, dom=None):
    """One eventless sweep over a ``[L, V]`` tile of stores (gather form),
    dispatching over the typed propagator banks (DESIGN.md §12).

    Pure-array form shared verbatim by the XLA backends and the Pallas
    kernel body — the single source of truth for the sweep semantics.
    Every bank computes its candidate tells, every variable reduces over
    its per-bank occurrence lists, and the joins compose by min/max —
    associativity/commutativity of ⊔ makes the kind order irrelevant to
    the result.  ``n_alldiff``/``n_cumulative`` are compile-time statics
    so models without a bank skip its (dummy-only) work entirely;
    ``ad_layout``/``cu_layout`` pick the dense or the sparse tile per
    bank (compile-time crossover, DESIGN.md §16) — same semantics,
    different scratch scaling.  ``cu_optional`` (static) switches the
    Cumulative tile to its presence path (DESIGN.md §18); off, the
    presence table is a ``[1, 1]`` dummy and unread.

    With ``n_table`` tables (DESIGN.md §17) the sweep also runs the
    Compact-Table tile over the bitset domain.  `dom` (``[L, V, W]``
    uint32 or None) opts the caller into carrying the bitset store:
    when given, the CT tile filters it, the sweep ends with
    `dom_normalize_tile`, and a 3-tuple (lb, ub, dom) is returned.
    When None on a table model, a transient range-set domain is derived
    from the current bounds for the CT tile (sound — a superset of any
    carried domain — just weaker on interval holes) and the legacy
    2-tuple comes back unchanged in shape.
    """
    L = lb.shape[0]
    # one named scope per kind tile, over its candidates and its join,
    # so a device trace can attribute op time to the tile
    with jax.named_scope("tile.linear"):
        cand_lb, cand_ub = candidates_tile(lb, ub, vidx, coef, rhs, bidx)
        # fold the reif-entailment slot in: occ_slot ∈ [0, K] indexes [K+1]
        g_lb, g_ub = _gather_join(cand_lb, cand_ub, occ_prop, occ_slot, L)
    if n_alldiff:
        with jax.named_scope("tile.alldiff"):
            if ad_layout == "sparse":
                ad_lb, ad_ub = alldiff_candidates_sparse_tile(
                    lb, ub, ad_pk_var, ad_pk_off, ad_pk_seg, n_alldiff)
                occ = jnp.take(ad_ptr, ad_occ_inst) + ad_occ_pos  # [V, Dad]
                j_lb, j_ub = _gather_join_flat(ad_lb, ad_ub, occ, L)
            else:
                ad_lb, ad_ub = alldiff_candidates_tile(lb, ub, ad_vars,
                                                       ad_offs, ad_mask)
                j_lb, j_ub = _gather_join(ad_lb, ad_ub, ad_occ_inst,
                                          ad_occ_pos, L)
        g_lb = jnp.maximum(g_lb, j_lb)
        g_ub = jnp.minimum(g_ub, j_ub)
    if n_cumulative:
        with jax.named_scope("tile.cumulative"):
            pres = cu_pres if cu_optional else None
            if cu_layout == "sparse":
                cu_lb, cu_ub = cumulative_candidates_sparse_tile(
                    lb, ub, cu_svar, cu_dur, cu_dem, cu_cap, pres)
            else:
                cu_lb, cu_ub = cumulative_candidates_tile(
                    lb, ub, cu_svar, cu_dur, cu_dem, cu_cap, horizon, pres)
            j_lb, j_ub = _gather_join(cu_lb, cu_ub, cu_occ_inst,
                                      cu_occ_pos, L)
        g_lb = jnp.maximum(g_lb, j_lb)
        g_ub = jnp.minimum(g_ub, j_ub)
    if n_table:
        with jax.named_scope("tile.table"):
            d_in = dom if dom is not None else B.from_bounds(
                lb, ub, dom_off, n_words, track=dom_track)
            ct_lb, ct_ub, ct_dm = ct_candidates_tile(
                lb, ub, d_in, ct_vars, ct_mask, ct_supp, dom_off, n_table)
            j_lb, j_ub = _gather_join(ct_lb, ct_ub, ct_occ_inst, ct_occ_pos,
                                      L)
            if dom is not None:
                dom = _gather_join_dom(ct_dm, ct_occ_inst, ct_occ_pos, dom)
        g_lb = jnp.maximum(g_lb, j_lb)
        g_ub = jnp.minimum(g_ub, j_ub)
    # clamp candidates into the initial box (overflow guard; sound because
    # box_lo-1/box_hi+1 still cross the opposite bound on failure)
    g_ub = jnp.maximum(g_ub, box_lo[None, :])
    g_lb = jnp.minimum(g_lb, box_hi[None, :])
    nlb = jnp.maximum(lb, g_lb)
    nub = jnp.minimum(ub, g_ub)
    if dom is None:
        return nlb, nub
    return dom_normalize_tile(nlb, nub, dom, dom_off, dom_track,
                              box_lo, box_hi, n_words)


# The positional table args of `sweep_tile`, in order — the ONE place
# the (backend-shared) sweep signature is spelled out.
TABLE_NAMES = (
    "vidx", "coef", "rhs", "bidx", "occ_prop", "occ_slot",
    "ad_vars", "ad_offs", "ad_mask", "ad_occ_inst", "ad_occ_pos",
    "ad_ptr", "ad_pk_var", "ad_pk_off", "ad_pk_seg",
    "cu_svar", "cu_dur", "cu_dem", "cu_cap", "cu_pres", "cu_occ_inst",
    "cu_occ_pos",
    "ct_vars", "ct_mask", "ct_supp", "ct_occ_inst", "ct_occ_pos",
    "dom_off", "dom_track", "box_lo", "box_hi")


def model_tables(cm: CompiledModel) -> Tuple:
    """The positional table args of `sweep_tile` (`TABLE_NAMES`)."""
    return tuple(getattr(cm, n) for n in TABLE_NAMES)


def model_statics(cm: CompiledModel) -> dict:
    """The static (kind/layout-dispatch) kwargs of `sweep_tile`."""
    return dict(horizon=cm.horizon, n_alldiff=cm.n_alldiff,
                n_cumulative=cm.n_cumulative,
                ad_layout=cm.ad_layout, cu_layout=cm.cu_layout,
                cu_optional=cm.cu_optional,
                n_table=cm.n_table, n_words=cm.n_words)


def presence_mask(cu_pres, n_vars: int):
    """bool[V]: the variables that are some Cumulative member's presence
    literal (the search counters' filter, DESIGN.md §18)."""
    return jnp.zeros((n_vars,), bool).at[cu_pres.reshape(-1)].set(True) \
        .at[TRUE_VAR].set(False)


def propagator_candidates(cm: CompiledModel, lb: jax.Array, ub: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Single-store view of `candidates_tile` (each ``[P+1, K+1]``).

    Kept as the entry point for the linear scatter form and the
    sequential SELECT-rule semantics (which are defined on the ReifLinLe
    bank; the native banks have their own tiles).
    """
    cand_lb, cand_ub = candidates_tile(lb[None], ub[None], cm.vidx, cm.coef,
                                       cm.rhs, cm.bidx)
    return cand_lb[0], cand_ub[0]


def sweep(cm: CompiledModel, lb: jax.Array, ub: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """One parallel iteration: D(P₁) ⊔ … ⊔ D(Pₙ) applied to one (lb, ub)."""
    nlb, nub = sweep_tile(lb[None], ub[None], *model_tables(cm),
                          **model_statics(cm))
    return nlb[0], nub[0]


def sweep_batch(cm: CompiledModel, lb: jax.Array, ub: jax.Array, dom=None):
    """Gather sweep over lane-batched ``[L, V]`` stores — one tensor op for
    the whole batch (the TURBO shape: every lane's sweep in one launch).
    Pass `dom` to carry the bitset store (3-tuple return, DESIGN.md §17)."""
    return sweep_tile(lb, ub, *model_tables(cm), **model_statics(cm),
                      dom=dom)


def sweep_scatter(cm: CompiledModel, lb: jax.Array, ub: jax.Array, dom=None):
    """Propagator-centric scatter form of the same sweep (oracle).

    This is literally "each propagator writes its variables through an
    atomic join" — the paper's load/store formulation — except the joins
    are XLA scatter-min/max, which are deterministic regardless of
    duplicate indices (associative reduce).  Used as the reference the
    gather sweep and the Pallas kernel are tested against.  The native
    banks reuse the *same* kind tiles as the gather form (DESIGN.md §12)
    and only differ in join strategy: per-row scatter instead of per-var
    occurrence gather — equal results by associativity of ⊔.
    """
    cand_lb, cand_ub = propagator_candidates(cm, lb, ub)
    # plain rows (b == TRUE) must not scatter their (dis)entailment slot:
    # the gather form has no TRUE-var occurrence for it (compile.py), and
    # a disentailed plain row always fails through term tightening in the
    # same sweep — neutralizing here keeps both forms bit-identical per
    # sweep, not just at the fixpoint (test_backend_parity_capped_iters)
    neu_ub, neu_lb = _neutrals(lb.dtype)
    plain = cm.bidx == TRUE_VAR
    cand_ub = cand_ub.at[:, -1].set(
        jnp.where(plain, neu_ub, cand_ub[:, -1]))
    cand_lb = cand_lb.at[:, -1].set(
        jnp.where(plain, neu_lb, cand_lb[:, -1]))
    tgt = jnp.concatenate([cm.vidx, cm.bidx[:, None]], axis=1)  # [P1, K+1]
    flat_v = tgt.reshape(-1)
    new_ub = ub.at[flat_v].min(jnp.maximum(cand_ub.reshape(-1), cm.box_lo[flat_v]))
    new_lb = lb.at[flat_v].max(jnp.minimum(cand_lb.reshape(-1), cm.box_hi[flat_v]))
    if cm.n_alldiff:
        if cm.ad_layout == "sparse":
            ad_lb, ad_ub = alldiff_candidates_sparse_tile(
                lb[None], ub[None], cm.ad_pk_var, cm.ad_pk_off,
                cm.ad_pk_seg, cm.n_alldiff)
            v = cm.ad_pk_var
        else:
            ad_lb, ad_ub = alldiff_candidates_tile(
                lb[None], ub[None], cm.ad_vars, cm.ad_offs, cm.ad_mask)
            v = cm.ad_vars.reshape(-1)
        new_ub = new_ub.at[v].min(
            jnp.maximum(ad_ub[0].reshape(-1), cm.box_lo[v]))
        new_lb = new_lb.at[v].max(
            jnp.minimum(ad_lb[0].reshape(-1), cm.box_hi[v]))
    if cm.n_cumulative:
        opt = cm.cu_optional
        pres = cm.cu_pres if opt else None
        if cm.cu_layout == "sparse":
            cu_lb, cu_ub = cumulative_candidates_sparse_tile(
                lb[None], ub[None], cm.cu_svar, cm.cu_dur, cm.cu_dem,
                cm.cu_cap, pres)
        else:
            cu_lb, cu_ub = cumulative_candidates_tile(
                lb[None], ub[None], cm.cu_svar, cm.cu_dur, cm.cu_dem,
                cm.cu_cap, cm.horizon, pres)
        v = (jnp.concatenate([cm.cu_svar, cm.cu_pres], axis=1) if opt
             else cm.cu_svar).reshape(-1)
        new_ub = new_ub.at[v].min(
            jnp.maximum(cu_ub[0].reshape(-1), cm.box_lo[v]))
        new_lb = new_lb.at[v].max(
            jnp.minimum(cu_lb[0].reshape(-1), cm.box_hi[v]))
    if cm.n_table:
        d_in = (dom[None] if dom is not None else B.from_bounds(
            lb[None], ub[None], cm.dom_off, cm.n_words, track=cm.dom_track))
        ct_lb, ct_ub, ct_dm = ct_candidates_tile(
            lb[None], ub[None], d_in, cm.ct_vars, cm.ct_mask, cm.ct_supp,
            cm.dom_off, cm.n_table)
        v = cm.ct_vars.reshape(-1)
        new_ub = new_ub.at[v].min(
            jnp.maximum(ct_ub[0].reshape(-1), cm.box_lo[v]))
        new_lb = new_lb.at[v].max(
            jnp.minimum(ct_lb[0].reshape(-1), cm.box_hi[v]))
        if dom is not None:
            # bitset joins stay in gather form under the scatter strategy
            # too: there is no scatter-AND join, and ⊔-associativity makes
            # the strategy irrelevant (see _gather_join_dom)
            dom = _gather_join_dom(ct_dm, cm.ct_occ_inst, cm.ct_occ_pos,
                                   dom[None])[0]
    if dom is None:
        return new_lb, new_ub
    nlb, nub, ndom = dom_normalize_tile(
        new_lb[None], new_ub[None], dom[None], cm.dom_off, cm.dom_track,
        cm.box_lo, cm.box_hi, cm.n_words)
    return nlb[0], nub[0], ndom[0]


def sweep_scatter_batch(cm: CompiledModel, lb: jax.Array, ub: jax.Array,
                        dom=None):
    """Scatter sweep over lane-batched ``[L, V]`` stores (vmapped joins)."""
    if dom is None:
        return jax.vmap(partial(sweep_scatter, cm))(lb, ub)
    return jax.vmap(lambda l, u, d: sweep_scatter(cm, l, u, d))(lb, ub, dom)


@partial(jax.jit, static_argnames=("max_iters", "stop_on_fail", "use_scatter"))
def fixpoint(cm: CompiledModel, lb: jax.Array, ub: jax.Array,
             max_iters: Optional[int] = None, stop_on_fail: bool = True,
             use_scatter: bool = False):
    """Run sweeps to the least fixed point (paper Thm. 2 guarantees
    existence/uniqueness; finite lattices guarantee termination).

    Returns (lb', ub', n_sweeps, converged).  `converged` is a per-store
    flag: True iff the last sweep changed nothing (or the store failed —
    failure is definitive).  With ``max_iters`` the loop may stop early
    with converged=False; callers must then keep sweeping before trusting
    all-fixed stores as solutions (search.py does — see §Perf H1).
    With ``stop_on_fail`` the loop exits as soon as some domain empties
    (failed stores are discarded by search — a beyond-paper early-exit).
    """
    step = sweep_scatter if use_scatter else sweep

    def cond(st):
        lb_, ub_, changed, it = st
        ok = changed
        if max_iters is not None:
            ok = ok & (it < max_iters)
        if stop_on_fail:
            ok = ok & jnp.logical_not(jnp.any(lb_ > ub_))
        return ok

    def body(st):
        lb_, ub_, _, it = st
        nlb, nub = step(cm, lb_, ub_)
        changed = jnp.any((nlb != lb_) | (nub != ub_))
        return nlb, nub, changed, it + 1

    init = (lb, ub, jnp.asarray(True), jnp.asarray(0, jnp.int32))
    lb, ub, changed, iters = lax.while_loop(cond, body, init)
    converged = jnp.logical_not(changed) | jnp.any(lb > ub)
    return lb, ub, iters, converged


def fixpoint_tile(lb, ub, *tables, horizon: int, n_alldiff: int = 0,
                  n_cumulative: int = 0, ad_layout: str = "dense",
                  cu_layout: str = "dense", cu_optional: bool = False,
                  n_table: int = 0, n_words: int = 1, dom=None,
                  max_iters: Optional[int] = None,
                  stop_on_fail: bool = True, step=None):
    """Per-lane-masked fixpoint loop over a ``[L, V]`` tile (gather form).

    Pure-array form (no `CompiledModel`) so the Pallas kernel bodies —
    the unfused fixpoint kernel and the resident search megakernel
    (DESIGN.md §13) — can run it on VMEM refs; `fixpoint_batch` wraps it
    for the XLA backends.  A lane participates in a sweep iff its own
    per-lane cond (changed ∧ it < max_iters ∧ ¬failed) holds, so results,
    sweep counts and convergence flags are identical across every caller
    (idempotence of ⊔ makes the frozen-lane masking exact).

    `step` overrides the sweep function (the scatter backend passes its
    join strategy through here); default is `sweep_tile` on `tables`.
    With `dom` (``[L, V, W]``) the bitset store rides in the carry (None
    is an empty pytree, so the loop structure is unchanged without it)
    and a sweep counts as "changed" when any domain word moved even if
    the hull did not — interior Compact-Table wipeouts must keep the
    lane sweeping.

    Returns (lb', ub', sweeps[L], converged[L]), with dom' inserted
    before the counters when it is carried.
    """
    L = lb.shape[0]
    have_dom = dom is not None
    if step is None:
        def step(lb_, ub_, dom_):
            return sweep_tile(lb_, ub_, *tables, horizon=horizon,
                              n_alldiff=n_alldiff,
                              n_cumulative=n_cumulative,
                              ad_layout=ad_layout, cu_layout=cu_layout,
                              cu_optional=cu_optional,
                              n_table=n_table, n_words=n_words, dom=dom_)
    elif not have_dom:
        _step2 = step

        def step(lb_, ub_, dom_):
            return _step2(lb_, ub_)

    def lane_live(lb_, ub_, changed, it):
        ok = changed
        if max_iters is not None:
            ok = ok & (it < max_iters)
        if stop_on_fail:
            ok = ok & jnp.logical_not(jnp.any(lb_ > ub_, axis=1))
        return ok                                          # bool[L]

    def cond(st):
        lb_, ub_, dom_, changed, it = st
        return jnp.any(lane_live(lb_, ub_, changed, it))

    def body(st):
        lb_, ub_, dom_, changed, it = st
        active = lane_live(lb_, ub_, changed, it)
        out = step(lb_, ub_, dom_)
        if have_dom:
            nlb, nub, ndom = out
            ndom = jnp.where(active[:, None, None], ndom, dom_)
        else:
            (nlb, nub), ndom = out, dom_
        nlb = jnp.where(active[:, None], nlb, lb_)
        nub = jnp.where(active[:, None], nub, ub_)
        ch = jnp.any((nlb != lb_) | (nub != ub_), axis=1)
        if have_dom:
            ch = ch | jnp.any(ndom != dom_, axis=(1, 2))
        changed = jnp.where(active, ch, changed)
        return nlb, nub, ndom, changed, it + active.astype(jnp.int32)

    init = (lb, ub, dom, jnp.ones((L,), bool), jnp.zeros((L,), jnp.int32))
    lb, ub, dom, changed, iters = lax.while_loop(cond, body, init)
    converged = jnp.logical_not(changed) | jnp.any(lb > ub, axis=1)
    if have_dom:
        return lb, ub, dom, iters, converged
    return lb, ub, iters, converged


@partial(jax.jit, static_argnames=("max_iters", "stop_on_fail", "use_scatter"))
def fixpoint_batch(cm: CompiledModel, lb: jax.Array, ub: jax.Array,
                   dom=None, max_iters: Optional[int] = None,
                   stop_on_fail: bool = True, use_scatter: bool = False):
    """Lane-batched fixpoint: one `while_loop` over the whole ``[L, V]``
    store tensor, each sweep a single batched tensor op (`sweep_batch`).

    This is the TURBO superstep shape — one propagation launch for all
    lanes — replacing the per-lane `fixpoint` under `vmap` whose
    while_loop degenerates to lockstep select-masking anyway.  The loop
    itself is `fixpoint_tile`, shared verbatim with the Pallas kernels.

    Returns (lb', ub', sweeps[L], converged[L]); with `dom` carried the
    bitset store is threaded through and returned before the counters.
    """
    step = partial(sweep_scatter_batch, cm) if use_scatter else None
    return fixpoint_tile(lb, ub, *model_tables(cm), **model_statics(cm),
                         dom=dom, max_iters=max_iters,
                         stop_on_fail=stop_on_fail, step=step)


# --------------------------------------------------------------------------
# Sequential / chaotic iteration semantics — test-grade implementations of
# the paper's `seq P` (Prop. 3) and fair schedules (Def. 5 / Thm. 6).
# --------------------------------------------------------------------------

def apply_one(cm: CompiledModel, lb, ub, p: jax.Array):
    """Apply a single guarded command (SELECT rule) — one transition of ↪."""
    cand_lb, cand_ub = propagator_candidates(cm, lb, ub)  # (cheap enough for tests)
    row_ub, row_lb = cand_ub[p], cand_lb[p]
    tgt = jnp.concatenate([cm.vidx[p], cm.bidx[p][None]])
    new_ub = ub.at[tgt].min(jnp.maximum(row_ub, cm.box_lo[tgt]))
    new_lb = lb.at[tgt].max(jnp.minimum(row_lb, cm.box_hi[tgt]))
    return new_lb, new_ub


def sequential_fixpoint(cm: CompiledModel, lb, ub, order=None,
                        max_rounds: int = 10_000):
    """fix D(seq P) under the schedule `order` (default: program order).

    Python-loop driven; used only by tests to validate Prop. 3 / Thm. 6.
    """
    import numpy as np
    order = list(range(cm.n_props)) if order is None else list(order)
    lb = jnp.asarray(lb)
    ub = jnp.asarray(ub)
    for _ in range(max_rounds):
        plb, pub = lb, ub
        for p in order:
            lb, ub = apply_one(cm, lb, ub, jnp.asarray(p))
        if bool(jnp.all(lb == plb) & jnp.all(ub == pub)):
            return np.asarray(lb), np.asarray(ub)
    raise RuntimeError("sequential fixpoint did not converge")
