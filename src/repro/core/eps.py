"""Embarrassingly-parallel-search decomposition (paper §TURBO, after
Malapert/Régin/Rezgui 2016; DESIGN.md §9).

TURBO "dynamically generates subproblems following a variant of EPS"; we
generate them by iterative splitting on the chip: repeatedly split the
widest-frontier subproblem with the search branching rule, propagate both
children with the *same* fixpoint engine, and drop failed children.  The
resulting pool partitions the root search space (left `x ≤ m` / right
`x ≥ m+1` are complementary), so lane-level DFS over the pool is complete.

The split loop is one device program (`split_program`): a `while_loop`
over fixed ``[target + 1, V]`` row arrays that pops the widest live row
(the earliest inserted on ties), splits it and propagates both children
in one ``[2, V]`` per-lane-masked fixpoint (`fixpoint_batch`).  Only the
root's propagation and the final pool come back to the host, so a
decomposition is two dispatches whatever the target.  The order is the
sequential widest-first one, which is a chain (under the ``prove``
preset every Taillard split takes a child of the one before), so the
pool is exactly what a plain one-split-at-a-time loop builds
(`tests/test_eps.py`); batching a whole frontier level would change the
pool (DESIGN.md §9).

The pool feeds `engine.solve(eps_target=...)`: it seeds the per-device
lane pools, and `search.dispatch_pool` replenishes idle lanes from the
remainder every superstep (DESIGN.md §9).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core.compile import CompiledModel
from repro.core.fixpoint import fixpoint, fixpoint_batch
from repro.core import search as S

_I32_MAX = int(np.iinfo(np.int32).max)


def decompose(cm: CompiledModel, target: int,
              opts: "S.SearchOptions" = None,
              stats: Optional[dict] = None,
              program: Optional[Callable] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Split the root into ~`target` consistent subproblems.

    Returns (subs_lb, subs_ub) with shape [S, V], S ≥ 1 (S can exceed or
    fall short of `target` when the tree is shallow/unsatisfiable): the
    frontier in insertion order, then the solution leaves in the order
    they were found.  ``program`` is `split_program` for this target and
    ``opts``' branching rule, compiled (a `Solver` passes its own); by
    default it is jitted here.  ``stats``, when given, receives
    ``dispatches`` (device calls: the root's fixpoint and the split
    loop), ``splits`` and ``sweep_rounds`` (the lockstep sweep rounds of
    the splits' pair fixpoints).
    """
    opts = opts or S.SearchOptions()
    if program is None:
        program = _jitted_program(target, opts.var_strategy,
                                  opts.val_strategy)
    with obs.span("repro.eps.decompose"):
        return _decompose(cm, program,
                          stats if stats is not None else {})


@functools.lru_cache(maxsize=None)
def _jitted_program(target: int, var_strategy: str, val_strategy: str):
    return jax.jit(split_program(target, var_strategy, val_strategy))


def _decompose(cm: CompiledModel, program: Callable,
               stats: dict) -> Tuple[np.ndarray, np.ndarray]:
    stats.update(dispatches=1, splits=0, sweep_rounds=0)
    with obs.span("repro.eps.dispatch"):
        root_lb, root_ub, _, _ = fixpoint(cm, cm.lb0, cm.ub0)
        lb, ub = np.asarray(root_lb), np.asarray(root_ub)
    if (lb > ub).any():
        return lb[None], ub[None]          # failed root: one failed sub
    width = int((ub - lb)[np.asarray(cm.branch_vars)].clip(min=0).sum())
    if width > _I32_MAX:
        raise OverflowError(
            f"the root's branch variables span {width} values in all, "
            f"more than the split loop's int32 widths hold; narrow their "
            f"domains (DESIGN.md §9)")
    stats["dispatches"] = 2
    with obs.span("repro.eps.dispatch"):
        rows_lb, rows_ub, live, leaf, seq, splits, rounds = jax.device_get(
            program(cm, root_lb, root_ub))
    stats.update(splits=int(splits), sweep_rounds=int(rounds))
    idx = np.concatenate([np.flatnonzero(f)[np.argsort(seq[f])]
                          for f in (live, leaf)])
    if idx.size == 0:                       # everything failed: UNSAT root
        bad_l, bad_u = lb.copy(), ub.copy()
        bad_l[0], bad_u[0] = 1, 0           # an explicitly failed store
        return bad_l[None], bad_u[None]
    return rows_lb[idx], rows_ub[idx]


def split_program(target: int, var_strategy: str, val_strategy: str):
    """The split loop for one target and branching rule, as a function
    ``(cm, root_lb, root_ub) -> (rows_lb, rows_ub, live, leaf, seq,
    n_splits, n_sweep_rounds)`` to jit; named so its executable reads
    ``jit_eps_split_loop`` in a device trace."""
    fn = functools.partial(_split_loop, target, var_strategy, val_strategy)
    fn.__name__ = "eps_split_loop"
    return fn


def _width(lb, ub, bv):
    """Sum of the branch variables' ranges per store, in int32: the
    root's fits (`_decompose` checks it) and a propagated child's is
    never larger."""
    return jnp.sum(jnp.maximum(ub[..., bv] - lb[..., bv], 0)
                   .astype(jnp.int32), axis=-1)


def _split_loop(target: int, var_strategy: str, val_strategy: str,
                cm: CompiledModel, lb, ub):
    """Split the widest live row (the earliest inserted on ties) until
    live rows and leaves reach ``target`` or no live row is left.

    Rows are ``[target + 1, V]``: before a split at most ``target - 1``
    are in use, and a split frees one and takes two.  ``live`` marks the
    frontier, ``leaf`` the rows with every branch variable fixed; ``seq``
    is a live row's insertion number (the root 0) and a leaf's number in
    the order leaves were found.  The ``le`` child reuses the popped
    row, the ``ge`` child the first free one.
    """
    cap = target + 1
    bv = cm.branch_vars
    rows = jnp.arange(cap)
    big = jnp.asarray(jnp.iinfo(lb.dtype).max // 4, lb.dtype)
    zero = jnp.asarray(0, jnp.int32)

    def cond(c):
        live, leaf = c[2], c[3]
        n_live = jnp.sum(live)
        return (n_live > 0) & (n_live + jnp.sum(leaf) < target)

    def body(c):
        rlb, rub, live, leaf, seq, width, n_seq, n_leaf, n_splits, n_rounds = c
        w = jnp.where(live, width, -1)
        i = jnp.argmin(jnp.where(live & (w == jnp.max(w)), seq, _I32_MAX))
        l, u = rlb[i], rub[i]
        unf = l[bv] < u[bv]
        live = live.at[i].set(False)

        def to_leaf():
            return (rlb, rub, live, leaf.at[i].set(True),
                    seq.at[i].set(n_leaf), width, n_seq, n_leaf + 1,
                    n_splits, n_rounds)

        def split():
            if var_strategy == S.MIN_DOM:
                k = jnp.argmin(jnp.where(unf, u[bv] - l[bv], big))
            elif var_strategy == S.MIN_LB:
                k = jnp.argmin(jnp.where(unf, l[bv], big))
            else:
                k = jnp.argmax(unf)
            v = bv[k]
            m = l[v] if val_strategy == S.VAL_MIN else (l[v] + u[v]) // 2
            klb, kub, sweeps, _ = fixpoint_batch(
                cm, jnp.stack([l, l.at[v].max(m + 1)]),
                jnp.stack([u.at[v].min(m), u]))
            ok = jnp.all(klb <= kub, axis=1)
            # le takes the popped row, ge the first other free row (the
            # popped row if le failed); a failed child's slot is dropped
            free = ~(live | leaf) & (rows != i)
            ge = jnp.where(ok[0], jnp.argmax(free), i)
            slots = jnp.where(ok, jnp.stack([i, ge]), cap)
            okn = ok.astype(jnp.int32)
            return (rlb.at[slots].set(klb, mode="drop"),
                    rub.at[slots].set(kub, mode="drop"),
                    live.at[slots].set(True, mode="drop"), leaf,
                    seq.at[slots].set(n_seq + jnp.stack([0, okn[0]]),
                                      mode="drop"),
                    width.at[slots].set(_width(klb, kub, bv), mode="drop"),
                    n_seq + jnp.sum(okn), n_leaf, n_splits + 1,
                    n_rounds + jnp.max(sweeps))

        return lax.cond(jnp.any(unf), split, to_leaf)

    init = (jnp.tile(lb[None], (cap, 1)), jnp.tile(ub[None], (cap, 1)),
            rows == 0, jnp.zeros((cap,), bool), jnp.zeros((cap,), jnp.int32),
            jnp.zeros((cap,), jnp.int32).at[0].set(_width(lb, ub, bv)),
            zero + 1, zero, zero, zero)
    rlb, rub, live, leaf, seq, _, _, _, n_splits, n_rounds = lax.while_loop(
        cond, body, init)
    return rlb, rub, live, leaf, seq, n_splits, n_rounds


def pad_pool(subs_lb: np.ndarray, subs_ub: np.ndarray,
             size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a pool ``[S, V]`` up to ``size`` entries with explicitly-failed
    stores (``lb[0] > ub[0]``) — a lane that pops one fails it in a
    single superstep and re-arms, so statuses/objectives are unchanged.

    Used by the session API for two shape-stabilization jobs
    (DESIGN.md §11): bucketing pool sizes (`api._bucket`: powers of two
    up to 1024, then multiples of 1024 — capped so a 10³-variable model
    with a large ``eps_target`` can't silently allocate a pool ~2× the
    request, DESIGN.md §16) so the compiled runner is reused across
    instances whose decompositions differ slightly, and rounding the
    pool to a device-count multiple for the sharded mesh engine.
    ``size <= S`` is a no-op.

    The padded rows are inert under BOTH bank layouts: failure is
    carried by store row 0 (``lb[0] > ub[0]``), which the per-lane
    fixpoint masking freezes before any kind tile — dense or sparse —
    ever sweeps the lane (asserted by `tests/test_sparse_tiles.py`).
    """
    s = subs_lb.shape[0]
    if size <= s:
        return subs_lb, subs_ub
    fl = np.repeat(np.asarray(subs_lb[:1]).copy(), size - s, axis=0)
    fu = np.repeat(np.asarray(subs_ub[:1]).copy(), size - s, axis=0)
    fl[:, 0], fu[:, 0] = 1, 0
    return (np.concatenate([np.asarray(subs_lb), fl]),
            np.concatenate([np.asarray(subs_ub), fu]))


def fit_pool(subs_lb: np.ndarray, subs_ub: np.ndarray,
             size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fit a pool ``[S, V]`` to *exactly* ``size`` entries — the
    fixed-shape splice used by the serving scheduler (DESIGN.md §15):
    a `LaneBatch` slot's pool rows are a fixed ``[size, V]`` block of
    the compiled batch, so an admitted request's pool must be padded up
    (with inert failed stores, `pad_pool`) and can never exceed the
    bucket size without forcing a recompile — that case raises instead.
    """
    s = int(subs_lb.shape[0])
    if s > size:
        raise ValueError(
            f"pool of {s} subproblems does not fit the fixed bucket size "
            f"{size}; decompose with a smaller eps_target or grow the "
            f"bucket (which recompiles the batch runner)")
    return pad_pool(np.asarray(subs_lb), np.asarray(subs_ub), size)


def failed_pool(template_lb: np.ndarray, template_ub: np.ndarray,
                size: int) -> Tuple[np.ndarray, np.ndarray]:
    """An all-failed pool ``[size, V]`` (every store has ``lb[0] >
    ub[0]``) — what an idle/retired `LaneBatch` slot holds so its lanes
    drain in one superstep each and the slot freezes (DESIGN.md §15).
    ``template_lb/ub`` supply the store dtype and width ``V`` (a ``[V]``
    row or any ``[..., V]`` pool)."""
    lb = np.asarray(template_lb).reshape(-1, np.asarray(template_lb).shape[-1])
    ub = np.asarray(template_ub).reshape(-1, np.asarray(template_ub).shape[-1])
    fl = np.repeat(lb[:1].copy(), size, axis=0)
    fu = np.repeat(ub[:1].copy(), size, axis=0)
    fl[:, 0], fu[:, 0] = 1, 0
    return fl, fu
