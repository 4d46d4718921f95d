"""Pluggable propagation backends (DESIGN.md §2.3).

The paper's central claim is that eventless propagation is **one
bulk-parallel program**; everything above it (search, EPS, B&B) only ever
needs two entry points:

* ``fixpoint(cm, lb, ub)``        — one store to its least fixed point,
* ``fixpoint_batch(cm, lb, ub)``  — a whole ``[n_lanes, V]`` store tensor
  in one launch (the TURBO superstep shape: grid cells = lane tiles).

`PropagationBackend` is that contract; four implementations register
here and are selected by name everywhere a store is propagated
(`SearchOptions.backend` → `engine.solve` → `launch/solve.py` CLI →
benchmarks → examples):

  ``gather``   variable-centric XLA sweep (`fixpoint.sweep_batch`) — the
               CPU/GPU/TPU-portable production default;
  ``scatter``  propagator-centric scatter-join oracle — the literal
               reading of the paper's atomic load/store compilation;
  ``pallas``   the VMEM-resident Pallas kernel
               (`kernels/fixpoint_kernel.fixpoint_pallas`), run by the
               Pallas interpreter: Mosaic does not lower it for a TPU yet,
               so on a TPU construction raises
               (`fixpoint_kernel.MOSAIC_REFUSAL`) unless ``interpret=True``
               is passed;
  ``pallas_resident``
               the resident *search* megakernel (DESIGN.md §13): K whole
               supersteps — dispatch, branch, fixpoint, commit — fused
               into one `pl.pallas_call` that the host chunk scheduler
               launches once per K supersteps.

All four compute the same least fixed point from the same single
implementation of the propagator math (`fixpoint.candidates_tile`);
parity is property-tested in `tests/test_backends.py`.  The comparison
spec (see `kernels/ops.py`): equal failed-lane masks, bit-identical
stores on non-failed lanes — failed lanes' contents are unspecified and
search discards them.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Protocol, Tuple, runtime_checkable

import jax

from repro.core.compile import CompiledModel
from repro.core import fixpoint as F

FixpointResult = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]


@runtime_checkable
class PropagationBackend(Protocol):
    """Contract every propagation implementation satisfies.

    Both methods return ``(lb', ub', sweeps, converged)``; for the batch
    form `sweeps` and `converged` are per-lane ``[L]`` arrays.
    ``converged`` is True iff the lane reached a genuine fixed point (or
    failed — failure is definitive); with a `max_iters` cap it may be
    False, and callers must keep sweeping before trusting all-fixed
    stores as solutions (search.py's §Perf H1 soundness guard).
    """

    name: str

    def fixpoint(self, cm: CompiledModel, lb: jax.Array, ub: jax.Array, *,
                 max_iters: Optional[int] = None) -> FixpointResult:
        ...

    def fixpoint_batch(self, cm: CompiledModel, lb: jax.Array,
                       ub: jax.Array, *, dom: Optional[jax.Array] = None,
                       max_iters: Optional[int] = None) -> FixpointResult:
        # with `dom` (the bitset store, DESIGN.md §17) backends return
        # (lb', ub', dom', sweeps, converged) instead of the 4-tuple
        ...


class GatherBackend:
    """Variable-centric gather sweep, batched as one XLA tensor program."""

    name = "gather"

    def fixpoint(self, cm, lb, ub, *, max_iters=None):
        return F.fixpoint(cm, lb, ub, max_iters=max_iters)

    def fixpoint_batch(self, cm, lb, ub, *, dom=None, max_iters=None):
        return F.fixpoint_batch(cm, lb, ub, dom, max_iters=max_iters)


class ScatterBackend:
    """Propagator-centric scatter-join form (the reference semantics)."""

    name = "scatter"

    def fixpoint(self, cm, lb, ub, *, max_iters=None):
        return F.fixpoint(cm, lb, ub, max_iters=max_iters, use_scatter=True)

    def fixpoint_batch(self, cm, lb, ub, *, dom=None, max_iters=None):
        return F.fixpoint_batch(cm, lb, ub, dom, max_iters=max_iters,
                                use_scatter=True)


@partial(jax.jit, static_argnames=("lane_tile", "max_sweeps", "interpret"))
def _pallas_batch(cm, lb, ub, dom, lane_tile, max_sweeps, interpret):
    from repro.kernels.fixpoint_kernel import fixpoint_pallas
    return fixpoint_pallas(cm, lb, ub, dom=dom, lane_tile=lane_tile,
                           max_sweeps=max_sweeps, interpret=interpret)


class PallasBackend:
    """VMEM-resident Pallas fixpoint kernel (Pallas interpreter only).

    `lane_tile` is the grid-cell width — the number of lanes whose two
    stores co-reside in VMEM for the whole loop (the TURBO shared-memory
    analogue).  The effective tile is clamped to the batch size so tiny
    batches don't pay padding sweeps.

    The per-lane `sweeps` this backend reports are *tile-granular*: a
    tile sweeps in lockstep until nothing in it changes, so the count
    exceeds the XLA backends' per-lane useful-sweep counts on the same
    input (and so do `n_sweeps` search stats under ``backend="pallas"``).
    Stores and convergence are unaffected — only the counter semantics
    differ.
    """

    name = "pallas"

    def __init__(self, lane_tile: int = 8,
                 interpret: Optional[bool] = None,
                 max_sweeps: int = 16384):
        self.lane_tile = lane_tile
        # default: the interpreter off a TPU; on a TPU the kernel would be
        # lowered by Mosaic, which refuses it — raise rather than fall back
        self.interpret = (jax.default_backend() != "tpu"
                          if interpret is None else interpret)
        if not self.interpret:
            from repro.kernels.fixpoint_kernel import MOSAIC_REFUSAL
            raise NotImplementedError(f"backend {self.name!r}: "
                                      f"{MOSAIC_REFUSAL}")
        self.max_sweeps = max_sweeps

    def fixpoint(self, cm, lb, ub, *, max_iters=None):
        nlb, nub, sweeps, conv = self.fixpoint_batch(
            cm, lb[None], ub[None], max_iters=max_iters)
        return nlb[0], nub[0], sweeps[0], conv[0]

    def fixpoint_batch(self, cm, lb, ub, *, dom=None, max_iters=None):
        cap = self.max_sweeps if max_iters is None else int(max_iters)
        tile = max(1, min(self.lane_tile, lb.shape[0]))
        return _pallas_batch(cm, lb, ub, dom, lane_tile=tile,
                             max_sweeps=cap, interpret=self.interpret)


class PallasResidentBackend(PallasBackend):
    """Resident search megakernel (DESIGN.md §13): K supersteps of the
    whole four-phase search loop fused into one `pl.pallas_call`, with
    every piece of lane state (stores, decision paths, status flags,
    pool cursor, tile-best bound) held in VMEM across supersteps
    (`kernels/fixpoint_kernel.search_pallas`).

    As a plain `PropagationBackend` it behaves like `pallas` (the
    inherited unfused fixpoint kernel, with ``lane_tile=8`` when the
    resident tile is the whole-batch default 0) — the fused path is the
    extra `superstep_launch` contract consumed by the host chunk
    scheduler (`core/api._run_chunk`), which calls it once per K
    supersteps instead of driving `search.lanes_step` per superstep.

    ``lane_tile=0`` (default) keeps all lanes in ONE grid cell — the
    bit-parity mode whose EPS dispatch is the exact shared queue of the
    unfused path; a positive tile (or a VMEM auto-shrink) shards the
    pool across cells (sound/complete, different dispatch trajectory).
    """

    name = "pallas_resident"

    def __init__(self, supersteps_per_launch: int = 16, lane_tile: int = 0,
                 interpret: Optional[bool] = None, max_sweeps: int = 16384):
        super().__init__(lane_tile=lane_tile or 8, interpret=interpret,
                         max_sweeps=max_sweeps)
        self.resident_lane_tile = lane_tile
        self.supersteps_per_launch = supersteps_per_launch

    def n_tiles(self, cm: CompiledModel, n_lanes: int, *, max_depth: int,
                pool_size: int) -> int:
        """Grid cells the resident kernel will use for `n_lanes` lanes —
        the host scheduler sizes the per-cell pool-cursor carry
        (`api._init_carry(n_heads=...)`) with this so carry shapes stay
        stable across launches."""
        from repro.kernels.fixpoint_kernel import fit_lane_tile
        tile = (n_lanes if self.resident_lane_tile in (0, None)
                else self.resident_lane_tile)
        tile = fit_lane_tile(cm, tile, n_lanes, resident=True,
                             max_depth=max_depth, pool_size=pool_size,
                             interpret=self.interpret)
        return -(n_lanes // -tile)

    def superstep_launch(self, cm: CompiledModel, subs_lb, subs_ub, st,
                         gbest, it, pool_head, *, opts):
        """One K-superstep megakernel launch; returns
        ``(st', gbest', it', pool_head', stopped)``."""
        from repro.kernels.fixpoint_kernel import search_pallas
        return search_pallas(
            cm, subs_lb, subs_ub, st, gbest, it, pool_head,
            supersteps=self.supersteps_per_launch,
            lane_tile=self.resident_lane_tile,
            max_sweeps=self.max_sweeps,
            max_fixpoint_iters=opts.max_fixpoint_iters,
            var_strategy=opts.var_strategy,
            val_strategy=opts.val_strategy,
            stop_on_first=opts.stop_on_first,
            interpret=self.interpret)


_REGISTRY: Dict[str, Callable[..., PropagationBackend]] = {}


def register_backend(name: str,
                     factory: Callable[..., PropagationBackend]) -> None:
    """Register a backend factory under `name` (last registration wins —
    deliberate, so downstream code can swap in a tuned kernel)."""
    _REGISTRY[name] = factory


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str, **opts) -> PropagationBackend:
    """Instantiate a registered backend; `opts` go to its factory."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown propagation backend {name!r}; "
            f"available: {', '.join(available_backends())}") from None
    return factory(**opts)


register_backend("gather", GatherBackend)
register_backend("scatter", ScatterBackend)
register_backend("pallas", PallasBackend)
register_backend("pallas_resident", PallasResidentBackend)
