"""Per-layer readings from what the program reports about itself: the
host phase times and counters each `SolveResult` carries, and the
program's own spans (`repro.obs`), which land in the traced slices on
the solving thread beside the runtime's events.

Every reader returns None where the program has none of it (a program
from before these fields and spans: its `SolveResult` lacks the field),
so the run leaves the metric out; a program that has the field and
leaves it None, or has the fields and records no span in the slice,
fails the run.  Readings from results skip the solves a traced slice
overlapped (``disturbed``, `perfbench.spans`), as the benchmark's own
spans do.
"""

from __future__ import annotations

from typing import List, Optional

from perfbench.trace import _union

# the program's span names all start with "repro." (`repro.obs.PREFIX`)
DECOMPOSE_SPAN = "repro.eps.decompose"
DISPATCH_SPAN = "repro.eps.dispatch"       # one fixpoint and its read-back


def _kept(run, *fields: str) -> Optional[List]:
    """The undisturbed solves' results, or None where there are none or
    the program's results lack one of ``fields``; raises where a kept
    solve reports one of them as None."""
    out = [a.result for s, a in zip(run.spans.solves, run.answers)
           if not s.disturbed and a.result is not None]
    if not out or not all(hasattr(out[0], f) for f in fields):
        return None
    for r in out:
        for f in fields:
            if getattr(r, f) is None:
                raise ValueError(f"a solve of the window reports {f} as "
                                 f"None: the program stopped timing it")
    return out


def decompose_s(run) -> Optional[float]:
    """Mean host seconds a solve spent preparing its pool: EPS
    decomposition, padding and the transfer to the device."""
    kept = _kept(run, "decompose_s")
    return sum(r.decompose_s for r in kept) / len(kept) if kept else None


def decompose_dispatches(run) -> Optional[float]:
    """Mean fixpoint dispatches of a solve's EPS decomposition."""
    kept = _kept(run, "n_decompose_dispatches")
    if not kept:
        return None
    return sum(r.n_decompose_dispatches for r in kept) / len(kept)


def chunk_superstep_ms(run) -> Optional[float]:
    """Host milliseconds of the program's chunk-runner calls, each until
    ready, per search superstep."""
    kept = _kept(run, "search_s")
    steps = sum(r.n_supersteps for r in kept) if kept else 0
    return 1e3 * sum(r.search_s for r in kept) / steps if steps else None


def lockstep_waste(run) -> Optional[float]:
    """Per cent of the lane-sweeps of the superstep fixpoints spent on
    lanes that had already converged: 100 (1 - sweeps / (lanes x
    rounds)), over the undisturbed solves."""
    kept = _kept(run, "n_sweep_rounds", "n_lanes")
    slots = sum(r.n_lanes * r.n_sweep_rounds for r in kept) if kept else 0
    if not slots:
        return None
    return 100.0 * (1.0 - sum(r.n_sweeps for r in kept) / slots)


def _overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two merged, sorted interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def decompose_idle_share(run) -> Optional[float]:
    """Per cent of the traced slices' idle time on the busiest chip that
    falls inside the program's decomposition: its ``eps.decompose``
    spans, and the ``eps.dispatch`` spans of a decomposition still open
    when the slice ended (the profiler keeps a span only once it
    closes)."""
    idle = inside = 0.0
    seen = False
    for t in run.trace.traces:
        spans = [iv for iv in t.host
                 if iv[2] in (DECOMPOSE_SPAN, DISPATCH_SPAN)]
        seen = seen or bool(spans)
        busy = max((_union(m, t.start, t.end) for m in t.modules),
                   key=lambda b: sum(e - s for s, e in b))
        dec = _union(spans, t.start, t.end)
        idle += t.window_s() - sum(e - s for s, e in busy)
        inside += sum(e - s for s, e in dec) - _overlap(dec, busy)
    if not seen:
        if any(hasattr(a.result, "decompose_s") for a in run.answers):
            raise ValueError(f"no {DECOMPOSE_SPAN!r} or {DISPATCH_SPAN!r} "
                             f"span in the traced slices of a program "
                             f"that reports decompose_s")
        return None
    return 100.0 * inside / idle if idle > 0.0 else None
