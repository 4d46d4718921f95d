"""The benchmark's own spans around two layers of the program, and the
slices of the window that the profiler traces.

A ``--trace 1`` run wraps, for its window only, the calls that
`Solver.solve` makes into

* the host EPS decomposition's fixpoint (``repro.core.eps.fixpoint``,
  one call per split), and
* the chunk runner (``repro.core.api.CompiledRunner.__call__``), timed
  until its result is ready, which the solve loop waits for next;

and records, per solve, when it was called, when its first chunk-runner
call began, and the host seconds the runner calls took.  Nothing of the
program is edited; the wrappers are removed when the window closes.

The profiler traces only a slice of the window: a job-shop decomposition
runs about 1.7 million device operations per second, and the TPU's
trace buffers and the profiler's collection do not hold a whole window.
The traffic file gives the slice as ``{"at": "window", "seconds": s}``:
it opens when the window opens and is stopped, with its collection, at
the first wrapped call after ``s`` seconds.  Every solve that overlaps
the slice, from its opening to the end of its collection, is marked
``disturbed`` and left out of the per-layer numbers.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

AT = ("window",)


@dataclasses.dataclass
class SolveSpan:
    t_call: float
    t_done: Optional[float] = None
    t_first_runner: Optional[float] = None
    runner_s: float = 0.0
    disturbed: bool = False


@dataclasses.dataclass
class Slice:
    at: str
    seconds: float
    t_open: Optional[float] = None     # when the trace started
    t_end: Optional[float] = None      # when its collection ended


class Spans:
    """``start_trace(k)`` and ``stop_trace()`` open and close the profiler
    for slice ``k``."""

    def __init__(self, slices: Sequence[dict],
                 start_trace: Callable[[int], None],
                 stop_trace: Callable[[], None]):
        self.slices = [Slice(at=s["at"], seconds=float(s["seconds"]))
                       for s in slices]
        bad = [s.at for s in self.slices if s.at not in AT]
        if bad:
            raise ValueError(f"trace slices at {bad}: only {AT} exist")
        self._start_trace = start_trace
        self._stop_trace = stop_trace
        self._open: Optional[int] = None     # index of the running slice
        self.solves: List[SolveSpan] = []
        self._restore: List[Callable[[], None]] = []

    # -- the window's side ------------------------------------------------

    def start(self) -> None:
        self._install()
        self._maybe_open("window")

    def begin_solve(self) -> None:
        self.solves.append(SolveSpan(t_call=time.time()))

    def end_solve(self) -> None:
        span = self.solves[-1]
        span.t_done = time.time()
        span.disturbed = any(self._overlaps(s, span) for s in self.slices)

    def done(self) -> bool:
        """Every slice has been traced and collected."""
        return all(s.t_end is not None for s in self.slices)

    def close(self) -> None:
        """Restore the program and stop a trace that still runs."""
        for undo in reversed(self._restore):
            undo()
        self._restore.clear()
        self._stop(self._open)

    # -- the wrappers -------------------------------------------------------

    def _install(self) -> None:
        import jax
        from repro.core import api, eps

        fixpoint = eps.fixpoint
        runner_call = api.CompiledRunner.__call__

        def traced_fixpoint(*a, **k):
            self._maybe_stop()
            return fixpoint(*a, **k)

        def timed_runner_call(runner, *a, **k):
            self._maybe_stop()
            t0 = time.time()
            out = jax.block_until_ready(runner_call(runner, *a, **k))
            span = self.solves[-1]
            if span.t_first_runner is None:
                span.t_first_runner = t0
            span.runner_s += time.time() - t0
            return out

        eps.fixpoint = traced_fixpoint
        api.CompiledRunner.__call__ = timed_runner_call
        self._restore += [lambda: setattr(eps, "fixpoint", fixpoint),
                          lambda: setattr(api.CompiledRunner, "__call__",
                                          runner_call)]

    # -- the slices -----------------------------------------------------------

    def _next(self) -> Optional[int]:
        for k, s in enumerate(self.slices):
            if s.t_open is None:
                return k
        return None

    def _maybe_open(self, at: str) -> None:
        k = self._next()
        if k is None or self.slices[k].at != at or self._open is not None \
                or any(s.t_end is None for s in self.slices[:k]):
            return
        self._open = k
        self._start_trace(k)
        self.slices[k].t_open = time.time()

    def _maybe_stop(self) -> None:
        k = self._open
        if k is not None and \
                time.time() - self.slices[k].t_open >= self.slices[k].seconds:
            self._stop(k)

    def _stop(self, k: Optional[int]) -> None:
        if k is None or self._open != k:
            return
        self._stop_trace()                    # collects: seconds
        self.slices[k].t_end = time.time()
        self._open = None

    @staticmethod
    def _overlaps(s: Slice, span: SolveSpan) -> bool:
        if s.t_open is None:
            return False
        end = s.t_end if s.t_end is not None else float("inf")
        return s.t_open < span.t_done and span.t_call < end


def _kept(run):
    return [(s, a) for s, a in zip(run.spans.solves, run.answers)
            if not s.disturbed and s.t_first_runner is not None
            and a.result is not None]


def pre_search_s(run) -> Optional[float]:
    """Mean host seconds from the call into a solve to its first
    chunk-runner call: EPS decomposition, pool padding and runner
    lookup, before any search runs."""
    gaps = [s.t_first_runner - s.t_call for s, _ in _kept(run)]
    return sum(gaps) / len(gaps) if gaps else None


def superstep_ms(run) -> Optional[float]:
    """Host milliseconds of chunk-runner calls, until ready, per search
    superstep the same solves ran."""
    keep = _kept(run)
    steps = sum(a.result.n_supersteps for _, a in keep)
    secs = sum(s.runner_s for s, _ in keep)
    return 1e3 * secs / steps if steps and secs else None
