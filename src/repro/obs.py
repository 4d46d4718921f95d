"""Spans inside the solver, on the profiler's clock.

``span(name)`` marks a stretch of host work::

    with obs.span("repro.eps.decompose"):
        ...

It always enters a `jax.profiler.TraceAnnotation` of the same name, so
the span lands in any profiler trace on the device trace's clock.  When
a `Recorder` is open it also keeps the span in memory: name, start and
end on one monotonic host clock (``time.perf_counter_ns``), its own id,
its parent's and the id of the solve it belongs to.

Every span name starts with `PREFIX`, so a trace reader tells the
program's spans from the runtime's events by one test.  Spans nest by a
per-thread stack.  `solve()` opens a new solve: it and every span under
it carry that solve's id.  It is kept by a recorder only and enters no
annotation: a span over the whole solve would cover every idle gap of a
trace.  With no recorder open a span costs one `TraceAnnotation` enter
and exit plus one check, and nothing is kept.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import List, Optional

import jax

PREFIX = "repro."
SOLVE = PREFIX + "solve"

_lock = threading.Lock()
_open: Optional["Recorder"] = None       # the recorder open in this process


@dataclasses.dataclass
class Span:
    """One recorded span; ``end_ns`` is 0 while it is open."""
    name: str
    start_ns: int
    span_id: int
    parent_id: Optional[int]
    solve_id: Optional[int]
    end_ns: int = 0

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class Recorder:
    """Keeps every span opened, in any thread, while it is open::

        with obs.Recorder() as rec:
            solver.solve(cm)
        decompose = rec.named("repro.eps.decompose")

    One recorder is open in a process at a time."""

    def __init__(self):
        self.spans: List[Span] = []          # in the order they opened
        self._ids = itertools.count(1)
        self._solve_ids = itertools.count(1)
        self._local = threading.local()

    def __enter__(self) -> "Recorder":
        global _open
        with _lock:
            if _open is not None:
                raise RuntimeError("an obs.Recorder is already open")
            _open = self
        return self

    def __exit__(self, *exc) -> None:
        global _open
        with _lock:
            if _open is self:
                _open = None

    # -- reading --------------------------------------------------------------

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children(self, span: Span) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span.span_id]

    def self_ns(self, span: Span) -> int:
        """The span's duration less the time its children cover."""
        covered, reach = 0, span.start_ns
        for c in sorted(self.children(span), key=lambda s: s.start_ns):
            lo, hi = max(c.start_ns, reach), min(c.end_ns, span.end_ns)
            if hi > lo:
                covered += hi - lo
                reach = hi
        return span.duration_ns - covered

    # -- recording --------------------------------------------------------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _begin(self, name: str) -> Span:
        stack = self._stack()
        parent = stack[-1] if stack else None
        if name == SOLVE:
            solve_id = next(self._solve_ids)
        else:
            solve_id = parent.solve_id if parent is not None else None
        rec = Span(name=name, start_ns=time.perf_counter_ns(),
                   span_id=next(self._ids),
                   parent_id=parent.span_id if parent is not None else None,
                   solve_id=solve_id)
        self.spans.append(rec)
        stack.append(rec)
        return rec

    def _end(self, rec: Span) -> None:
        rec.end_ns = time.perf_counter_ns()
        stack = self._stack()
        # by identity, from the top: a span held across a generator's
        # yields may close after spans opened later
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is rec:
                del stack[i]
                break


class span:
    """Context manager for one span (module docstring)."""

    __slots__ = ("name", "_annotation", "_recorder", "_record")
    annotate = True

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._annotation = None
        if self.annotate:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._recorder = _open
        if self._recorder is not None:
            self._record = self._recorder._begin(self.name)
        return self

    def __exit__(self, *exc) -> None:
        if self._recorder is not None:
            self._recorder._end(self._record)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class solve(span):
    """The root span of one solve (module docstring): a new solve id, kept
    by a recorder only."""

    __slots__ = ()
    annotate = False

    def __init__(self):
        super().__init__(SOLVE)
