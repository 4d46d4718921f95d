"""Reduction of profiler traces of slices of the window to the device's
busy and idle time.

On a TPU the trace holds, per chip, a plane ``/device:TPU:<k>`` whose
line ``XLA Modules`` has one event per executable run on that chip, and
a host plane ``/host:CPU`` whose solving thread carries the benchmark's
own spans (``bench.window`` where the window opens, ``bench.solve``
around each call into `Solver.solve`) beside the runtime's dispatch
events (``PjitFunction(fixpoint)``, ``np.asarray(jax.Array)``, ...).
Host and device events share one clock.

The device is counted busy while one of its executables runs (the
union of the ``XLA Modules`` intervals); a slice runs from its opening
mark (``bench.window``) to the last event recorded.  The TPU records
every operation too, and no profiler setting tried turns that off
(`perfbench/spans.py` says why the trace is therefore cut into slices).

Everything below works on a plain list of events ``[plane, line, name,
start_ns, duration_ns]``, so the reduction is tested on a small
recorded trace (`tests/data`).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

SOLVE_SPAN = "bench.solve"
WINDOW_MARK = "bench.window"
MODULE_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# Profiler settings the run traces with: no Python tracer, and the
# host runtime's dispatch and transfer events.
PROFILE_OPTIONS = dict(python_tracer_level=0, host_tracer_level=2)

Interval = Tuple[float, float, str]


def module_name(name: str) -> str:
    """``jit_fixpoint(1234...)`` -> ``jit_fixpoint``."""
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals: Sequence[Interval], lo: float, hi: float):
    """Merged ``[start, end)`` spans of ``intervals`` clipped to
    ``[lo, hi)``."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Trace:
    modules: List[List[Interval]]     # per chip used, sorted by start
    host: List[Interval]               # the solving thread's other events
    start: float                       # the slice's opening
    end: float                         # the last event recorded
    longest_host_s: float = 0.0

    # -- construction -----------------------------------------------------

    @classmethod
    def from_events(cls, events, n_chips: int) -> "Trace":
        devices: Dict[int, List[Interval]] = defaultdict(list)
        marks: Dict[Tuple[str, str], List[float]] = defaultdict(list)
        by_line: Dict[Tuple[str, str], List[Interval]] = defaultdict(list)
        for plane, line, name, start_ns, dur_ns in events:
            iv = (start_ns * 1e-9, (start_ns + dur_ns) * 1e-9, name)
            m = DEVICE_PLANE.match(plane)
            if m and line == MODULE_LINE:
                devices[int(m.group(1))].append(iv)
            elif plane == HOST_PLANE:
                by_line[(plane, line)].append(iv)
                if name in (WINDOW_MARK, SOLVE_SPAN):
                    marks[(plane, line)].append(iv[0])
        chips = sorted(devices)[:n_chips]
        if len(chips) < n_chips:
            raise ValueError(f"trace has module events of {len(chips)} "
                             f"chip(s), the run used {n_chips}")
        if not marks:
            raise ValueError(f"trace has no {WINDOW_MARK!r} or "
                             f"{SOLVE_SPAN!r} event")
        line = min(marks, key=lambda k: min(marks[k]))
        host = sorted(iv for iv in by_line[line]
                      if iv[2] not in (WINDOW_MARK, SOLVE_SPAN))
        modules = [sorted(devices[c]) for c in chips]
        start = min(marks[line])
        end = max([e for _, e, _ in host]
                  + [e for mods in modules for _, e, _ in mods]
                  + [e for _, e, n in by_line[line] if n == SOLVE_SPAN])
        return cls(modules=modules, host=host, start=start, end=end,
                   longest_host_s=max((e - s for s, e, _ in host),
                                      default=0.0))

    @classmethod
    def from_dir(cls, directory: str, n_chips: int) -> "Trace":
        import jax
        found = sorted(glob.glob(os.path.join(
            directory, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        data = jax.profiler.ProfileData.from_file(found[-1])
        return cls.from_events(_events(data), n_chips)

    # -- the window and the device -----------------------------------------

    def window_s(self) -> float:
        return self.end - self.start

    def _busy(self, chip: int) -> float:
        return sum(e - s for s, e in
                   _union(self.modules[chip], self.start, self.end))

    def busy_s(self) -> float:
        """Seconds in which an executable ran, averaged over chips."""
        return sum(self._busy(c) for c in range(len(self.modules))) \
            / len(self.modules)

    def idle_share(self) -> float:
        """Per cent of the slice in which the busiest chip ran
        nothing."""
        return 100.0 * (1.0 - self.busiest_s() / self.window_s())

    # -- breakdown ------------------------------------------------------------

    def busiest_s(self) -> float:
        return max(self._busy(c) for c in range(len(self.modules)))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The executables that took most device time (first chip), and
        the first chip's idle time by what the solving thread was doing
        in it: the runtime event overlapping each gap longest, or
        ``python`` where the thread ran Python between runtime calls."""
        return _rank(*self._parts(), top)

    def _parts(self):
        lo, hi = self.start, self.end
        dev: Dict[str, float] = defaultdict(float)
        for s, e, name in self.modules[0]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                dev[module_name(name)] += e - s
        busy = _union(self.modules[0], lo, hi)
        edges = [lo] + [x for b in busy for x in b] + [hi]
        idle: Dict[str, float] = defaultdict(float)
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, best_len = "python", 0.0
            for s, e, name in self._host_overlapping(g0, g1):
                ov = min(e, g1) - max(s, g0)
                if ov > best_len:
                    best, best_len = name, ov
            idle[best] += g1 - g0
        return dev, idle

    def _host_overlapping(self, a: float, b: float):
        i = bisect.bisect_left(self.host, (a - self.longest_host_s,))
        while i < len(self.host) and self.host[i][0] < b:
            s, e, name = self.host[i]
            if e > a:
                yield s, e, name
            i += 1


def _rank(dev, idle, top):
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return dict(device_ops=rank(dev), idle_gaps=rank(idle))


class Slices:
    """The traced slices of one run, read as one window: busy and window
    seconds add up, and the idle share is that of the sums."""

    def __init__(self, traces: Sequence[Trace]):
        self.traces = list(traces)

    def window_s(self) -> float:
        return sum(t.window_s() for t in self.traces)

    def busy_s(self) -> float:
        return sum(t.busy_s() for t in self.traces)

    def idle_share(self) -> float:
        busiest = sum(t.busiest_s() for t in self.traces)
        return 100.0 * (1.0 - busiest / self.window_s())

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        dev: Dict[str, float] = defaultdict(float)
        idle: Dict[str, float] = defaultdict(float)
        for t in self.traces:
            d, i = t._parts()
            for k, v in d.items():
                dev[k] += v
            for k, v in i.items():
                idle[k] += v
        return _rank(dev, idle, top)


def _events(data) -> List[list]:
    """``[plane, line, name, start_ns, duration_ns]`` of the events the
    reduction reads: module events of every TPU and every event of the
    host's threads."""
    out = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not is_dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if is_dev and line.name != MODULE_LINE:
                continue
            for ev in line.events:
                out.append([plane.name, line.name, ev.name, ev.start_ns,
                            ev.duration_ns])
    return out
