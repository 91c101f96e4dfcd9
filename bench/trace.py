"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From the device planes (``/device:TPU:<n>``): the intervals in which an
operation ran (line ``XLA Ops``), the programs that ran (line ``XLA
Modules``, one event per executable launch) and each operation's name
(on a TPU, the text of its HLO instruction).  From the host plane: the
benchmark's own spans (``bench.*``, written by ``serve_loop``), which
give the measured window and tell what the host was doing in each idle
gap of the device.

Everything is clipped to the span named ``bench.window``.  Times are
seconds.  ``TraceSummary`` is plain data, so the metric readers and the
tests need no trace file.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    n_devices: int
    ops: List[List[Event]]          # per device, clipped to the window
    modules: List[List[Event]]      # per device, started in the window
    host: List[Event]               # bench.* spans overlapping the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which any operation ran, averaged over devices."""
        return sum(_length(union([(e.start, e.end) for e in dev]))
                   for dev in self.ops) / max(self.n_devices, 1)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every gap between device operations inside the window (device
        0), named after the host span that overlaps it most."""
        if not self.ops:
            return []
        busy = union([(e.start, e.end) for e in self.ops[0]])
        gaps, t = [], self.window[0]
        for a, b in busy + [(self.window[1], self.window[1])]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        return [(self.host_span_at(a, b), b - a) for a, b in gaps]

    def host_span_at(self, a: float, b: float) -> str:
        best, name = 0.0, "none"
        for e in self.host:
            if e.name == WINDOW:
                continue
            o = min(b, e.end) - max(a, e.start)
            if o > best:
                best, name = o, e.name
        return name

    def module_time(self, match: str) -> Tuple[int, float]:
        """(launches, device seconds) of programs whose name contains
        ``match``, on device 0."""
        evs = [e for e in (self.modules[0] if self.modules else [])
               if match in e.name]
        return len(evs), sum(e.dur for e in evs)

    def op_time(self, names: Sequence[str]) -> Tuple[int, float]:
        """(count, device seconds) of operations whose name contains any
        of ``names``, on device 0."""
        evs = [e for e in (self.ops[0] if self.ops else [])
               if any(m in e.name for m in names)]
        return len(evs), sum(e.dur for e in evs)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` operations with the most self time on device 0 (an
        operation's time less that of the operations nested in it, as a
        loop holds its body), by short name (``%fusion.3``)."""
        tot: Dict[str, float] = defaultdict(float)
        stack: List[List] = []              # [event, time of children]

        def close(item):
            e, kids = item
            tot[e.name.split(" = ", 1)[0]] += e.dur - kids

        for e in sorted(self.ops[0] if self.ops else [],
                        key=lambda e: (e.start, -e.end)):
            while stack and stack[-1][0].end <= e.start:
                close(stack.pop())
            if stack:
                stack[-1][1] += e.dur
            stack.append([e, 0.0])
        while stack:
            close(stack.pop())
        return sorted(tot.items(), key=lambda x: -x[1])[:k]


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _length(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
            for e in line.events]


def _clip(evs: List[Event], w: Interval) -> List[Event]:
    out = []
    for e in evs:
        a, b = max(e.start, w[0]), min(e.end, w[1])
        if b > a:
            out.append(Event(e.name, a, b))
    return out


def summarize(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host: List[Event] = []
    dev_ops: Dict[str, List[Event]] = {}
    dev_mods: Dict[str, List[Event]] = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops.setdefault(plane.name, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    dev_mods.setdefault(plane.name, []).extend(_events(line))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(HOST_PREFIX))
    windows = [e for e in host if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} {WINDOW} spans")
    w = (windows[0].start, windows[0].end)
    names = sorted(set(dev_ops) | set(dev_mods))
    return TraceSummary(
        window=w, n_devices=len(names),
        ops=[_clip(dev_ops.get(n, []), w) for n in names],
        modules=[[e for e in dev_mods.get(n, []) if w[0] <= e.start <= w[1]]
                 for n in names],
        host=[e for e in host if e.end >= w[0] and e.start <= w[1]])


def load(log_dir: str) -> Optional[TraceSummary]:
    """The summary of the newest trace under ``log_dir``, or None."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return summarize(ProfileData.from_file(max(files, key=os.path.getmtime)))
