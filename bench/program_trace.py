"""What the program itself writes into a profiler trace, beside what
``bench/trace.py`` keeps.

The serving engine opens host spans named ``serve.*``
(``repro.serve.telemetry.spans``); ``serve.step`` carries the rows its
decode stepped as the stat ``rows``, and the host waits for the device
inside ``serve.sync`` and ``serve.launch``.  The model puts the paged-attention call under the scope
``paged_attention``; on a TPU each device operation's metadata (not the
event) carries its scope path, the HLO ``op_name``
(``jit(fused_decode)/while/body/.../paged_attention/...``), in the stat
``SCOPE_STAT``.  ``jax.profiler``'s ``ProfileData`` shows only the
events' stats, so ``reduce`` reads the metadata's from the serialized
trace.  ``load`` is
``bench.trace.load`` with the result hung on the summary as
``program``, which is where the readers ``metrics/host_step_ms.py``,
``decode_rows.py`` and ``paged_attention_ms.py`` look; that summary
also names each idle gap after the innermost span over it, the
program's included.  On a trace of a program without the spans or the
scope every list here is empty, and those readers return None.  Times
are seconds, on the trace's clock.

``load``, ``summarize_with_program`` and ``ProgramSummary`` stand in
for an edit of ``bench/trace.py``: once its ``summarize`` keeps
``reduce``'s result and its ``host_span_at`` sees the program's spans,
they and ``bench/trace_program.py`` go.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

from bench.trace import (DEVICE_PREFIX, WINDOW, Event, TraceSummary,
                         summarize, union)

PREFIX = "serve."
STEP = "serve.step"
# the spans in which the host waits for the device
WAITS = ("serve.sync", "serve.launch")
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramTrace:
    window: Tuple[float, float]
    spans: List[Span]               # serve.* spans overlapping the window
    scopes: Dict[str, str]          # device 0 operation name -> its scope

    def steps(self) -> List[Span]:
        """``serve.step`` spans begun in the window."""
        w0, w1 = self.window
        return [s for s in self.spans
                if s.name == STEP and w0 <= s.start <= w1]

    def split(self) -> List[Dict[str, float]]:
        """For each of ``steps()``: the seconds inside it of each span in
        ``WAITS`` that begins in it, and as ``host`` the rest."""
        waits = {w: sorted((s.start, s.end) for s in self.spans
                           if s.name == w) for w in WAITS}
        starts = {w: [a for a, _ in iv] for w, iv in waits.items()}
        out = []
        for st in self.steps():
            row = {}
            for w, iv in waits.items():
                lo = bisect.bisect_left(starts[w], st.start)
                hi = bisect.bisect_right(starts[w], st.end)
                row[w] = sum(min(b, st.end) - a for a, b in iv[lo:hi])
            row["host"] = st.dur - sum(row.values())
            out.append(row)
        return out

    def in_scope(self, name: str, scope: str) -> bool:
        """Whether operation ``name`` runs under ``scope``, a component
        of its scope path."""
        return scope in self.scopes.get(name, "").split("/")

    def scoped_s(self, ops: List[Event], scope: str) -> float:
        """Seconds (the union of their intervals) of the operations in
        ``ops`` that run under ``scope``."""
        return sum(b - a for a, b in union(
            [(e.start, e.end) for e in ops if self.in_scope(e.name, scope)]))


def reduce(profile, window: Tuple[float, float],
           xspace: bytes) -> ProgramTrace:
    """The program's spans in ``profile`` and the device scopes in
    ``xspace``, the same trace serialized."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        a, b = e.start_ns * 1e-9, e.end_ns * 1e-9
                        if b >= window[0] and a <= window[1]:
                            spans.append(Span(e.name, a, b, dict(e.stats)))
    spans.sort(key=lambda s: s.start)
    devices = sorted(p.name for p in profile.planes
                     if p.name.startswith(DEVICE_PREFIX))
    scopes = metadata_stat(xspace, devices[0], SCOPE_STAT) if devices else {}
    return ProgramTrace(window, spans, scopes)


@dataclasses.dataclass
class ProgramSummary(TraceSummary):
    """A ``TraceSummary`` with the program's part, whose ``idle_gaps``
    name each gap after the innermost span that covers the most of it,
    the program's spans included."""
    program: Optional[ProgramTrace] = None

    def host_span_at(self, a: float, b: float) -> str:
        best, name = (0.0, 0.0), "none"
        spans = [e for e in self.host if e.name != WINDOW]
        for e in spans + (self.program.spans if self.program else []):
            o = min(b, e.end) - max(a, e.start)
            if o > 0 and (o, -e.dur) > best:
                best, name = (o, -e.dur), e.name
        return name


def load(log_dir: str) -> Optional[ProgramSummary]:
    """``bench.trace.load`` with the program's part as ``program``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    with open(max(files, key=os.path.getmtime), "rb") as f:
        return summarize_with_program(f.read())


def summarize_with_program(xspace: bytes) -> ProgramSummary:
    """``bench.trace.summarize`` of a serialized trace, with
    ``program``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(xspace)
    summary = summarize(profile)
    return ProgramSummary(**vars(summary), program=reduce(
        profile, summary.window, xspace))


# -- the metadata's stats, from the serialized XSpace (tsl's xplane.proto:
# XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5, maps
# of key 1 to value 2; XEventMetadata.name 2, .stats 5; XStatMetadata.name
# 2; XStat.metadata_id 1, .str_value 5, .ref_value 7, a stat_metadata id
# whose name is the string)

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized message: an int for a
    varint, a view of the bytes for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in a serialized XSpace")
        yield key >> 3, v


def _map_values(entries) -> Dict[int, object]:
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def metadata_stat(xspace: bytes, plane: str, stat: str) -> Dict[str, str]:
    """Event name -> the string stat ``stat`` on the event's metadata, in
    the plane named ``plane``."""
    for f, raw in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(raw))
        if not any(k == 2 and bytes(v).decode() == plane
                   for k, v in fields):
            continue
        names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                 for k, v in _map_values(
                     [v for k, v in fields if k == 5]).items()}
        out: Dict[str, str] = {}
        for md in _map_values([v for k, v in fields if k == 4]).values():
            md = list(_fields(md))
            for k, v in md:
                st = dict(_fields(v)) if k == 5 else {}
                if names.get(st.get(1)) == stat:
                    name = next((bytes(w).decode() for j, w in md
                                 if j == 2), "")
                    out[name] = (bytes(st[5]).decode() if 5 in st
                                 else names.get(st.get(7), ""))
        return out
    return {}
