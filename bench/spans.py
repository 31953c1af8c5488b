"""Device idle time divided by what the program's host was doing.

The program opens ``repro:<kind>[:<name>]`` spans around its own work
(``repro.core.ledger.span``): ``step``, ``solve``, ``region``, and inside a
region call ``dispatch``, ``sync``, ``place``, ``exchange`` and
``exchange_wait``; ``readback`` around each scalar the host reads. They
share the device trace's clock, so each device's idle intervals can be cut
by the innermost such span the dispatching thread was in:

- :func:`load` reads a trace as :mod:`bench.trace` does, and keeps each
  event's stats in ``args`` and each host thread apart (a host line's
  name gets ``#<index>``: threads of one process may share a name);
- :func:`idle_by_span` intersects the idle intervals of every device with
  the timeline of the innermost ``repro:`` span on the host line that holds
  ``bench:window``, and splits a gap across spans by time. Idle time under
  no ``repro:`` span is ``unattributed``. Spans on other threads (the halo
  exchange's worker) attribute nothing; they are read by their ``op``.

This reads alongside :func:`bench.trace.reduce`, whose ``idle_gaps`` still
name each gap by the ``bench:`` span over its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as tr

PREFIX = "repro:"
UNATTRIBUTED = "unattributed"
#: kinds no finer than a SIMPLE iteration: idle under them is not put down
#: to any one step of the host's work
COARSE = ("step", UNATTRIBUTED)


@dataclasses.dataclass(frozen=True)
class Event(tr.Event):
    """A trace event with its stats (``op``, ``impl``, ``target``)."""
    args: Optional[dict] = None


def kind_of(name: str) -> str:
    """``repro:region:Amul`` -> ``region``."""
    return name[len(PREFIX):].split(":", 1)[0]


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir`` whose
    plane is a device or the host, in seconds, with its stats."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        host = plane.name == "/host:CPU"
        if not (host or tr.DEVICE_PLANE.match(plane.name)):
            continue
        for i, line in enumerate(plane.lines):
            lname = f"{line.name}#{i}" if host else line.name
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                out.append(Event(plane.name, lname, ev.name, t0,
                                 t0 + ev.duration_ns * 1e-9,
                                 dict(ev.stats) or None))
    return out


def save(events: Sequence[tr.Event], path: str) -> None:
    """Write events as gzipped JSON rows of 6 fields (the 6th: stats)."""
    with gzip.open(path, "wt") as f:
        json.dump([[e.plane, e.line, e.name, e.start, e.end,
                    getattr(e, "args", None)] for e in events], f)


def read_saved(path: str) -> List[Event]:
    """Rows of 5 fields (``bench.trace.save``) or 6 (:func:`save`)."""
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def dispatch_line(events: Sequence[tr.Event]) -> Tuple[str, str]:
    """The (plane, line) of the thread that opened ``bench:window``."""
    for e in events:
        if e.name == tr.WINDOW_SPAN:
            return e.plane, e.line
    raise ValueError(f"no {tr.WINDOW_SPAN!r} span in the trace")


def timeline(spans: Sequence[tr.Event]) -> List[Tuple[float, float, str]]:
    """Sorted, disjoint ``(start, end, kind)`` pieces of one thread's
    time, each under the innermost of ``spans`` (which nest, as spans of
    one thread do); time under none of them is left out."""
    out: List[Tuple[float, float, str]] = []
    stack: List[tr.Event] = []
    t = float("-inf")

    def emit(a, b, e):
        if b > a:
            out.append((a, b, kind_of(e.name)))

    for e in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= e.start:
            top = stack.pop()
            emit(t, top.end, top)
            t = max(t, top.end)
        if stack:
            emit(t, e.start, stack[-1])
        t = max(t, e.start)
        stack.append(e)
    while stack:
        top = stack.pop()
        emit(t, top.end, top)
        t = max(t, top.end)
    return out


def intersect(a: Sequence[Tuple[float, float]],
              pieces: Sequence[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Seconds of disjoint sorted intervals ``a`` under each kind of the
    disjoint sorted ``pieces``."""
    out: Dict[str, float] = {}
    j = 0
    for lo, hi in a:
        while j < len(pieces) and pieces[j][1] <= lo:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < hi:
            s = min(hi, pieces[k][1]) - max(lo, pieces[k][0])
            if s > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + s
            k += 1
    return out


def idle_by_span(events: Sequence[tr.Event],
                 window: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, float]:
    """Idle seconds of the traced window by the kind of the innermost
    ``repro:`` span on the dispatching thread, mean over the devices;
    the values sum to ``bench.trace.reduce(events).idle_s``."""
    lo, hi = window or tr.window_of(events)
    host = dispatch_line(events)
    pieces = timeline([e for e in events if (e.plane, e.line) == host
                       and e.name.startswith(PREFIX)])
    planes = sorted({e.plane for e in events
                     if tr.DEVICE_PLANE.match(e.plane)})
    if not planes:
        raise ValueError("no TPU device plane in the trace")
    out: Dict[str, float] = {}
    for plane in planes:
        busy = tr.union(tr.clip([(e.start, e.end) for e in events
                                 if e.plane == plane and e.line == tr.OPS_LINE],
                                lo, hi))
        idle = tr.subtract([(lo, hi)], busy)
        by_kind = intersect(idle, pieces)
        by_kind[UNATTRIBUTED] = tr.length(idle) - sum(by_kind.values())
        for k, s in by_kind.items():
            out[k] = out.get(k, 0.0) + s / len(planes)
    return out


def coverage(idle: Dict[str, float]) -> float:
    """Share of the idle time under a span finer than a whole step."""
    total = sum(idle.values())
    fine = sum(s for k, s in idle.items() if k not in COARSE)
    return fine / total if total > 0 else 0.0


def by_op(events: Sequence[tr.Event], op: int) -> List[Event]:
    """Every ``repro:`` span of one replayed op, on any thread."""
    return [e for e in events if e.name.startswith(PREFIX)
            and (getattr(e, "args", None) or {}).get("op") == op]
