"""Profiler traces: record one, and reduce it to device intervals and host
spans.

A trace is read with ``jax.profiler.ProfileData`` (nothing but JAX). The
device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per operation that ran. The host plane holds the
benchmark's own ``jax.profiler.TraceAnnotation`` spans (``bench.window``,
``bench.unit``) and the runtime's host events, all on one clock with the
device events.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Tuple

OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_MIN_NS = 50_000


@dataclasses.dataclass
class Event:
    start: float        # ns
    end: float          # ns
    name: str
    category: str = ""  # the op's hlo category or long name, where given


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # plane name -> op events
    host: List[Tuple[str, Event]]       # (thread line, event)

    def window(self, span: str = WINDOW_SPAN) -> Tuple[float, float]:
        """(start, end) ns of the last host span named ``span``."""
        found = [e for _, e in self.host if e.name == span]
        if not found:
            raise ValueError(f"trace has no host span {span!r}")
        w = max(found, key=lambda e: e.start)
        return w.start, w.end


@contextlib.contextmanager
def recording(keep: str = ""):
    """Trace the block; yields a dict whose ``"trace"`` is the reduced
    ``Trace`` once the block has ended. Python function tracing is off
    (it would slow the host path it measures); the raw files are deleted
    once read (``keep`` names a path to copy the ``.xplane.pb`` to)."""
    import jax
    out = {}
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        try:
            files = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                     recursive=True))
            if files:
                out["trace"] = load(files[-1])
                if keep:
                    shutil.copy(files[-1], keep)
        finally:
            shutil.rmtree(d, ignore_errors=True)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` file to device op events and host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Tuple[str, Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == OP_LINE] or lines
            evs = []
            for line in ops:
                for e in line.events:
                    st = _stats(e)
                    cat = str(st.get("hlo_category") or st.get("long_name")
                              or "")
                    evs.append(Event(float(e.start_ns),
                                     float(e.start_ns + e.duration_ns),
                                     e.name, cat))
            devices[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:CPU"):
            # the benchmark's spans, and host events long enough to
            # explain an idle gap (short runtime events are legion)
            for line in plane.lines:
                for e in line.events:
                    if (e.duration_ns >= HOST_MIN_NS
                            or e.name.startswith("bench.")):
                        host.append((line.name, Event(
                            float(e.start_ns),
                            float(e.start_ns + e.duration_ns), e.name)))
    return Trace(devices, host)


# ------------------------------------------------------------- reduction
def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the interval [lo, hi]; those outside it dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(s, t, e.name, e.category))
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint pairs."""
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(a, b) for a, b in merged]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one event runs."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def idle_gaps(events: List[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which no event runs, longest first."""
    gaps, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def short_name(name: str) -> str:
    """An op's instruction name ("fusion.12") from the HLO text the
    trace gives as its name ("%fusion.12 = f32[...] fusion(...)")."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its self time: its duration less the durations of
    the events nested directly inside it (a ``while`` op encloses the ops
    of its body on the same line)."""
    out: List[List] = []
    stack: List[List] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        item = [e, e.end - e.start]
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.end - e.start
        stack.append(item)
        out.append(item)
    return [(e, t) for e, t in out]


def time_by_name(events: List[Event], match) -> Tuple[float, int]:
    """(summed duration ns, count) of the events whose name or category
    ``match(text)`` accepts."""
    hit = [e for e in events if match(e.name) or match(e.category)]
    return sum(e.end - e.start for e in hit), len(hit)


def host_activity(trace: Trace, t: float, skip=(WINDOW_SPAN,),
                  depth: int = 3) -> str:
    """What the host was doing at time ``t``: the names of the (at most
    ``depth``) outermost host spans that cover it, joined by ' > ' (the
    benchmark's own spans and the runtime's host events alike)."""
    cover = [e for _, e in trace.host
             if e.start <= t <= e.end and e.name not in skip]
    cover.sort(key=lambda e: (e.start, -e.end))
    return " > ".join(e.name for e in cover[:depth]) or "no host span"
