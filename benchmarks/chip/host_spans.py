"""The program's host spans on the device trace's clock.

The serving path opens ``jax.profiler.TraceAnnotation`` spans named
``serve.*`` (``repro.serve.spans``).  With the profiler's host tracer on
they land on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
chips' programs, one line per thread, beside JAX's own host events such
as ``PjitFunction(<name>)``, the host side of each jitted call.

The two planes agree only up to an offset: on a recorded v5e trace a
decode window starts on the chip 0.7 ms before the host's call of it.
No program can start before the host called it, so :func:`shift_of`
pairs each host call of a program with the chip's next run of it and
takes the most negative difference as the host's shift.

With the spans shifted and clipped to the device slice,
:func:`innermost` lays out which span of one thread was innermost at
each instant, and :func:`idle_by_span` / :func:`name_gaps` put every
stretch in which no operation ran on a chip down to it.  Past the
pattern of the events to load, nothing here knows a span's or a
program's name: the callers pass them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import xplane

HOST_PLANE = "/host:CPU"
# The program's spans and JAX's host side of each jitted call.
HOST_EVENTS = re.compile(r"^(serve\.|PjitFunction\()")
NO_SPAN = "unattributed"

Segment = Tuple[float, float, str]      # start_ns, end_ns, span name


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: float                # ns from the trace's start
    dur: float                  # ns
    thread: int                 # the host line (one per thread)
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(path: str, pattern: re.Pattern = HOST_EVENTS) -> List[HostSpan]:
    """The host events of the trace at ``path`` whose names match
    ``pattern``, with their stats, in order of start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if pattern.search(e.name):
                    out.append(HostSpan(e.name, float(e.start_ns),
                                        float(e.duration_ns), i,
                                        dict(e.stats)))
    return sorted(out, key=lambda s: (s.start, -s.dur))


def outermost(spans: Sequence[HostSpan], name: str) -> List[HostSpan]:
    """The spans called ``name`` that no other span of that name on
    their thread holds (JAX nests a jitted call's host events)."""
    same = sorted((s for s in spans if s.name == name),
                  key=lambda s: (s.thread, s.start, -s.dur))
    out: List[HostSpan] = []
    for s in same:
        if out and out[-1].thread == s.thread and s.end <= out[-1].end:
            continue
        out.append(s)
    return sorted(out, key=lambda s: s.start)


def shift_of(dev: xplane.DeviceTrace, spans: Sequence[HostSpan],
             call: str, program: str) -> Optional[float]:
    """The host's offset from ``dev``'s clock, in ns: each outermost
    host event named ``call`` is paired with the first program on
    ``dev`` matching ``program`` that had not ended when it began, and
    the most negative (device start - host start) is returned, or 0
    where none is negative.  ``None`` when nothing pairs."""
    rx = re.compile(program)
    runs = sorted((s, s + d) for name, s, d in dev.modules
                  if rx.search(name))
    deltas = []
    for c in outermost(spans, call):
        nxt = next((a for a, b in runs if b > c.start), None)
        if nxt is not None:
            deltas.append(nxt - c.start)
    if not deltas:
        return None
    return min(0.0, min(deltas))


def shifted(spans: Sequence[HostSpan], by: float) -> List[HostSpan]:
    return [dataclasses.replace(s, start=s.start + by) for s in spans]


def clip(spans: Sequence[HostSpan], t0: float, t1: float,
         origin: float = 0.0) -> List[HostSpan]:
    """The parts of ``spans`` in [t0, t1] (ns), starts counted from
    ``origin``."""
    out = []
    for s in spans:
        a, b = max(s.start, t0), min(s.end, t1)
        if b > a:
            out.append(dataclasses.replace(s, start=a - origin, dur=b - a))
    return out


def on_threads_of(spans: Sequence[HostSpan], name: str) -> List[HostSpan]:
    """The spans on every thread that holds a span called ``name``."""
    threads = {s.thread for s in spans if s.name == name}
    return [s for s in spans if s.thread in threads]


def innermost(spans: Sequence[HostSpan]) -> List[Segment]:
    """Which span was innermost at each instant, as sorted disjoint
    segments, for the spans of one thread (they nest).  Instants in no
    span have no segment."""
    out: List[Segment] = []
    stack: List[Tuple[float, str]] = []      # (end, name), innermost last
    cur = -float("inf")

    def until(x: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end
        if stack and x > cur:
            out.append((cur, x, stack[-1][1]))
        cur = max(cur, x)

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        until(s.start)
        end = min(s.end, stack[-1][0]) if stack else s.end
        stack.append((end, s.name))
    until(float("inf"))
    return out


def _overlaps(segments: Sequence[Segment], starts: Sequence[float],
              a: float, b: float) -> Dict[str, float]:
    """ns of [a, b] under each segment's name (``starts``: the
    segments' starts)."""
    got: Dict[str, float] = {}
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segments) and segments[i][0] < b:
        s, e, name = segments[i]
        lo, hi = max(s, a), min(e, b)
        if hi > lo:
            got[name] = got.get(name, 0.0) + hi - lo
        i += 1
    return got


def idle_intervals(dev: xplane.DeviceTrace, t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """The stretches of [t0, t1] in which no operation ran on ``dev``."""
    busy = xplane.busy_intervals(xplane.clip(dev.ops, t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(dev: xplane.DeviceTrace, t0: float, t1: float,
                 segments: Sequence[Segment]) -> Dict[str, float]:
    """Seconds of ``dev``'s idle time in [t0, t1] under each innermost
    span; time under none is counted as ``unattributed``."""
    out: Dict[str, float] = {}
    starts = [x for x, _, _ in segments]
    for a, b in idle_intervals(dev, t0, t1):
        got = _overlaps(segments, starts, a, b)
        rest = (b - a) - sum(got.values())
        if rest > 0:
            got[NO_SPAN] = got.get(NO_SPAN, 0.0) + rest
        for name, ns in got.items():
            out[name] = out.get(name, 0.0) + ns / 1e9
    return out


def name_gaps(dev: xplane.DeviceTrace, t0: float, t1: float,
              segments: Sequence[Segment], waiting: str, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches of ``dev`` in [t0, t1], in
    seconds, each named by the innermost span that covers most of it:
    ``waiting <span>`` for the span ``waiting`` (the wait for work),
    ``host <span>`` for any other, and as ``xplane.idle_gaps`` names it
    where no span covers it.  With no segments the list is
    ``xplane.idle_gaps``'s."""
    gaps = sorted(idle_intervals(dev, t0, t1), key=lambda g: g[0] - g[1])[:n]
    plain = iter(xplane.idle_gaps(dev, t0, t1, n))
    starts = [x for x, _, _ in segments]
    out = []
    for a, b in gaps:
        fallback = next(plain)
        got = _overlaps(segments, starts, a, b)
        if not got:
            out.append(fallback)
            continue
        name = max(got, key=got.get)
        kind = "waiting" if name == waiting else "host"
        out.append((f"{kind} {name}", (b - a) / 1e9))
    return out
