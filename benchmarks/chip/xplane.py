"""From a profiler trace (``.xplane.pb``) to device times.

The profiler writes one plane per chip (``/device:TPU:<n>``) with a line
of XLA operations and a line of XLA programs (modules), each event with
a start and a duration in nanoseconds from the start of the trace.
:func:`load` keeps those two lines of each chip; the functions below
reduce them.  Nothing here knows a program or kernel name: the metric
readers match the names they need.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]     # name, start_ns, duration_ns


@dataclasses.dataclass
class DeviceTrace:
    device: int
    ops: List[Event]
    modules: List[Event]


def load(path: str) -> List[DeviceTrace]:
    """Every chip's operations and programs in the trace at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = _events(lines.get(OPS_LINE))
        modules = _events(lines.get(MODULES_LINE))
        out.append(DeviceTrace(int(m.group(1)), ops, modules))
    return sorted(out, key=lambda d: d.device)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def clip(events: Sequence[Event], t0: float, t1: float,
         origin: float = 0.0) -> List[Event]:
    """The parts of ``events`` that lie in [t0, t1] (ns), with their
    starts counted from ``origin``."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a - origin, b - a))
    return out


def busy_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted (start, end)."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[Tuple[float, float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in busy_intervals(events))


def time_matching(events: Sequence[Event], pattern: str) -> float:
    """Total ns of the events whose name matches ``pattern`` (a regular
    expression searched in the name)."""
    rx = re.compile(pattern)
    return sum(d for name, _, d in events if rx.search(name))


# Control-flow operations hold the operations of their bodies, which the
# trace lists as well.
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s]")


def top_ops(devs: Sequence[DeviceTrace], n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` operations with most device time, in seconds, averaged
    over the chips; a loop or branch counts through its body's
    operations, not as one of its own."""
    tot: Dict[str, float] = {}
    for dev in devs:
        for name, _, d in dev.ops:
            if not CONTAINERS.match(name):
                tot[name] = tot.get(name, 0.0) + d
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / len(devs) / 1e9) for name, ns in ranked]


def idle_gaps(dev: DeviceTrace, t0: float, t1: float, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``n`` longest stretches of [t0, t1] in which no operation ran
    on ``dev``, in seconds, each named by the program that ran before
    it.  No span of the program says yet what the host was doing."""
    busy = busy_intervals(clip(dev.ops, t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    ends = sorted((s + d, name) for name, s, d in dev.modules)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        before = [name for end, name in ends if end <= a]
        where = f"after {before[-1]}" if before else "at the trace start"
        out.append((f"unattributed, {where}", (b - a) / 1e9))
    return out
