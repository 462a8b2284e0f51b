"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time, device time per program (HLO module),
exposed collective time, the top device operations and the longest idle
gaps with what the host was doing in them.

Device planes are those named ``/device:<X>:<n>`` with a number ``n``
(``/device:TPU:0``; not ``/device:CUSTOM:Megascale Trace``).
On each, operation intervals come from the ``XLA Ops`` line and program
runs from the ``XLA Modules`` line; a plane without those lines
contributes every event of its lines as an operation.  Busy time is the
union of operation intervals inside the window, never their sum, so
overlapping operations (async copies, collectives beside compute) count
once.  Host spans are the events of the host plane's threads, such as the
benchmark's own ``TraceAnnotation`` ranges.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                      # [start_ns, end_ns)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|alltoall", re.I)
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")


@dataclass
class Event:
    name: str
    start: int
    end: int


@dataclass
class Device:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host: List[Event]

    def host_span(self, name: str) -> Optional[Interval]:
        """First..last extent of the host spans called ``name``."""
        spans = [e for e in self.host if e.name == name]
        if not spans:
            return None
        return min(e.start for e in spans), max(e.end for e in spans)


def _events(line) -> List[Event]:
    """A line's events; an HLO operation is named by its instruction
    (``%fusion.3``), not its whole text."""
    return [Event(e.name.split(" = ")[0], int(e.start_ns),
                  int(e.start_ns + e.duration_ns)) for e in line.events]


def from_profile(pd) -> Trace:
    """``jax.profiler.ProfileData`` -> :class:`Trace`."""
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                dev.ops = _events(lines[OPS_LINE])
            else:
                dev.ops = [e for ln in plane.lines
                           if ln.name != MODULES_LINE for e in _events(ln)]
            if MODULES_LINE in lines:
                dev.modules = _events(lines[MODULES_LINE])
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or a text-format XSpace (``.pbtxt``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".pbtxt"):
        with open(path) as f:
            return from_profile(ProfileData.from_text_proto(f.read()))
    return from_profile(ProfileData.from_file(str(path)))


# --- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted ``a`` not covered by disjoint ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# --- reductions ---------------------------------------------------------------

def busy_ns(dev: Device, window: Interval) -> int:
    """Time inside ``window`` in which any operation ran on ``dev``."""
    return length(clip(union((e.start, e.end) for e in dev.ops), window))


def mean_busy_s(trace: Trace, window: Interval) -> float:
    """Busy seconds averaged over the trace's devices."""
    if not trace.devices:
        return 0.0
    return sum(busy_ns(d, window) for d in trace.devices) \
        / len(trace.devices) / 1e9


def module_runs(dev: Device, prefix: str, window: Interval) -> List[Event]:
    """Runs of the programs whose name starts with ``prefix`` that start
    inside ``window``."""
    lo, hi = window
    return [e for e in dev.modules
            if e.name.startswith(prefix) and lo <= e.start < hi]


def exposed_collective_ns(dev: Device, window: Interval) -> int:
    """Collective time inside ``window`` during which no other operation
    runs on ``dev``."""
    coll = union((e.start, e.end) for e in dev.ops if COLLECTIVE.search(e.name))
    comp = union((e.start, e.end) for e in dev.ops
                 if not COLLECTIVE.search(e.name))
    return length(clip(subtract(coll, comp), window))


def top_ops(trace: Trace, window: Interval, n: int = 10):
    """[[op name, seconds]] of the operations that took most device time
    inside ``window``, summed over devices and averaged per device."""
    tot: Dict[str, int] = {}
    for d in trace.devices:
        for e in d.ops:
            for s, t in clip([(e.start, e.end)], window):
                tot[e.name] = tot.get(e.name, 0) + (t - s)
    k = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(trace: Trace, window: Interval, n: int = 10):
    """[[what the host was doing, seconds]] of the longest idle gaps on
    the first device inside ``window``; a gap is named after the host
    span that covers most of it, or ``host:untraced``."""
    if not trace.devices:
        return []
    busy = clip(union((e.start, e.end) for e in trace.devices[0].ops),
                window)
    gaps = subtract([window], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        cover: Dict[str, int] = {}
        for h in trace.host:
            ov = min(h.end, e) - max(h.start, s)
            if ov > 0 and h.name != "bench.window":
                cover[h.name] = cover.get(h.name, 0) + ov
        what = max(cover, key=cover.get) if cover else "host:untraced"
        out.append([what, (e - s) / 1e9])
    return out
