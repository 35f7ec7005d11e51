"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX: planes
(one per device, one for the host), their lines (a device's op stream, a
host thread), and events with a start and a duration in nanoseconds.
Everything below works on plain ``(start, end, name)`` tuples, so the tests
check it against hand-made timelines as well as against a recorded file.

Definitions (on-chip-measurement guide, section 4):

* busy      union of the intervals in which an operation ran on the device
* idle      the window minus busy
* op time   self time by name: an instant belongs to the innermost event
            that covers it, so a ``while`` is not counted over its body
* collective time   union of the collective ops' intervals; its *exposed*
            part is where no other operation ran on that device
* idle gap  a maximal idle interval, credited to the innermost host span
            (``TraceAnnotation``) open on the harness's thread at the time
"""

from __future__ import annotations

import heapq
import re
from dataclasses import dataclass, field

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = r"^XLA Modules$"
HOST_PLANE = r"^/host:CPU$"
# XLA's opcode spellings and the jax.lax primitives' (an instruction lowered
# from ``lax.all_to_all`` is named ``all_to_all.<n>``, not ``all-to-all.<n>``)
COLLECTIVE_OP = (r"^(all-to-all|all_to_all|all-reduce|all_reduce|psum|pmin|"
                 r"pmax|all-gather|all_gather|reduce-scatter|reduce_scatter|"
                 r"collective-permute|ppermute)")
NO_SPAN = "(no span)"
# A label of the profiler's own where it lost an op's name: no instruction of
# a compiled program is called ``region.<n>`` (its computations are
# ``region_<n>.<m>``).  One plane of a four-chip trace, another from run to
# run, carries a stretch of every step under such labels (PERF.md section 6)
UNNAMED_OP = r"^region\.\d+( |$)"
UNNAMED_SHARE = 0.01                 # of a plane's busy time in the window


# -- intervals ----------------------------------------------------------------

def union(intervals):
    """Merged, sorted ``(start, end)`` list covering the same instants."""
    out = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals if iv[1] > iv[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def clip(intervals, lo, hi):
    """Intervals (with whatever follows start and end) cut to [lo, hi]."""
    return [(max(iv[0], lo), min(iv[1], hi)) + tuple(iv[2:])
            for iv in intervals if iv[1] > lo and iv[0] < hi]


def subtract(a, b):
    """The part of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    j = 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def gaps(busy, lo, hi):
    """Maximal idle intervals of [lo, hi] given busy intervals."""
    return subtract([(lo, hi)], busy)


def flatten(events):
    """Non-overlapping ``(start, end, name)`` segments: every instant some
    event covers goes to the innermost one (latest start, then earliest
    end).  Their total length is the union of the events."""
    evs = sorted((e for e in events if e[1] > e[0]),
                 key=lambda e: (e[0], -e[1]))
    points = sorted({p for e in evs for p in (e[0], e[1])})
    out, heap, i = [], [], 0
    for lo, hi in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= lo:
            s, e, name = evs[i][:3]
            heapq.heappush(heap, (-s, e, i, name))
            i += 1
        while heap and heap[0][1] <= lo:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][2] == name and out[-1][1] == lo:
            out[-1] = (out[-1][0], hi, name)
        else:
            out.append((lo, hi, name))
    return out


def by_name(segments) -> dict:
    """Summed length of non-overlapping segments by name."""
    acc = {}
    for s, e, name in segments:
        acc[name] = acc.get(name, 0.0) + (e - s)
    return acc


def credit(intervals, segments) -> dict:
    """Length of ``intervals`` by the name of the segment covering each
    part; what no segment covers goes to ``NO_SPAN``.  ``segments`` are
    non-overlapping (``flatten``)."""
    acc = {}
    segs = sorted(segments)
    j = 0
    for s, e in union(intervals):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            if a > at:
                acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + (a - at)
            lo, hi = max(a, at), min(b, e)
            if hi > lo:
                acc[name] = acc.get(name, 0.0) + (hi - lo)
            at = max(at, hi)
            k += 1
        if at < e:
            acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + (e - at)
    return acc


# -- the trace ------------------------------------------------------------------

@dataclass
class Device:
    name: str
    op_lines: list = field(default_factory=list)   # one event list per line
    modules: list = field(default_factory=list)    # program executions
    segments: dict = field(default_factory=dict)   # device_segments by window

    @property
    def ops(self):
        return [e for line in self.op_lines for e in line]


@dataclass
class Trace:
    devices: list
    host_lines: dict    # thread (line) name -> events
    named: dict = field(default_factory=dict)      # named_devices by window

    def window(self, pattern: str):
        """(start, end) spanned by the host events whose name matches
        ``pattern`` (the harness's own chunk annotation); without one, by
        the device events."""
        rx = re.compile(pattern)
        hit = [e for line in self.host_lines.values() for e in line
               if rx.search(e[2])]
        if not hit:
            hit = [e for d in self.devices for e in d.ops]
        if not hit:
            return None
        return min(e[0] for e in hit), max(e[1] for e in hit)

    def span_segments(self, anchor: str, spans: str):
        """Innermost-span timeline of the host thread that carries the
        ``anchor`` annotation, over the events matching ``spans``."""
        a, rx = re.compile(anchor), re.compile(spans)
        for line in self.host_lines.values():
            if any(a.search(e[2]) for e in line):
                return flatten([e for e in line if rx.search(e[2])])
        return []


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?)(\w+\[[\d,]*\])")


def op_label(text: str) -> str:
    """``fusion.24 f32[2340001,300]``: the instruction's name and the shape
    it produces (the first, of a tuple), from the HLO text the device's op
    line carries as the event name; any other text is kept as it is."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    return f"{m.group(1)} {m.group(3)}" + ("..." if m.group(2) else "")


_SUFFIX = re.compile(r"(\.(\d+|remat\d*|clone))+(?= |$)")


def op_group(label: str) -> str:
    """``copy f32[2340001,300]`` for ``copy.141.remat3 f32[2340001,300]``:
    the breakdown sums an op over its numbered and rematerialised copies,
    so ten entries show where the time goes and not ten names of one op."""
    return _SUFFIX.sub("", label, count=1)


def _events(line, label=str):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             label(e.name)) for e in line.events]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(data) -> Trace:
    devices, host = [], {}
    for plane in data.planes:
        if re.search(DEVICE_PLANE, plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if re.search(OP_LINE, line.name):
                    dev.op_lines.append(_events(line, op_label))
                elif re.search(MODULE_LINE, line.name):
                    dev.modules.extend(_events(line))
            devices.append(dev)
        elif re.search(HOST_PLANE, plane.name):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(_events(line))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


# -- per-device numbers --------------------------------------------------------

def device_segments(dev: Device, window):
    """Innermost-op segments of every op line, cut to the window (kept: every
    number of a device is read off the same segments)."""
    if window not in dev.segments:
        lo, hi = window
        dev.segments[window] = [seg for line in dev.op_lines
                                for seg in flatten(clip(line, lo, hi))]
    return dev.segments[window]


def busy_seconds(dev: Device, window) -> float:
    return total(device_segments(dev, window)) / 1e9


def op_seconds(dev: Device, window, label=str) -> dict:
    """Self time by op name (or by ``label`` of it), seconds."""
    acc = {}
    for name, ns in by_name(device_segments(dev, window)).items():
        acc[label(name)] = acc.get(label(name), 0.0) + ns / 1e9
    return acc


def matching_seconds(dev: Device, window, pattern: str):
    """(seconds in the ops whose name matches ``pattern``, e.g.
    ``COLLECTIVE_OP``; seconds of it in which no other op ran on this
    device)."""
    rx = re.compile(pattern)
    segs = device_segments(dev, window)
    hit = [s for s in segs if rx.search(s[2])]
    other = [s for s in segs if not rx.search(s[2])]
    return total(hit) / 1e9, total(subtract(hit, other)) / 1e9


def named_devices(trace: Trace, window):
    """``(planes to read op names from, [(plane, share)] left out)``: the ONE
    decision for every reader that goes by an op's name (``trace_scope``,
    ``trace_ops``, the breakdown's ``device_ops``).  A plane with more than
    ``UNNAMED_SHARE`` of its busy time under ``UNNAMED_OP`` labels says
    nothing of what ran in that time — a scope would lose it to ``unscoped``,
    a kernel's mean would count the plane as 0 — so it is left out of the
    mean over the planes, unless every plane is like that (then all are read
    as they are).  Busy and idle time need no name: they read every plane."""
    if window not in trace.named:
        share = [(d, matching_seconds(d, window, UNNAMED_OP)[0]
                  / max(busy_seconds(d, window), 1e-12))
                 for d in trace.devices]
        good = [d for d, s in share if s <= UNNAMED_SHARE]
        trace.named[window] = (good, [(d.name, s) for d, s in share
                                      if s > UNNAMED_SHARE]) \
            if good else (list(trace.devices), [])
    return trace.named[window]


def left_out_text(left_out) -> str:
    return "".join(f"; left out of the means by op name: {name}, {share:.1%} "
                   "of its busy time under region.<n> labels"
                   for name, share in left_out)


def launches(dev: Device, window) -> int:
    """Program executions that started inside the window."""
    lo, hi = window
    return sum(1 for e in dev.modules if lo <= e[0] < hi)


def idle_gaps_by_span(trace: Trace, dev: Device, window, anchor: str,
                      spans: str) -> dict:
    """Idle seconds of ``dev`` by the innermost host span open at the time."""
    lo, hi = window
    idle = gaps(device_segments(dev, window), lo, hi)
    segs = clip(trace.span_segments(anchor, spans), lo, hi)
    return {k: v / 1e9 for k, v in credit(idle, segs).items()}


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]
