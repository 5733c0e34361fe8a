"""From a profiler trace to plain lists, once, and the reductions the
readers share. `load` needs JAX; everything else works on lists of
`Event`, so it can be tested on hand-made ones.

Times are seconds. A device plane is one chip (`/device:TPU:0`); its
`XLA Ops` line holds one event per executed operation, nested where an
operation (a loop, a call) runs others inside it. Host planes hold the
`TraceAnnotation` spans of the program (`serving.*`) and of the harness
(`chipbench.*`) on the same clock.
"""
from __future__ import annotations

import collections
import re

Event = collections.namedtuple("Event", "plane line name start duration")

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:"
SPAN_PREFIXES = ("serving.", "chipbench.")


def load(path: str) -> list:
    """Every event of an `.xplane.pb` as (plane, line, name, start,
    duration), in seconds on the trace's own clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def device_ops(events, plane_prefix: str = DEVICE_PLANE,
               line: str = OPS_LINE) -> dict:
    """Device plane name -> its operations, sorted by start."""
    planes = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(plane_prefix) and e.line == line:
            planes[e.plane].append(e)
    return {p: sorted(v, key=lambda e: (e.start, -e.duration))
            for p, v in planes.items()}


def busy_intervals(ops) -> list:
    """The union of the operations' intervals as disjoint (start, end)
    pairs in order."""
    out = []
    for e in sorted(ops, key=lambda e: e.start):
        end = e.start + e.duration
        if out and e.start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([e.start, end])
    return [(a, b) for a, b in out]


def busy_and_window(events) -> tuple:
    """(busy seconds, window seconds), each averaged over the chips
    that ran anything. The window of a chip runs from its first
    operation's start to its last one's end."""
    busy, window = [], []
    for ops in device_ops(events).values():
        spans = busy_intervals(ops)
        if spans:
            busy.append(sum(b - a for a, b in spans))
            window.append(spans[-1][1] - spans[0][0])
    if not busy:
        return 0.0, 0.0
    return sum(busy) / len(busy), sum(window) / len(window)


def self_times(ops) -> dict:
    """Name -> seconds an operation ran itself, without the operations
    nested inside it (events of one line nest, they do not cross)."""
    total = collections.defaultdict(float)
    stack = []                       # [name, end, self seconds]
    for e in sorted(ops, key=lambda e: (e.start, -e.duration)):
        while stack and e.start >= stack[-1][1] - 1e-12:
            name, _end, own = stack.pop()
            total[name] += own
        if stack:
            stack[-1][2] -= e.duration
        stack.append([e.name, e.start + e.duration, e.duration])
    for name, _end, own in stack:
        total[name] += own
    return dict(total)


def own_name(name: str) -> str:
    """The instruction's own name: the trace names an operation by its
    whole HLO line, operands and all, and an operand's name must not
    count as a match."""
    return name.split(" = ", 1)[0]


def named_time(ops, needles) -> tuple:
    """(seconds, events) of the operations whose own name holds any of
    `needles`, outermost match only."""
    seconds, count, until = 0.0, 0, -1.0
    for e in sorted(ops, key=lambda e: (e.start, -e.duration)):
        if e.start < until:
            continue
        if any(n in own_name(e.name) for n in needles):
            seconds += e.duration
            count += 1
            until = e.start + e.duration
    return seconds, count


def idle_gaps(ops) -> list:
    spans = busy_intervals(ops)
    return [(a[1], b[0]) for a, b in zip(spans, spans[1:]) if b[0] > a[1]]


def host_spans(events, prefixes=SPAN_PREFIXES) -> list:
    return [e for e in events if e.plane.startswith(HOST_PLANE)
            and e.name.startswith(tuple(prefixes))]


def attribute_gaps(gaps, spans) -> dict:
    """Idle seconds by what the host was doing: each gap goes to the
    shortest span that covers its middle, or to `(no span)`."""
    out = collections.defaultdict(float)
    spans = sorted(spans, key=lambda s: s.duration)
    for a, b in gaps:
        mid = (a + b) / 2
        name = next((s.name for s in spans
                     if s.start <= mid <= s.start + s.duration),
                    "(no span)")
        out[name] += b - a
    return dict(out)


_NUMBER = re.compile(r"\.\d+\Z")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r" [a-z][a-z\-]*\(")


def short_name(name: str, width: int = 120) -> str:
    """The trace names a device operation by its whole HLO line. Keep
    the instruction's name without its number and the shapes it
    produces: `fusion (f32[18000], bf16[2048,18000])` says what a bare
    `fusion.12` cannot, and one layer's kernel falls together with the
    next layer's."""
    if not name.startswith("%") or " = " not in name:
        return name[:width]
    op, rest = name[1:].split(" = ", 1)
    op = _NUMBER.sub("", op)
    cut = _OPCODE.search(rest)
    shapes = _LAYOUT.sub("", rest[:cut.start()] if cut else rest)
    return f"{op} {shapes}"[:width]


def breakdown(events, top: int = 10) -> dict:
    """The contract's optional `breakdown`: the device operations that
    took most time (self time, summed over chips) and the idle time by
    host span, ten of each at most."""
    ops_time = collections.defaultdict(float)
    gap_time = collections.defaultdict(float)
    spans = host_spans(events)
    for ops in device_ops(events).values():
        for name, s in self_times(ops).items():
            ops_time[short_name(name)] += s
        for name, s in attribute_gaps(idle_gaps(ops), spans).items():
            gap_time[name] += s

    def first(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {"device_ops": first(ops_time), "idle_gaps": first(gap_time)}
