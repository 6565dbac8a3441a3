"""Reduce a profiler trace (``.xplane.pb``) to device times.

Read with ``jax.profiler.ProfileData`` alone.  A TPU device is a plane
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation, named by the instruction's HLO text, with start
and duration in nanoseconds on the host's clock.  Control-flow
instructions (``while``, ``conditional``, ``call``) span the operations
of their bodies, which the line lists too; they are left out.  The
benchmark's host spans (``TraceAnnotation``) are events named ``bench.*``
on the host plane; ``bench.window`` spans the measured window.

Per device, inside the window:

* busy: the union of the operation intervals; idle is the rest;
* collective: operations whose opcode is a collective (all-gather,
  all-to-all, reduce-scatter, all-reduce, collective-permute, with their
  start/done halves); exposed collective time is the part of their union
  that no other operation overlaps;
* kernels: operations whose HLO text matches one of the given patterns
  (the ZeRO++ quantize kernels), their summed duration and their count.

Idle gaps are labelled by the host span that overlaps them most.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-to-all|reduce-scatter|all-reduce|collective-permute)")
CONTAINERS = ("while", "conditional", "call")
# the opcode: the first lower-case word followed by "(" after the result
# shape (a layout's "T(8,128)" follows ":", never a space)
OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
BREAKDOWN_TOP = 10

Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged: Sequence[Interval]) -> int:
    return sum(b - a for a, b in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
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


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


@dataclasses.dataclass
class Op:
    name: str           # short name (device ops) or span name (host)
    start: int
    end: int
    opcode: str = ""
    text: str = ""      # the full HLO text of a device op


def parse_hlo(text: str) -> Tuple[str, str]:
    """(short name, opcode) of an ``XLA Ops`` event's HLO text: the short
    name is the instruction name with its result shape, layouts dropped."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    m = OPCODE.search(rest)
    if not m:
        return name, ""
    shape = LAYOUT.sub("", rest[:m.start() + 1])
    return f"{name} = {shape}"[:160], m.group(1)


@dataclasses.dataclass
class Device:
    busy_ns: int
    collective_exposed_ns: int
    kernel_ns: int
    kernel_count: int
    gaps: List[Interval]


@dataclasses.dataclass
class Summary:
    window_ns: int
    devices: Dict[int, Device]
    op_ns: Dict[str, float]              # mean over devices, per op name
    gap_ns: Dict[str, float]             # mean idle over devices, per span
    kernel_names: Dict[str, int]         # kernel instruction -> count

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def mean(self, field: str) -> float:
        vals = [getattr(d, field) for d in self.devices.values()]
        return sum(vals) / len(vals)

    @property
    def busy_s(self) -> float:
        return self.mean("busy_ns") / 1e9

    def breakdown(self) -> Dict[str, list]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gap_ns.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v / 1e9] for k, v in ops[:BREAKDOWN_TOP]],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps[:BREAKDOWN_TOP]]}


def device_ops(profile) -> Dict[int, List[Op]]:
    out: Dict[int, List[Op]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                short, opcode = parse_hlo(ev.name)
                if opcode in CONTAINERS:
                    continue
                ops.append(Op(short, s, s + int(ev.duration_ns), opcode,
                              ev.name))
        out[int(m.group(1))] = ops
    return out


def host_spans(profile) -> List[Op]:
    out = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    out.append(Op(ev.name, s, s + int(ev.duration_ns)))
    return out


def _labels(gaps: Sequence[Interval], spans: List[Op]) -> List[str]:
    """For each gap (sorted), the host span that overlaps it most."""
    spans = sorted(spans, key=lambda sp: sp.start)
    ends, out, j = [], [], 0
    for sp in spans:                    # running max of ends, for the sweep
        ends.append(max(sp.end, ends[-1]) if ends else sp.end)
    for a, b in gaps:
        while j < len(spans) and ends[j] <= a:
            j += 1
        best, label, k = 0, "other", j
        while k < len(spans) and spans[k].start < b:
            ov = min(b, spans[k].end) - max(a, spans[k].start)
            if ov > best:
                best, label = ov, spans[k].name
            k += 1
        out.append(label)
    return out


def window(spans: List[Op], ops: Dict[int, List[Op]]) -> Interval:
    """The traced window: the ``bench.window`` span, widened to the first
    and the last device operation.  The trace starts once set-up has
    drained the device and stops after the window's last step is done, so
    every device operation in it belongs to the window's steps; but device
    timestamps can lead the host's by about half a millisecond (the
    recorded one-chip trace: a step's first operation starts 0.43 ms
    before its ``bench.dispatch`` span), which would cut the first step's
    first operations off a window taken from the host span alone."""
    first = min(o.start for v in ops.values() for o in v)
    last = max(o.end for v in ops.values() for o in v)
    spans = [s for s in spans if s.name == WINDOW_SPAN]
    if not spans:
        return first, last
    return min(first, spans[0].start), max(last, spans[0].end)


def summarize(path: str, kernels: Sequence[str],
              devices: Optional[Sequence[int]] = None) -> Summary:
    """Reduce the trace at ``path``.  ``kernels``: regular expressions
    naming the kernel operations; ``devices``: the device ids to keep (all
    when None)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    ops = device_ops(profile)
    if devices is not None:
        ops = {d: o for d, o in ops.items() if d in set(devices)}
    if not ops or not any(ops.values()):
        raise ValueError("no device operations in the trace")
    spans = host_spans(profile)
    lo, hi = window(spans, ops)
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    kpat = re.compile("|".join(f"(?:{k})" for k in kernels)) if kernels \
        else None
    per_dev, op_ns = {}, collections.Counter()
    gap_ns, knames = collections.Counter(), collections.Counter()
    for dev, dops in ops.items():
        dops = [o for o in dops if o.end > lo and o.start < hi]
        busy = union(clip(((o.start, o.end) for o in dops), lo, hi))
        coll = union(clip(((o.start, o.end) for o in dops
                           if COLLECTIVE.match(o.opcode)), lo, hi))
        other = union(clip(((o.start, o.end) for o in dops
                            if not COLLECTIVE.match(o.opcode)), lo, hi))
        kops = [o for o in dops if kpat and kpat.search(o.text)]
        for o in kops:
            knames[o.name.split(" = ")[0]] += 1
        for o in dops:
            op_ns[o.name] += (min(o.end, hi) - max(o.start, lo)) / len(ops)
        gaps = subtract([(lo, hi)], busy)
        for g, label in zip(gaps, _labels(gaps, inner)):
            gap_ns[label] += (g[1] - g[0]) / len(ops)
        per_dev[dev] = Device(
            busy_ns=length(busy),
            collective_exposed_ns=length(subtract(coll, other)),
            kernel_ns=sum(o.end - o.start for o in kops),
            kernel_count=len(kops), gaps=gaps)
    return Summary(window_ns=hi - lo, devices=per_dev, op_ns=dict(op_ns),
                   gap_ns=dict(gap_ns), kernel_names=dict(knames))
