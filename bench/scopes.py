#!/usr/bin/env python3
"""Device time by program scope, from the ``op_name`` of each operation in
a profiler trace (``.xplane.pb``).

    python bench/scopes.py <trace.xplane.pb> --steps <n>

XLA writes each HLO instruction's ``op_name``, the JAX name stack of the
code that made it (``jit(local_step)/transpose(jvp(step.layers))/while/
body/dot_general``), into the trace as the ``tf_op`` stat of the
instruction's event metadata.  ``jax.profiler.ProfileData`` does not give
metadata stats, so this module reads the XSpace proto itself, with the
``xplane_pb2`` module loaded from its installed file by path: importing
the ``tensorflow`` package that ships it costs seconds and memory.

Per device, inside the traced window of ``trace_reduce.window``, each
operation falls in the first class that applies:

  optimizer     its name stack holds an ``optim.`` scope
  backward      it holds ``transpose(``: the backward pass, the layers'
                recompute included
  forward       any other name stack
  unattributed  no ``tf_op``: an instruction that XLA made itself

An instruction XLA made inside a loop (the ``while`` of a scan, whose
event spans its body's operations) is that loop's work: it takes the
class that holds the most named time in the innermost loop around it
that holds any.  It stays unattributed where no loop around it holds a
named operation (XLA's own copy loops, top-level rewrites).  A class's
time is the union of its operations' intervals.  A collective
operation (``trace_reduce.COLLECTIVE``) also takes the innermost ``zero.*``
label of its name stack; a label's exposed time is the part of the union
of its collectives that no other (non-collective) operation overlaps.
Times are means over the devices, per step, in ms.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import importlib.util
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

CLASSES = ("forward", "backward", "optimizer", "unattributed")
OPTIM = re.compile(r"(?:^|[/(])optim\.")
LABEL = re.compile(r"zero\.\w+")
XPLANE_PB2 = os.path.join("tsl", "profiler", "protobuf", "xplane_pb2.py")
TOP = 10


def _xplane_pb2():
    """The generated proto module, loaded once by path from the
    ``tensorflow`` distribution without importing ``tensorflow``."""
    if "xplane_pb2" in sys.modules:
        return sys.modules["xplane_pb2"]
    spec = importlib.util.find_spec("tensorflow")
    roots = list(spec.submodule_search_locations or []) if spec else []
    for root in roots:
        path = os.path.join(root, XPLANE_PB2)
        if os.path.exists(path):
            mod_spec = importlib.util.spec_from_file_location("xplane_pb2",
                                                              path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            sys.modules["xplane_pb2"] = mod
            return mod
    raise ImportError(f"no {XPLANE_PB2} in an installed tensorflow")


def classify(tf_op: str) -> str:
    """The class of an operation whose name stack is ``tf_op``."""
    if not tf_op:
        return "unattributed"
    if OPTIM.search(tf_op):
        return "optimizer"
    if "transpose(" in tf_op:
        return "backward"
    return "forward"


def label(tf_op: str) -> Optional[str]:
    """The innermost ``zero.*`` label of a name stack, or None."""
    hits = LABEL.findall(tf_op)
    return hits[-1] if hits else None


@dataclasses.dataclass
class ScopedOp:
    op: tr.Op           # name, start, end, opcode, text (trace_reduce's)
    tf_op: str          # "" where the instruction carries no op_name


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[ScopedOp]]      # device id -> its operations
    spans: List[tr.Op]                  # the benchmark's host spans
    loops: Dict[int, List[tr.Interval]] = dataclasses.field(
        default_factory=dict)           # device id -> its loops' spans


def _stat_value(plane, stat):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    return getattr(stat, kind) if kind else None


@functools.lru_cache(maxsize=4)
def load(path: str) -> Trace:
    """The trace at ``path``, read once: each device operation with its
    name stack, and the host spans named ``bench.*``.  Start and duration
    are truncated to whole nanoseconds as ``ProfileData`` gives them."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops: Dict[int, List[ScopedOp]] = {}
    loops: Dict[int, List[tr.Interval]] = {}
    spans: List[tr.Op] = []
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        names = {sid: sm.name for sid, sm in plane.stat_metadata.items()}
        if not m:
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_metadata[ev.metadata_id].name
                    if name.startswith(tr.SPAN_PREFIX):
                        s = line.timestamp_ns + ev.offset_ps // 1000
                        spans.append(tr.Op(name, s,
                                           s + ev.duration_ps // 1000))
            continue
        meta = {}                       # metadata id -> (short, opcode, ..)
        for mid, em in plane.event_metadata.items():
            short, opcode = tr.parse_hlo(em.name)
            tf_op = next((str(_stat_value(plane, st)) for st in em.stats
                          if names.get(st.metadata_id) == "tf_op"), "")
            tf_op = tf_op[:-1] if tf_op.endswith(":") else tf_op
            meta[mid] = (short, opcode, em.name, tf_op)
        dev, dloops = [], []
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for ev in line.events:
                short, opcode, text, tf_op = meta[ev.metadata_id]
                s = line.timestamp_ns + ev.offset_ps // 1000
                e = s + ev.duration_ps // 1000
                if opcode in tr.CONTAINERS:
                    dloops.append((s, e))
                else:
                    dev.append(ScopedOp(tr.Op(short, s, e, opcode, text),
                                        tf_op))
        ops[int(m.group(1))] = dev
        loops[int(m.group(1))] = dloops
    return Trace(ops=ops, spans=spans, loops=loops)


def classes(ops: Sequence[ScopedOp], loops: Sequence[tr.Interval]
            ) -> List[str]:
    """The class of each of one device's operations: by its name stack,
    or, with none, by the named time inside the innermost loop around it
    that holds any.  Loops nest, so one sweep in start order, with the
    loops still open on a stack, finds the loops around each operation."""
    events = sorted([(a, -b, 0, j) for j, (a, b) in enumerate(loops)] +
                    [(o.op.start, -o.op.end, 1, i)
                     for i, o in enumerate(ops)])
    named = [collections.Counter() for _ in loops]
    stack: List[int] = []
    around: Dict[int, List[int]] = {}
    for start, neg_end, kind, i in events:
        while stack and loops[stack[-1]][1] <= start:
            stack.pop()
        if kind == 0:
            stack.append(i)
            continue
        inside = [j for j in stack if loops[j][1] >= -neg_end]
        if ops[i].tf_op:
            for j in inside:
                named[j][classify(ops[i].tf_op)] += -neg_end - start
        else:
            around[i] = inside
    out = []
    for i, o in enumerate(ops):
        cls = classify(o.tf_op)
        for j in reversed(around.get(i, [])):
            if named[j]:
                cls = named[j].most_common(1)[0][0]
                break
        out.append(cls)
    return out


@dataclasses.dataclass
class Split:
    """Nanoseconds inside the window, means over the devices."""
    window_ns: int
    busy_ns: float
    class_ns: Dict[str, float]          # class -> union of its operations
    collective_ns: Dict[str, float]     # zero.* label -> union of its
    #                                     collectives
    exposed_ns: Dict[str, float]        # ... the part nothing else overlaps
    scope_ns: Dict[str, float]          # tf_op (or "" ) -> summed time
    unattributed_ns: Dict[str, float]   # short op name -> summed time


@functools.lru_cache(maxsize=8)
def split(path: str, devices: Optional[Sequence[int]] = None) -> Split:
    """Reduce the trace at ``path`` by class and by collective label;
    ``devices``: the device ids to keep (all when None), as a tuple."""
    return split_trace(load(path), devices)


def split_trace(trace: Trace, devices: Optional[Sequence[int]] = None
                ) -> Split:
    """``split`` of a trace already read."""
    ops = {d: o for d, o in trace.ops.items()
           if devices is None or d in set(devices)}
    if not ops or not any(ops.values()):
        raise ValueError("no device operations in the trace")
    lo, hi = tr.window(trace.spans, {d: [s.op for s in v]
                                     for d, v in ops.items()})
    n = len(ops)
    busy = 0
    class_ns = collections.Counter()
    coll_ns, exp_ns = collections.Counter(), collections.Counter()
    scope_ns, un_ns = collections.Counter(), collections.Counter()
    for dev, dops in ops.items():
        cls_of = classes(dops, trace.loops.get(dev, []))
        dops = [(s, c) for s, c in zip(dops, cls_of)
                if s.op.end > lo and s.op.start < hi]
        busy += tr.length(tr.union(tr.clip(
            ((s.op.start, s.op.end) for s, _ in dops), lo, hi)))
        by_class = collections.defaultdict(list)
        by_label = collections.defaultdict(list)
        compute = []
        for s, cls in dops:
            iv = (max(s.op.start, lo), min(s.op.end, hi))
            by_class[cls].append(iv)
            scope_ns[s.tf_op] += (iv[1] - iv[0]) / n
            if cls == "unattributed":
                un_ns[s.op.name] += (iv[1] - iv[0]) / n
            if tr.COLLECTIVE.match(s.op.opcode):
                lbl = label(s.tf_op)
                if lbl:
                    by_label[lbl].append(iv)
            else:
                compute.append(iv)
        for cls, ivs in by_class.items():
            class_ns[cls] += tr.length(tr.union(ivs)) / n
        compute = tr.union(compute)
        for lbl, ivs in by_label.items():
            u = tr.union(ivs)
            coll_ns[lbl] += tr.length(u) / n
            exp_ns[lbl] += tr.length(tr.subtract(u, compute)) / n
    return Split(window_ns=hi - lo, busy_ns=busy / n,
                 class_ns={c: class_ns.get(c, 0.0) for c in CLASSES},
                 collective_ns=dict(coll_ns), exposed_ns=dict(exp_ns),
                 scope_ns=dict(scope_ns), unattributed_ns=dict(un_ns))


def class_ms(sp: Split, cls: str, steps: int) -> Optional[float]:
    """A class's device time per step, ms; None where no operation of the
    class ran (a program without the class's scopes)."""
    ns = sp.class_ns[cls]
    return ns / 1e6 / steps if ns else None


def exposed_ms(sp: Split, lbl: str, steps: int) -> Optional[float]:
    """A label's exposed collective time per step, ms: 0 where its
    collectives ran and were all overlapped, None where none ran."""
    if not sp.collective_ns.get(lbl):
        return None
    return sp.exposed_ns.get(lbl, 0.0) / 1e6 / steps


def report(sp: Split, steps: int, top: int = TOP) -> List[str]:
    """Lines for a log: the classes, the labels, the largest scopes and
    the largest unattributed operations, per step."""
    per = lambda ns: f"{ns / 1e6 / steps:9.3f} ms"  # noqa: E731
    out = [f"busy {per(sp.busy_ns)} a step over {steps} steps"]
    out += [f"  {c:<13}{per(sp.class_ns[c])}  "
            f"{100 * sp.class_ns[c] / sp.busy_ns:5.2f} %" for c in CLASSES]
    for lbl in sorted(sp.collective_ns):
        out.append(f"  {lbl:<20} collective {per(sp.collective_ns[lbl])}, "
                   f"exposed {per(sp.exposed_ns.get(lbl, 0.0))}")
    out.append(f"largest {top} scopes:")
    for k, v in sorted(sp.scope_ns.items(), key=lambda kv: -kv[1])[:top]:
        out.append(f"  {per(v)}  {k or '(no op_name)'}")
    out.append(f"largest {top} unattributed operations:")
    for k, v in sorted(sp.unattributed_ns.items(),
                       key=lambda kv: -kv[1])[:top]:
        out.append(f"  {per(v)}  {k}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--steps", type=int, required=True)
    args = ap.parse_args(argv)
    sp = split(args.trace)
    print("\n".join(report(sp, args.steps)), file=sys.stderr)
    print(json.dumps({
        "steps": args.steps, "window_ms": sp.window_ns / 1e6,
        "busy_ms_per_step": sp.busy_ns / 1e6 / args.steps,
        **{f"{c}_ms_per_step": class_ms(sp, c, args.steps)
           for c in CLASSES},
        **{f"{lbl[5:]}_exposed_ms_per_step": exposed_ms(sp, lbl, args.steps)
           for lbl in sorted(sp.collective_ns)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
