"""Loop-aware analysis of compiled (post-optimization) HLO text.

``compiled.cost_analysis()`` visits every instruction ONCE — it does not
multiply while-loop bodies by their trip counts, which makes it useless for
layer-scanned models (the entire per-layer compute/communication lives in a
while body).  This module re-derives the three roofline inputs by walking
the HLO computation graph with trip-count multiplication:

  * flops       — 2·|out|·K for every dot, recursing through while/call/
                  fusion/conditional, × trip count inside loops
  * hbm_bytes   — Σ (operand + output bytes) per *materialized* instruction
                  (fusion = one kernel: its operands/outputs are the HBM
                  traffic; internals are free), × trip count
  * collectives — per-op operand/wire bytes with ring-algorithm volume
                  formulas, split by interconnect tier (model / data / pod),
                  × trip count

Trip counts are parsed from each loop condition's integer constants — our
loops all come from lax.scan, whose conditions compare the induction
variable against a literal.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^{]*)?\{\s*$")


def _split_instr(line: str):
    """(name, type_str, opcode) or None.  Handles tuple types that contain
    parens and /*index=N*/ comments (while/conditional results)."""
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    name = m.group(1)
    rest = line[m.end():]
    if rest.startswith("("):          # tuple type: consume balanced parens
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str = rest[: i + 1]
        tail = rest[i + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str = rest[:sp]
        tail = rest[sp + 1:].lstrip()
    p = tail.find("(")
    if p <= 0:
        return None
    opcode = tail[:p].strip()
    if not re.fullmatch(r"[\w\-]+", opcode):
        return None
    return name, type_str, opcode
_GROUPS_V1_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_FREE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
             "after-all", "iota", "partition-id", "replica-id"}


def _first_type_dims(tstr: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(tstr):
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((m.group(1), dims))
    return out


def _type_bytes(tstr: str) -> int:
    total = 0
    for dt, dims in _first_type_dims(tstr):
        if dt in _DTYPE_BYTES:
            total += int(np.prod(dims)) * _DTYPE_BYTES[dt] if dims \
                else _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    operands: List[str]
    line: str


def _parse_operands(line: str, opcode: str) -> List[str]:
    i = line.find(opcode + "(")
    if i < 0:
        return []
    call = line[i + len(opcode) + 1:]
    depth, args = 1, []
    buf = ""
    for ch in call:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                args.append(buf)
                break
        if depth >= 1:
            buf += ch
    return re.findall(r"%([\w.\-]+)", "".join(args))


def parse_module(text: str) -> Dict[str, List[Instr]]:
    """Split HLO text into computations (name -> instruction list)."""
    comps: Dict[str, List[Instr]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m and ("(" in line or line.startswith("ENTRY")):
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        got = _split_instr(line)
        if got:
            name, tstr, opcode = got
            comps[cur].append(Instr(name, tstr, opcode,
                                    _parse_operands(line, opcode), line))
    return comps


def _attr_comp(line: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", line)
    return m.group(1) if m else None


def _trip_count(comps, cond_name: str) -> int:
    """Max integer constant in the loop condition = scan trip count."""
    best = 1
    for ins in comps.get(cond_name, []):
        if ins.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", ins.line)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _parse_groups(line: str) -> Optional[np.ndarray]:
    m = _GROUPS_V1_RE.search(line)
    if m:
        groups = [[int(x) for x in g.split(",") if x]
                  for g in re.findall(r"\{([^}]*)\}", m.group(1))]
        width = max(len(g) for g in groups)
        return np.array([g + [g[-1]] * (width - len(g)) for g in groups])
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.transpose(perm)
        return ids.reshape(ng, gs)
    return None


def _group_tier(groups: Optional[np.ndarray], multi_pod: bool) -> str:
    if groups is None:
        return "data"
    g = groups
    if multi_pod and np.ptp(g // 256, axis=1).max() > 0:
        return "pod"
    if np.ptp((g % 256) // 16, axis=1).max() > 0:
        return "data"
    return "model"


def _wire_bytes(op: str, in_bytes: int, out_bytes: int, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-gather":
        return max(out_bytes - in_bytes, 0)
    if op == "reduce-scatter":
        return max(in_bytes - out_bytes, 0)
    if op == "all-reduce":
        return 2.0 * in_bytes * (n - 1) / n
    if op == "all-to-all":
        return in_bytes * (n - 1) / n
    if op == "collective-permute":
        return float(in_bytes)
    return float(in_bytes)


def _dot_flops(ins: Instr, shapes: Dict[str, str]) -> float:
    out_elems = 1
    for dt, dims in _first_type_dims(ins.type_str):
        out_elems = int(np.prod(dims)) if dims else 1
        break
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    if not m or not ins.operands:
        return 2.0 * out_elems  # dot with no contraction info
    cdims = [int(x) for x in m.group(1).split(",") if x]
    lhs_t = shapes.get(ins.operands[0])
    if lhs_t is None:
        return 2.0 * out_elems
    for dt, dims in _first_type_dims(lhs_t):
        k = 1
        for c in cdims:
            if c < len(dims):
                k *= dims[c]
        return 2.0 * out_elems * k
    return 2.0 * out_elems


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    transcendentals: float = 0.0
    coll_per_op: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    coll_per_tier: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"model": 0.0, "data": 0.0, "pod": 0.0})
    coll_count: int = 0

    def add_collective(self, base: str, in_b: float, wire: float, tier: str,
                       mult: float):
        d = self.coll_per_op.setdefault(
            base, {"count": 0, "operand_bytes": 0.0, "wire_bytes": 0.0})
        d["count"] += mult
        d["operand_bytes"] += in_b * mult
        d["wire_bytes"] += wire * mult
        self.coll_per_tier[tier] += wire * mult
        self.coll_count += int(mult)


def _walk(comps, name: str, mult: float, t: Totals, multi_pod: bool,
          world: int, memo: Dict[str, "Totals"], depth: int = 0):
    """Accumulate totals for one computation, scaled by ``mult``."""
    if depth > 50:
        return
    shapes = {i.name: i.type_str for i in comps.get(name, [])}
    for ins in comps.get(name, []):
        op = ins.opcode
        if op == "while":
            body = _attr_comp(ins.line, "body")
            cond = _attr_comp(ins.line, "condition")
            trips = _trip_count(comps, cond) if cond else 1
            if body:
                _walk(comps, body, mult * trips, t, multi_pod, world, memo,
                      depth + 1)
            continue
        if op in ("call", "async-start"):
            tgt = _attr_comp(ins.line, "to_apply") \
                or _attr_comp(ins.line, "calls")
            if tgt:
                _walk(comps, tgt, mult, t, multi_pod, world, memo, depth + 1)
            continue
        if op == "conditional":
            for tgt in re.findall(r"%([\w.\-]+)",
                                  ins.line.split("branch_computations")[-1]
                                  if "branch_computations" in ins.line
                                  else ""):
                _walk(comps, tgt, mult, t, multi_pod, world, memo, depth + 1)
            continue

        base = op.replace("-start", "")
        if base in _COLL_OPS and not op.endswith("-done"):
            in_b = sum(_type_bytes(shapes.get(o, "")) for o in ins.operands)
            out_b = _type_bytes(ins.type_str)
            groups = _parse_groups(ins.line)
            n = groups.shape[1] if groups is not None else world
            tier = _group_tier(groups, multi_pod)
            wire = _wire_bytes(base, in_b, out_b, n)
            t.add_collective(base, in_b, wire, tier, mult)
            t.hbm_bytes += (in_b + out_b) * mult
            continue

        if op == "fusion":
            tgt = _attr_comp(ins.line, "calls")
            if tgt:
                # dots may hide inside fusions: count their flops, but the
                # fusion's HBM traffic is its own output (operands were
                # counted when produced)
                sub = memo.get(tgt)
                if sub is None:
                    sub = Totals()
                    _walk(comps, tgt, 1.0, sub, multi_pod, world, {},
                          depth + 1)
                    sub.hbm_bytes = 0.0
                    memo[tgt] = sub
                t.flops += sub.flops * mult
                t.transcendentals += sub.transcendentals * mult
            t.hbm_bytes += _type_bytes(ins.type_str) * mult
            continue

        if op in ("dot", "convolution"):
            t.flops += _dot_flops(ins, shapes) * mult
            # dots re-read both operands from HBM (weights/activations) and
            # write the product: count operands + output
            t.hbm_bytes += (sum(_type_bytes(shapes.get(o, ""))
                                for o in ins.operands)
                            + _type_bytes(ins.type_str)) * mult
            continue
        if op in ("exponential", "tanh", "log", "rsqrt", "sqrt", "power",
                  "sine", "cosine", "logistic"):
            t.transcendentals += _type_bytes(ins.type_str) * mult

        if op not in _FREE_OPS:
            # non-dot materializations: count the write once; reads were
            # someone else's write (fusion-blind traffic lower bound — see
            # DESIGN.md §Roofline caveats)
            t.hbm_bytes += _type_bytes(ins.type_str) * mult


def analyze_hlo(text: str, world: int, multi_pod: bool) -> Dict:
    """Loop-aware flops / bytes / collective totals for the entry module."""
    comps = parse_module(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                entry = m.group(1)
                break
    if entry is None:  # fall back: biggest computation
        entry = max(comps, key=lambda k: len(comps[k]))
    t = Totals()
    _walk(comps, entry, 1.0, t, multi_pod, world, {})
    return {
        "flops": t.flops,
        "hbm_bytes": t.hbm_bytes,
        "transcendental_bytes": t.transcendentals,
        "collectives": {
            "per_op": t.coll_per_op,
            "per_tier_wire": t.coll_per_tier,
            "count": t.coll_count,
            "operand_bytes": sum(d["operand_bytes"]
                                 for d in t.coll_per_op.values()),
            "wire_bytes": sum(d["wire_bytes"]
                              for d in t.coll_per_op.values()),
        },
    }


# ---------------------------------------------------------------------------
# liveness-aware peak memory estimate
# ---------------------------------------------------------------------------

_ALIAS_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
              "after-all", "constant", "iota", "partition-id", "replica-id"}


def _comp_peak(comps, name: str, memo: Dict[str, float]) -> float:
    """Peak live bytes of one computation under its textual (program-order)
    schedule — a valid sequential schedule, hence an ACHIEVABLE peak.

    The CPU backend's actual buffer assignment schedules for thread
    concurrency and can hold many more buffers live simultaneously; a TPU
    compiler schedules much closer to program order.  Aliasing ops are free;
    while loops contribute state + max(body, cond) peak; fusions contribute
    their output only (internals live in registers/VMEM).
    """
    if name in memo:
        return memo[name]
    memo[name] = 0.0  # cycle guard
    instrs = comps.get(name, [])
    sizes: Dict[str, float] = {}
    alias_of: Dict[str, str] = {}

    def root(n):  # follow alias chains to the owning buffer
        seen = set()
        while n in alias_of and n not in seen:
            seen.add(n)
            n = alias_of[n]
        return n

    # last textual use index per buffer root
    last_use: Dict[str, int] = {}
    for i, ins in enumerate(instrs):
        for o in ins.operands:
            last_use[o] = i
    live: Dict[str, float] = {}
    # parameters live from entry
    for ins in instrs:
        if ins.opcode == "parameter":
            sizes[ins.name] = _type_bytes(ins.type_str)
            live[ins.name] = sizes[ins.name]
    peak = sum(live.values())

    for i, ins in enumerate(instrs):
        extra = 0.0
        if ins.opcode in _ALIAS_OPS:
            if ins.opcode in ("get-tuple-element", "bitcast") and ins.operands:
                alias_of[ins.name] = ins.operands[0]
            sizes.setdefault(ins.name, 0.0)
        else:
            out_b = float(_type_bytes(ins.type_str))
            sizes[ins.name] = out_b
            live[ins.name] = out_b
            if ins.opcode == "while":
                body = _attr_comp(ins.line, "body")
                cond = _attr_comp(ins.line, "condition")
                extra = max(_comp_peak(comps, body, memo) if body else 0.0,
                            _comp_peak(comps, cond, memo) if cond else 0.0)
            elif ins.opcode in ("call", "conditional"):
                tgt = _attr_comp(ins.line, "to_apply")
                if tgt:
                    extra = _comp_peak(comps, tgt, memo)
        peak = max(peak, sum(live.values()) + extra)
        # free buffers whose last use has passed
        for o in list(live):
            if last_use.get(o, -1) <= i and o != ins.name:
                # keep if some alias of it is used later
                still = any(last_use.get(a, -1) > i
                            for a, r in alias_of.items() if root(r) == o)
                if not still:
                    del live[o]
    memo[name] = peak
    return peak


def estimate_peak_bytes(text: str) -> float:
    """Liveness-based peak for the entry computation (program order)."""
    comps = parse_module(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                entry = m.group(1)
                break
    if entry is None:
        entry = max(comps, key=lambda k: len(comps[k]))
    return _comp_peak(comps, entry, {})


# ---------------------------------------------------------------------------
# communication/compute overlap detection (prefetched schedule verification)
# ---------------------------------------------------------------------------
#
# Two complementary detections, because backends differ in what the compiled
# HLO shows:
#
#  * async pairs — GPU/TPU latency-hiding schedulers rewrite collectives
#    into ``all-gather-start``/``all-gather-done`` (or ``async-start`` /
#    ``async-done``) pairs and hoist the start above independent compute.
#    A pair with a dot scheduled between start and done IS overlap,
#    directly observable.
#  * dependence analysis — the CPU backend (and any backend before the LHS
#    pass) keeps collectives synchronous in the HLO text, so overlap has to
#    be read off the *structure*: inside a while (scan) body, a collective
#    that neither consumes this iteration's matmul results nor feeds them
#    is schedulable concurrently with the body's compute.  That is exactly
#    what the prefetched schedule produces (gathers feed only the loop
#    carry; the pipelined reduce-scatter consumes only the carry), and what
#    the synchronous schedule cannot (its gathers feed the dots directly).
#
# ``overlap_fraction`` is the wire-byte-weighted share of in-loop
# collectives that are overlappable; async pairs, when present, are
# reported alongside.  Nested loops (the MoE expert-chunk scan inside the
# layer scan) are weighted by their enclosing trip-count product, and a
# loop body with no compute at all (gather-only remat loops) exposes its
# collectives — see _body_overlap / _loop_multipliers.
#
# Ring-depth accounting: the depth-k prefetch ring (core/schedule.py)
# inserts each gather's result into a (k, ...) carried ring buffer, so the
# value is not consumed for k iterations — the gather is credited against
# k iterations of compute, not one.  ``slack_iters`` is read off the HLO
# structurally (the leading dim of the ring buffer the collective's result
# is dynamic-update-sliced into; 1 when no ring is found), and
# :func:`effective_overlap` turns (slack, per-iteration flops, wire bytes)
# into a wall-clock-model overlap fraction at an explicit operating point
# (peak flops, per-tier bandwidth, per-collective latency): structure says
# which bytes CAN move under compute, the operating point says which bytes
# FIT.  Depth k>1 strictly increases the fit when one iteration's compute
# cannot cover a gather — the low-bandwidth regime the ring exists for.


def _fusion_has_dot(comps, name: str, memo: Dict[str, bool],
                    visiting: Optional[set] = None) -> bool:
    if name in memo:
        return memo[name]
    visiting = set() if visiting is None else visiting
    if name in visiting:   # cycle (malformed HLO): unresolved, don't cache
        return False
    visiting.add(name)
    res = False
    for ins in comps.get(name, []):
        if ins.opcode in ("dot", "convolution"):
            res = True
            break
        if ins.opcode == "fusion":
            tgt = _attr_comp(ins.line, "calls")
            if tgt and _fusion_has_dot(comps, tgt, memo, visiting):
                res = True
                break
    visiting.discard(name)
    memo[name] = res
    return res


def _is_compute(comps, ins: Instr, memo: Dict[str, bool]) -> bool:
    """Does this instruction perform matmul work (directly or via fusion)?"""
    if ins.opcode in ("dot", "convolution"):
        return True
    if ins.opcode == "fusion":
        tgt = _attr_comp(ins.line, "calls")
        return bool(tgt) and _fusion_has_dot(comps, tgt, memo)
    return False


def _ring_slack(by_name: Dict[str, "Instr"], users: Dict[str, List[str]],
                ins: "Instr") -> int:
    """Iterations of compute a collective's result can hide under.

    The depth-k ring schedule dynamic-update-slices each prefetched buffer
    into a (k, ...) ring carried by the loop, so the value is first READ k
    iterations after the gather was issued.  Walk the collective's user
    chain (through dequantize fusions / converts / reshapes) until an op
    inserts it into a buffer whose rows past the leading dim hold as many
    elements as the value — ``(k, N)`` or the lane-dense ``(k, N/128,
    128)`` — and that leading dim is the ring depth.  No ring found (the
    value is consumed directly, e.g. the classic double buffer's bare
    carry or a synchronous gather) = 1.
    """
    shapes = _first_type_dims(ins.type_str)
    if not shapes:
        return 1
    size = int(np.prod(shapes[0][1]))
    seen, stack = set(), [ins.name]
    while stack:
        cur = stack.pop()
        for u in users.get(cur, []):
            if u in seen:
                continue
            seen.add(u)
            ui = by_name.get(u)
            if ui is None:
                continue
            udims_list = _first_type_dims(ui.type_str)
            if not udims_list:
                continue
            udims = udims_list[0][1]
            if (ui.opcode in ("dynamic-update-slice", "fusion")
                    and len(udims) >= 2 and udims[0] >= 1
                    and int(np.prod(udims[1:])) == size):
                return udims[0]
            stack.append(u)
    return 1


def _body_flops(comps, body: str, memo: Dict[str, float]) -> float:
    """Per-iteration matmul flops of one while body (nested loops counted
    at their trip counts — one outer iteration runs them in full)."""
    if body not in memo:
        t = Totals()
        _walk(comps, body, 1.0, t, False, 1, {})
        memo[body] = t.flops
    return memo[body]


def _body_overlap(comps, body: str, fus_memo: Dict[str, bool],
                  multi_pod: bool = False) -> List[Dict]:
    """Classify each collective in one while body as overlappable or
    exposed, by within-iteration dependence on matmul compute.

    A body with NO matmul compute at all (e.g. the gather-only loop XLA
    leaves behind when a nested remat's recomputed GEMMs are dead-code
    eliminated — the MoE expert-chunk re-gather) exposes every collective:
    independence means nothing when the iteration has nothing to hide
    behind."""
    instrs = comps.get(body, [])
    by_name = {i.name: i for i in instrs}
    users: Dict[str, List[str]] = {}
    for ins in instrs:
        for o in ins.operands:
            users.setdefault(o, []).append(ins.name)

    def reaches_compute_down(name: str) -> bool:
        seen, stack = set(), [name]
        while stack:
            cur = stack.pop()
            for u in users.get(cur, []):
                if u in seen:
                    continue
                seen.add(u)
                ins = by_name.get(u)
                if ins is None:
                    continue
                if _is_compute(comps, ins, fus_memo):
                    return True
                stack.append(u)
        return False

    def derives_from_compute_up(name: str) -> bool:
        seen, stack = set(), [name]
        while stack:
            cur = stack.pop()
            ins = by_name.get(cur)
            if ins is None:
                continue
            for o in ins.operands:
                if o in seen:
                    continue
                seen.add(o)
                oi = by_name.get(o)
                if oi is None:
                    continue
                if _is_compute(comps, oi, fus_memo):
                    return True
                stack.append(o)
        return False

    has_compute = any(_is_compute(comps, i, fus_memo) for i in instrs)
    out = []
    shapes = {i.name: i.type_str for i in instrs}
    for ins in instrs:
        base = ins.opcode.replace("-start", "")
        if base not in _COLL_OPS or ins.opcode.endswith("-done"):
            continue
        in_b = sum(_type_bytes(shapes.get(o, "")) for o in ins.operands)
        out_b = _type_bytes(ins.type_str)
        groups = _parse_groups(ins.line)
        n = groups.shape[1] if groups is not None else 0
        wire = _wire_bytes(base, in_b, out_b, n) if n else float(in_b)
        overlappable = (has_compute
                        and not reaches_compute_down(ins.name)
                        and not derives_from_compute_up(ins.name))
        out.append({"op": base, "name": ins.name, "wire_bytes": wire,
                    "overlappable": overlappable,
                    "tier": _group_tier(groups, multi_pod),
                    "slack_iters": _ring_slack(by_name, users, ins)
                    if overlappable else 1})
    return out


def _async_pairs(comps, fus_memo: Dict[str, bool]) -> Tuple[int, int]:
    """(n_async_collective_pairs, n_pairs_enclosing_compute) across all
    computations — textual program order between start and done."""
    pairs = enclosing = 0
    for name, instrs in comps.items():
        pos = {i.name: k for k, i in enumerate(instrs)}
        for ins in instrs:
            op = ins.opcode
            base = op.replace("-start", "")
            is_coll_start = op.endswith("-start") and base in _COLL_OPS
            if not (is_coll_start or op == "async-start"):
                continue
            # find the matching done: the (unique) *-done/async-done user
            done_idx = None
            for other in instrs:
                if ins.name in other.operands and (
                        other.opcode.endswith("-done")):
                    done_idx = pos[other.name]
                    break
            if done_idx is None:
                continue
            pairs += 1
            lo = pos[ins.name]
            if any(_is_compute(comps, instrs[k], fus_memo)
                   for k in range(lo + 1, done_idx)):
                enclosing += 1
    return pairs, enclosing


def _loop_multipliers(comps, entry: str) -> Dict[str, float]:
    """body name -> product of ENCLOSING loops' trip counts, walking from
    ``entry`` through while/call/conditional edges.

    A while nested inside another while's body (the MoE expert-chunk scan
    inside the layer scan) runs its trips once per outer iteration; its
    wire bytes must be weighted by the outer trip product or the nested
    (overlappable) chunk gathers are undercounted relative to the
    top-level loops.  Fusions are not traversed (XLA fusions cannot
    contain loops)."""
    mults: Dict[str, float] = {}
    seen = set()

    def walk(name: str, mult: float, depth: int = 0):
        if depth > 50 or (name, mult) in seen:
            return
        seen.add((name, mult))
        for ins in comps.get(name, []):
            if ins.opcode == "while":
                body = _attr_comp(ins.line, "body")
                cond = _attr_comp(ins.line, "condition")
                trips = _trip_count(comps, cond) if cond else 1
                if body:
                    mults[body] = max(mults.get(body, 0.0), mult)
                    walk(body, mult * trips, depth + 1)
            elif ins.opcode in ("call", "async-start", "conditional"):
                for key in ("to_apply", "calls"):
                    tgt = _attr_comp(ins.line, key)
                    if tgt and tgt in comps:
                        walk(tgt, mult, depth + 1)
                if ins.opcode == "conditional" and \
                        "branch_computations" in ins.line:
                    for tgt in re.findall(
                            r"%([\w.\-]+)",
                            ins.line.split("branch_computations")[-1]):
                        if tgt in comps:
                            walk(tgt, mult, depth + 1)

    walk(entry, 1.0)
    return mults


def _entry_name(text: str, comps) -> Optional[str]:
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_RE.match(line)
            if m:
                return m.group(1)
    return max(comps, key=lambda k: len(comps[k])) if comps else None


def analyze_overlap(text: str, multi_pod: bool = False) -> Dict:
    """Overlap metrics for a compiled HLO module (see block comment above).
    ``multi_pod`` feeds the tier classifier so cross-pod collectives are
    priced at the pod tier by :func:`effective_overlap`.

    Returns:
      in_loop_wire_bytes      — Σ wire bytes of collectives in while bodies
                                (× trip count × enclosing-loop trips)
      overlapped_wire_bytes   — the overlappable subset
      overlap_fraction        — overlapped / in_loop (0.0 when no in-loop
                                collectives)
      per_loop                — per while-body breakdown (``outer_mult`` is
                                the enclosing-loop trip product — nested
                                MoE chunk scans run once per outer layer)
      async_pairs / async_pairs_enclosing_compute — LHS-scheduler evidence,
                                when the backend emits async collectives
    """
    comps = parse_module(text)
    fus_memo: Dict[str, bool] = {}
    flop_memo: Dict[str, float] = {}
    entry = _entry_name(text, comps)
    mults = _loop_multipliers(comps, entry) if entry else {}
    per_loop = {}
    total = overlapped = 0.0
    n_coll = n_over = 0
    for name, instrs in comps.items():
        for ins in instrs:
            if ins.opcode != "while":
                continue
            body = _attr_comp(ins.line, "body")
            cond = _attr_comp(ins.line, "condition")
            if not body or body in per_loop:
                continue
            trips = _trip_count(comps, cond) if cond else 1
            colls = _body_overlap(comps, body, fus_memo, multi_pod)
            if not colls:
                continue
            mult = mults.get(body, 1.0)
            wire = sum(c["wire_bytes"] for c in colls) * trips * mult
            over = sum(c["wire_bytes"] for c in colls
                       if c["overlappable"]) * trips * mult
            per_loop[body] = {
                "trip_count": trips,
                "outer_mult": mult,
                "collectives": len(colls),
                "overlappable": sum(c["overlappable"] for c in colls),
                "wire_bytes": wire,
                "overlapped_wire_bytes": over,
                "has_compute": bool(_body_flops(comps, body, flop_memo)),
                "flops_per_iter": _body_flops(comps, body, flop_memo),
                "max_slack_iters": max(c["slack_iters"] for c in colls),
                "colls": [{k: c[k] for k in ("op", "wire_bytes",
                                             "overlappable", "tier",
                                             "slack_iters")}
                          for c in colls],
            }
            total += wire
            overlapped += over
            n_coll += len(colls)
            n_over += sum(c["overlappable"] for c in colls)
    pairs, enclosing = _async_pairs(comps, fus_memo)
    return {
        "in_loop_wire_bytes": total,
        "overlapped_wire_bytes": overlapped,
        "overlap_fraction": (overlapped / total) if total else 0.0,
        "in_loop_collectives": n_coll,
        "overlappable_collectives": n_over,
        "per_loop": per_loop,
        "async_pairs": pairs,
        "async_pairs_enclosing_compute": enclosing,
    }


# ---------------------------------------------------------------------------
# depth-credited (wall-clock-model) overlap at an operating point
# ---------------------------------------------------------------------------

# the canonical low-bandwidth operating point for ring measurements
# (checks.check_ring_overlap_depth, benchmarks/overlap_bench.py): ALL
# tiers priced at the slow interconnect.  On the <=16-device smoke meshes
# _group_tier's replica-group classification is degenerate (everything
# reads as the fast tier), so uniform pricing is the only honest way to
# measure the slow-interconnect regime there; per-tier bandwidths belong
# to real multi-node meshes.
RING_OPERATING_POINT = {
    "peak_flops": 197e12,                       # bf16 flop/s per chip
    "tier_bw": {"model": 12.5e9, "data": 12.5e9, "pod": 12.5e9},  # 1 IB
    "coll_latency_s": 20e-6,
}


def effective_overlap(ov: Dict, *, peak_flops: float,
                      tier_bw: Dict[str, float],
                      coll_latency_s: float = 0.0) -> Dict:
    """Ring-depth-credited overlap fraction at an explicit operating point.

    ``overlap_fraction`` (structural) says which in-loop wire bytes CAN be
    scheduled under compute; this model says which bytes FIT: a collective
    issued d iterations early (``slack_iters`` — the ring depth read off
    the HLO) has a window of d iterations of body compute to complete in,

        t_window = min(d, trips) · flops_per_iter / peak_flops
        t_comm   = coll_latency_s + wire / tier_bw[tier]
        hidden   = wire · min(1, t_window / t_comm)

    Exposed (structurally dependent) collectives hide nothing.  The
    fraction is monotone in ring depth and coincides with the structural
    fraction when every overlappable collective fits its window —
    ``prefetch=2`` beats ``prefetch=1`` exactly in the regime where one
    iteration's compute cannot cover a gather (slow interconnects, small
    decode batches).  ``ov`` is an :func:`analyze_overlap` result.
    """
    total = hidden = 0.0
    for loop in ov["per_loop"].values():
        weight = loop["trip_count"] * loop["outer_mult"]
        t_iter = loop["flops_per_iter"] / peak_flops
        for c in loop["colls"]:
            wire = c["wire_bytes"] * weight
            total += wire
            if not c["overlappable"] or c["wire_bytes"] <= 0:
                continue
            bw = tier_bw.get(c["tier"], min(tier_bw.values()))
            t_comm = coll_latency_s + c["wire_bytes"] / bw
            window = min(c["slack_iters"], loop["trip_count"]) * t_iter
            hidden += wire * (1.0 if t_comm <= 0.0
                              else min(1.0, window / t_comm))
    return {
        "effective_overlap_fraction": (hidden / total) if total else 0.0,
        "hidden_wire_bytes": hidden,
        "in_loop_wire_bytes": total,
        "operating_point": {"peak_flops": peak_flops, "tier_bw": tier_bw,
                            "coll_latency_s": coll_latency_s},
    }
