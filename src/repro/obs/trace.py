"""Span/event tracer with a host-side structured jsonl log.

Two complementary mechanisms, one per timescale:

* **Host spans/events** (`Tracer.span` / `.event` / `.counter`): plain
  Python context managers stamping ``time.monotonic_ns()``, buffered and
  flushed to an append-mode jsonl file at tick boundaries (``flush()``).
  Optionally each span also opens a ``jax.profiler.TraceAnnotation`` so
  the spans line up with device activity in a profiler trace.
* **In-jit labels** (`annotate`): ``jax.named_scope`` wrappers.  These are
  trace-time only — zero runtime cost, no instruction changes — but the
  labels survive into ``eqn.source_info.name_stack`` of the jaxpr
  (including through ``lax.scan`` bodies and ``custom_vjp`` transposition,
  where they appear wrapped as e.g. ``transpose(jvp(zero.hpz_gather))``),
  which is how ``launch/jaxpr_analysis.py`` attributes per-collective wire
  bytes.  XLA keeps the name stack as each instruction's ``op_name``, and
  a device profiler trace carries it as the ``tf_op`` of every operation
  it ran, which is how ``bench/scopes.py`` splits device time by scope.
  Instructions XLA makes itself (copy loops, rewritten slices) carry no
  ``op_name`` and cannot be labelled from here.

Kill-safety / replay contract (elastic training): every flush ends in
``os.fsync``; a SIGKILL can at worst truncate the final line, which
``read_events`` skips.  Counter records carry the step tag, and
``replay_counters`` deduplicates per ``(name, step)`` with
last-occurrence-wins — a restarted run that re-emits steps already in the
log (resume from an earlier checkpoint) replays to the same totals as an
uninterrupted run.  The log is opened in append mode so in-process or
cross-process restarts extend, never clobber, the history.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, IO, List, Optional, Tuple

import jax

_NULLCTX = contextlib.nullcontext()


def annotate(name: str):
    """In-jit label: a ``jax.named_scope`` whose name survives into the
    jaxpr ``name_stack`` and into the ``op_name`` of every instruction
    made under it, which the device trace carries as ``tf_op``.  Names:
    ``zero.<op>`` on the collective wrappers in ``core/collectives.py``
    (anything outside such a scope is bucketed as ``other`` by the wire
    analyzer); ``step.<phase>`` on the train step's phases
    (``models/model.py``, ``core/schedule.py``'s ring); ``optim.<part>``
    on the optimizer (``optim/adamw.py``)."""
    return jax.named_scope(name)


class _Span:
    """Enabled-path span: stamps monotonic ns, appends one record on exit."""

    __slots__ = ("_tracer", "_name", "_tags", "_t0", "_prof")

    def __init__(self, tracer: "Tracer", name: str, tags: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._tags = tags
        self._prof = None

    def __enter__(self):
        if self._tracer.profiler_annotations:
            self._prof = jax.profiler.TraceAnnotation(self._name)
            self._prof.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.monotonic_ns() - self._t0
        if self._prof is not None:
            self._prof.__exit__(exc_type, exc, tb)
        rec = {"kind": "span", "name": self._name,
               "t_ns": self._t0, "dur_ns": dur}
        if self._tags:
            rec.update(self._tags)
        self._tracer._emit(rec)
        return False


class Tracer:
    """Buffered jsonl tracer.  ``enabled=False`` makes every call a no-op
    (spans return one shared ``nullcontext`` — no allocation), which is the
    disabled-overhead story the telemetry gate measures."""

    def __init__(self, path: Optional[str] = None, *, enabled: bool = True,
                 profiler_annotations: bool = False):
        self.path = path
        self.enabled = enabled
        self.profiler_annotations = profiler_annotations
        self._buf: List[str] = []
        self._fh: Optional[IO[str]] = None

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **tags):
        if not self.enabled:
            return _NULLCTX
        return _Span(self, name, tags)

    def event(self, name: str, **tags) -> None:
        if not self.enabled:
            return
        rec = {"kind": "event", "name": name, "t_ns": time.monotonic_ns()}
        rec.update(tags)
        self._emit(rec)

    def counter(self, name: str, value, step: Optional[int] = None,
                **tags) -> None:
        """A replayable counter sample.  Records WITH a step tag are
        deduplicated per (name, step) on replay — emit per-step quantities
        this way so elastic restarts cannot double-count; records without
        a step are summed as-is."""
        if not self.enabled:
            return
        rec: Dict[str, Any] = {"kind": "counter", "name": name,
                               "t_ns": time.monotonic_ns(), "value": value}
        if step is not None:
            rec["step"] = step
        rec.update(tags)
        self._emit(rec)

    def _emit(self, rec: Dict[str, Any]) -> None:
        self._buf.append(json.dumps(rec, sort_keys=True))

    # -- io ----------------------------------------------------------------

    def flush(self) -> None:
        """Tick-boundary flush: one write + fsync for everything buffered.
        Called once per train step / serve tick, never from jitted code."""
        if not self._buf or self.path is None:
            self._buf.clear() if self.path is None else None
            return
        if self._fh is None:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._fh = open(self.path, "a")
        self._fh.write("\n".join(self._buf) + "\n")
        self._buf.clear()
        self._fh.flush()
        os.fsync(self._fh.fileno())

    def close(self) -> None:
        self.flush()
        if self._fh is not None:
            self._fh.close()
            self._fh = None


_disabled = Tracer(enabled=False)
_current: Tracer = _disabled


def get_tracer() -> Tracer:
    return _current


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install the process tracer (None restores the disabled singleton);
    returns the previous one."""
    global _current
    old = _current
    _current = tracer if tracer is not None else _disabled
    return old


# -- replay ----------------------------------------------------------------

def read_events(path: str) -> List[Dict[str, Any]]:
    """All records in file order.  Tolerates a truncated final line (the
    one write a SIGKILL can shear)."""
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def replay_counters(path: str, up_to_step: Optional[int] = None
                    ) -> Dict[str, float]:
    """Reduce the event log to counter totals.

    Stepped records dedupe per (name, step) with last-occurrence-wins, so
    a run that restarted from a checkpoint and re-emitted steps already in
    the log replays to the same totals as an uninterrupted run.  Unstepped
    records are summed in file order.
    """
    stepped: Dict[Tuple[str, int], float] = {}
    flat: Dict[str, float] = {}
    for rec in read_events(path):
        if rec.get("kind") != "counter":
            continue
        name = rec["name"]
        step = rec.get("step")
        value = rec.get("value", 0)
        if step is None:
            flat[name] = flat.get(name, 0) + value
        else:
            if up_to_step is not None and step > up_to_step:
                continue
            stepped[(name, step)] = value
    totals = dict(flat)
    for (name, _), value in stepped.items():
        totals[name] = totals.get(name, 0) + value
    return totals
