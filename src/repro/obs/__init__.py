"""Runtime telemetry: tracing, metrics, and the measured-vs-projected gate.

Three modules, layered so the import graph stays acyclic:

  ``obs.metrics``  stdlib-only counters/gauges/histograms behind a process
                   registry.  Safe to import from anywhere (kernels.ops,
                   core.collectives) — it never imports jax or repro.
  ``obs.trace``    span/event tracer: host-side jsonl event log with
                   monotonic timestamps, optional
                   ``jax.profiler.TraceAnnotation`` spans, and
                   ``annotate()`` — the in-jit ``jax.named_scope`` labels
                   (``zero.*`` on collectives, ``step.*`` on the train
                   step's phases, ``optim.*`` on the optimizer).  They
                   survive into jaxpr ``name_stack``s, where
                   ``launch/jaxpr_analysis.py`` attributes wire bytes to
                   specific ZeRO collectives, and into each compiled
                   instruction's ``op_name``, which a device profiler
                   trace carries as the ``tf_op`` of every operation
                   (``bench/scopes.py`` reads device time per scope).
  ``obs.report``   the measured-vs-projected gate (comm bytes vs the
                   analytic model, overhead).

Disabled overhead is ~zero: the null tracer hands out one shared
``nullcontext``, counters live host-side only, and nothing here ever runs
inside a jitted step — per-step comm bytes come from a one-time jaxpr walk
of the compiled step, accumulated by host counters at tick boundaries.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               get_registry, set_registry)
from repro.obs.trace import (Tracer, annotate, get_tracer, set_tracer,
                             replay_counters)
from repro.obs.report import (GateFailure, comm_gate, overhead_gate,
                              projected_wire_by_label, runtime_gate)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "get_registry", "set_registry",
    "Tracer", "annotate", "get_tracer", "set_tracer", "replay_counters",
    "GateFailure", "comm_gate", "overhead_gate", "projected_wire_by_label",
    "runtime_gate",
]
