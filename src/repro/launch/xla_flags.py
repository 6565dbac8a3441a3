"""XLA flags that let the compiler *execute* the prefetched schedule.

core/schedule.py arranges the program so that each scan iteration's
collectives are data-independent of its matmuls (verified structurally by
hlo_analysis.analyze_overlap).  Turning that freedom into wall-clock
overlap is the latency-hiding scheduler's job:

  * TPU — libtpu runs its latency-hiding scheduler by default, so nothing
    is set.  TPU compiler flags belong in ``LIBTPU_INIT_ARGS``, never in
    ``XLA_FLAGS``: jaxlib parses ``XLA_FLAGS`` and aborts the process on a
    ``--xla_tpu_*`` name it does not know.
  * CPU — no LHS pass exists; the thunk runtime's concurrency-optimized
    scheduler is the closest analogue.  jaxlib always carries the CPU
    backend, so this flag parses on every platform and is inert off CPU.

``enable_overlap_flags()`` must run before the first jax import in the
process (XLA reads the env once at backend init) — launch/train.py calls
it at the top of ``main()``.
"""
from __future__ import annotations

import os

OVERLAP_FLAGS = ("--xla_cpu_enable_concurrency_optimized_scheduler=true",)


def enable_overlap_flags() -> str:
    """Append :data:`OVERLAP_FLAGS` to ``$XLA_FLAGS`` (idempotent) and
    return the result."""
    parts = os.environ.get("XLA_FLAGS", "").split()
    present = {p.split("=", 1)[0] for p in parts}
    for flag in OVERLAP_FLAGS:
        # match on the flag NAME: a user-set opposite value wins, we never
        # append a duplicate that would silently override it
        if flag.split("=", 1)[0] not in present:
            parts.append(flag)
    os.environ["XLA_FLAGS"] = " ".join(parts)
    return os.environ["XLA_FLAGS"]
