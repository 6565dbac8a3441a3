"""The measured-vs-projected regression gate (DESIGN.md §8).

:func:`comm_gate` checks recorded per-step wire bytes (jaxpr walk,
``wire_by_label``) against the analytic projection (``Model.comm_events``
folded through ``zeropp.step_wire_by_label``) per collective label at a
strict default tolerance of 1%; :func:`overhead_gate` checks the
telemetry-disabled step time against a no-telemetry baseline (medians of
interleaved samples, so CI noise hits both sides alike);
:func:`runtime_gate` combines them into one pass/fail report.  Tolerance
policy: comm bytes are DETERMINISTIC (both sides count the same traced
program), so 1% is generous — the validated repo configurations match to
the byte and any real drift means one side's model is wrong; wall-clock
comparisons are loose because CPU CI timing is noisy.

This module deliberately imports nothing from jax or the rest of
``repro`` at module scope — gate helpers that need the analytic model
import lazily — so it stays importable from lightweight tooling.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

__all__ = ["comm_gate", "overhead_gate", "runtime_gate",
           "projected_wire_by_label", "GateFailure", "device_info"]


class GateFailure(AssertionError):
    """A measured-vs-projected check exceeded its tolerance."""


def device_info() -> Dict[str, Any]:
    """The devices a result was measured on, as JAX reports them."""
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# ---------------------------------------------------------------------------
# measured-vs-projected gate
# ---------------------------------------------------------------------------

def projected_wire_by_label(model: Any, sizes: Mapping[str, int],
                            accum: int = 1) -> Dict[str, float]:
    """Analytic per-step per-device wire bytes by collective label, from
    the schedule's event enumeration (``Model.comm_events``)."""
    from repro.core.zeropp import step_wire_by_label
    return step_wire_by_label(model.comm_events(accum=accum), model.zcfg,
                              dict(sizes))


def comm_gate(measured: Mapping[str, float], projected: Mapping[str, float],
              *, tol: float = 0.01, ignore: Sequence[str] = ("other",)
              ) -> Dict[str, Any]:
    """Per-label relative comparison of measured (jaxpr walk) vs projected
    (analytic event model) per-step wire bytes.

    ``other`` (unlabeled collectives: loss psums etc.) is reported but not
    gated by default — it carries no parameter traffic in this codebase
    and measures 0 bytes on every validated configuration.
    """
    rows: Dict[str, Dict[str, float]] = {}
    ok = True
    for lbl in sorted(set(measured) | set(projected)):
        m = float(measured.get(lbl, 0.0))
        p = float(projected.get(lbl, 0.0))
        rel = abs(m - p) / max(m, p, 1.0)
        gated = lbl not in ignore
        passed = (rel <= tol) or not gated
        ok = ok and passed
        rows[lbl] = {"measured": m, "projected": p, "rel": rel,
                     "pass": passed}
    return {"ok": ok, "tol": tol, "labels": rows}


def overhead_gate(enabled_s: Sequence[float], disabled_s: Sequence[float],
                  *, tol: float = 0.02) -> Dict[str, Any]:
    """Telemetry overhead check: median step time with the tracer+metrics
    DISABLED must be within ``tol`` of a run that never created them —
    and, reported for context, the enabled run's median.  Samples should
    come from alternating enabled/disabled steps of the same jitted
    function so machine noise lands on both sides."""
    med_e = _median(enabled_s)
    med_d = _median(disabled_s)
    rel = (med_d - med_e) / max(med_e, 1e-12)
    # disabled-path overhead can only come from the no-op guards; a
    # negative rel (disabled faster) trivially passes
    return {"ok": rel <= tol or med_d <= med_e, "tol": tol,
            "median_enabled_s": med_e, "median_disabled_s": med_d,
            "rel_overhead": rel}


def _median(xs: Sequence[float]) -> float:
    if not xs:
        raise ValueError("no samples")
    ys = sorted(float(x) for x in xs)
    n = len(ys)
    return ys[n // 2] if n % 2 else 0.5 * (ys[n // 2 - 1] + ys[n // 2])


def runtime_gate(*, measured: Mapping[str, float],
                 projected: Mapping[str, float],
                 enabled_s: Optional[Sequence[float]] = None,
                 disabled_s: Optional[Sequence[float]] = None,
                 comm_tol: float = 0.01, overhead_tol: float = 0.02,
                 strict: bool = False) -> Dict[str, Any]:
    """Combined gate report.  ``strict=True`` raises :class:`GateFailure`
    listing every failing check instead of returning ``ok=False``."""
    report: Dict[str, Any] = {"comm": comm_gate(measured, projected,
                                                tol=comm_tol)}
    if enabled_s and disabled_s:
        report["overhead"] = overhead_gate(enabled_s, disabled_s,
                                           tol=overhead_tol)
    report["ok"] = all(sec["ok"] for k, sec in report.items()
                       if isinstance(sec, dict))
    if strict and not report["ok"]:
        bad = []
        for lbl, row in report["comm"]["labels"].items():
            if not row["pass"]:
                bad.append(f"comm[{lbl}]: measured={row['measured']:.0f} "
                           f"projected={row['projected']:.0f} "
                           f"rel={row['rel']:.4f} > {comm_tol}")
        ov = report.get("overhead")
        if ov and not ov["ok"]:
            bad.append(f"overhead: disabled median {ov['median_disabled_s']:.6f}s "
                       f"vs baseline {ov['median_enabled_s']:.6f}s "
                       f"(rel {ov['rel_overhead']:.4f} > {overhead_tol})")
        raise GateFailure("measured-vs-projected gate failed:\n  "
                          + "\n  ".join(bad))
    return report

