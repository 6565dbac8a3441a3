"""BENCH snapshot export, snapshot diffing, and the measured-vs-projected
regression gate (DESIGN.md §8).

Three pieces:

* :func:`export_snapshot` — serialize the metrics registry into the
  repo's BENCH json schema (one top-level section key, nested plain
  dicts — the same shape ``benchmarks/snapshots/BENCH_*.json`` already
  use), optionally merged with caller-provided extras (gate results,
  run config) and written to disk.

* :func:`bench_diff` — compare two BENCH snapshots leaf-by-leaf and
  report relative drift.  Also a tiny CLI:
  ``python -m repro.obs.report diff OLD.json NEW.json [--rel-tol 0.05]``.

* The gate — :func:`comm_gate` checks recorded per-step wire bytes
  (jaxpr walk, ``wire_by_label``) against the analytic projection
  (``Model.comm_events`` folded through ``zeropp.step_wire_by_label``)
  per collective label at a strict default tolerance of 1%;
  :func:`overhead_gate` checks the telemetry-disabled step time against
  a no-telemetry baseline (medians of interleaved samples, so CI noise
  hits both sides alike); :func:`runtime_gate` combines them into one
  pass/fail report.  Tolerance policy: comm bytes are DETERMINISTIC
  (both sides count the same traced program), so 1% is generous — the
  validated repo configurations match to the byte and any real drift
  means one side's model is wrong; wall-clock comparisons are loose
  because CPU CI timing is noisy.

This module deliberately imports nothing from jax or the rest of
``repro`` at module scope — gate helpers that need the analytic model
import lazily — so it stays importable from lightweight tooling.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from repro.obs.metrics import Registry, get_registry

__all__ = ["export_snapshot", "bench_diff", "format_diff",
           "comm_gate", "overhead_gate", "runtime_gate",
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
# snapshot export
# ---------------------------------------------------------------------------

def export_snapshot(path: Optional[str] = None, *,
                    registry: Optional[Registry] = None,
                    section: str = "runtime",
                    extra: Optional[Mapping[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Registry -> ``{section: {metrics: <flat snapshot>, **extra}}``.

    The flat metric names (``comm.zero.qwz_gather.bytes``, ...) stay flat
    under ``"metrics"`` — they are the stable diffable surface; ``extra``
    carries structured one-off payloads (gate report, run config).
    """
    reg = registry if registry is not None else get_registry()
    body: Dict[str, Any] = {"metrics": reg.snapshot()}
    if extra:
        body.update(extra)
    doc = {section: body}
    if path:
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return doc


# ---------------------------------------------------------------------------
# snapshot diff
# ---------------------------------------------------------------------------

def _leaves(doc: Any, prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            out.update(_leaves(v, f"{prefix}{k}."))
        return out
    out[prefix[:-1]] = doc
    return out


def bench_diff(old: Mapping[str, Any], new: Mapping[str, Any], *,
               rel_tol: float = 0.05
               ) -> List[Tuple[str, Any, Any, Optional[float]]]:
    """Leaf-wise diff of two BENCH docs.

    Returns rows ``(key, old, new, rel)`` for every leaf that drifted
    beyond ``rel_tol`` (numeric), changed value (non-numeric), or exists
    on only one side (the missing side is None, rel is None).
    """
    a, b = _leaves(dict(old)), _leaves(dict(new))
    rows: List[Tuple[str, Any, Any, Optional[float]]] = []
    for key in sorted(set(a) | set(b)):
        va, vb = a.get(key), b.get(key)
        if key not in a or key not in b:
            rows.append((key, va, vb, None))
            continue
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)) \
                and not isinstance(va, bool) and not isinstance(vb, bool):
            denom = max(abs(va), abs(vb), 1e-12)
            rel = abs(va - vb) / denom
            if rel > rel_tol:
                rows.append((key, va, vb, rel))
        elif va != vb:
            rows.append((key, va, vb, None))
    return rows


def format_diff(rows: Sequence[Tuple[str, Any, Any, Optional[float]]]) -> str:
    if not rows:
        return "no drift"
    lines = []
    for key, va, vb, rel in rows:
        tail = f"  rel={rel:.3f}" if rel is not None else ""
        lines.append(f"  {key}: {va!r} -> {vb!r}{tail}")
    return "\n".join(lines)


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


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="repro.obs.report")
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("diff", help="compare two BENCH snapshots")
    d.add_argument("old")
    d.add_argument("new")
    d.add_argument("--rel-tol", type=float, default=0.05)
    d.add_argument("--fail-on-drift", action="store_true")
    args = ap.parse_args(argv)
    with open(args.old) as fh:
        old = json.load(fh)
    with open(args.new) as fh:
        new = json.load(fh)
    rows = bench_diff(old, new, rel_tol=args.rel_tol)
    print(format_diff(rows))
    return 1 if (rows and args.fail_on_drift) else 0


if __name__ == "__main__":
    sys.exit(main())
