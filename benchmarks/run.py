"""Benchmark entry point: one section per paper table/figure.

  comm_volume      — Table 1  (analytic + measured wire bytes)
  throughput_model — Figs 11-13 / Table 2 (roofline model over bandwidth)
  kernel_bench     — Table 3  (fused vs staged quantization pipeline)
  memory_model     — Fig 4 / Table 4 (DP vs ZeRO-3 vs hpZ vs MiCS)
  convergence      — Fig 14 / Table 5 (loss curves per variant)
  overlap_bench    — measured schedule overlap vs ring depth (BENCH json:
                     structural + depth-credited fractions, async pairs,
                     break-even depth projection; 8-dev subprocess)
  tuner_report     — static boot-time resolution sweep (repro.tune):
                     resolved knobs + (k+1)-ring HBM ledger + break-even
                     depth per arch x mesh, checked against the committed
                     deterministic snapshot
  serve_bench      — decode tok/s + TTFT vs occupancy, and the paged-pool
                     multi-tenant trace (equal-HBM admission, prefix-cache
                     TTFT, speculative acceptance) with its structural
                     facts gated against the committed BENCH_serve.json
  roofline         — §Roofline table from the dry-run JSONs (if present)

Any section that raises marks the whole run failed (nonzero exit) — no
silently swallowed crashes.

Run everything: PYTHONPATH=src python -m benchmarks.run
Select sections: PYTHONPATH=src python -m benchmarks.run comm_volume ...
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (comm_volume, convergence, kernel_bench,
                            memory_model, overlap_bench, roofline,
                            serve_bench, throughput_model, tuner_report)
    sections = {
        "comm_volume": comm_volume.main,
        "throughput_model": throughput_model.main,
        "kernel_bench": kernel_bench.main,
        "memory_model": memory_model.main,
        "convergence": convergence.main,
        "overlap_bench": overlap_bench.main,
        "tuner_report": tuner_report.main,
        "serve_bench": serve_bench.main,
    }
    pick = [a for a in sys.argv[1:] if a in sections] or list(sections)
    failures = []
    for name in pick:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            sections[name]()
            print(f"[{name} done in {time.time()-t0:.0f}s]")
        except Exception:
            failures.append(name)
            traceback.print_exc()

    if not sys.argv[1:] or "roofline" in sys.argv[1:]:
        print("\n===== roofline =====")
        try:
            rows = roofline.load()
            print(roofline.render(rows))
        except Exception:
            # a crashed section must fail the run, not scroll past — this
            # used to print the traceback and exit 0
            failures.append("roofline")
            traceback.print_exc()

    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
