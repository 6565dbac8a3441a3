"""Wall-clock overlap hook: measured schedule overlap vs ring depth.

For each (stack, prefetch depth) cell the compiled 8-device train step is
analyzed three ways (launch/hlo_analysis):

  * structural ``overlap_fraction`` — which in-loop wire bytes CAN move
    under compute (dependence analysis; depth-blind once > 0);
  * ``async_pairs_enclosing_compute`` — the latency-hiding scheduler's
    own evidence, when the backend emits async collectives (0 on the CPU
    smoke backend; the hook exists so an accelerator run records the real
    number next to the projection);
  * ``effective_overlap_fraction`` — ring-depth-credited overlap at a
    low-bandwidth operating point (a gather issued d layers early is
    credited against d layers of compute; see hlo_analysis.effective_overlap).

Alongside, the depth-k step-time projection from
``benchmarks/throughput_model.py`` (break-even depth per interconnect).
Emits one BENCH json line so the perf trajectory records the measured
numbers; ``python benchmarks/overlap_bench.py`` also prints a table.

Runs the measurement in a subprocess with simulated devices (see
testing/subproc.py note).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict

# Measured on simulated host devices: this process and the child it
# starts (which inherits the variable) never take an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from repro.testing.checks import _prefetch_abstract_args
from repro.launch.hlo_analysis import (RING_OPERATING_POINT as OP,
                                       analyze_overlap, effective_overlap)

out = {"operating_point": OP}
for key, arch in (("dense", "gpt-350m"), ("moe", "deepseek-moe-16b")):
    out[key] = {}
    for pf in (0, 1, 2):
        ts, args = _prefetch_abstract_args(pf, arch_name=arch, n_layers=4)
        txt = ts.fn.lower(*args).compile().as_text()
        ov = analyze_overlap(txt)
        eff = effective_overlap(ov, peak_flops=OP["peak_flops"],
                                tier_bw=OP["tier_bw"],
                                coll_latency_s=OP["coll_latency_s"])
        out[key][str(pf)] = {
            "overlap_fraction": ov["overlap_fraction"],
            "effective_overlap_fraction":
                eff["effective_overlap_fraction"],
            "async_pairs": ov["async_pairs"],
            "async_pairs_enclosing_compute":
                ov["async_pairs_enclosing_compute"],
            "max_slack_iters": max(
                (l["max_slack_iters"] for l in ov["per_loop"].values()),
                default=1),
            "in_loop_wire_bytes": ov["in_loop_wire_bytes"],
            "loops_without_compute": sum(
                1 for l in ov["per_loop"].values()
                if not l["has_compute"]),
        }
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""


def measure() -> Dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                       capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"overlap bench failed:\n{r.stdout}\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in:\n{r.stdout}")


def projection() -> Dict:
    try:
        from benchmarks.throughput_model import (
            SLOW_BWS, break_even_depth, model_tflops, step_time_ring)
    except ModuleNotFoundError:  # run as a script, not a package
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from throughput_model import (SLOW_BWS, break_even_depth,
                                      model_tflops, step_time_ring)
    n_dev = 18e9 / 384
    proj = {}
    for bw_name, bw in SLOW_BWS.items():
        proj[bw_name] = {
            "break_even_depth": break_even_depth(n_dev, 2048, "zeropp", bw),
            "tflops_by_depth": {
                str(d): model_tflops(
                    n_dev, 2048,
                    step_time_ring(n_dev, 2048, "zeropp", bw, d))
                for d in (0, 1, 2, 4)},
        }
    return proj


def main():
    measured = measure()
    res = {"device": measured.pop("device"),
           "operating_point": measured.pop("operating_point", None),
           "measured": measured, "projection": projection()}
    print("BENCH " + json.dumps({"overlap": res}))
    print(f"# device: {res['device']}")
    print(f"\n{'stack':<6} {'pf':>3} {'struct':>8} {'effective':>10} "
          f"{'slack':>6} {'async':>6} {'bare loops':>10}")
    for stack, by_pf in res["measured"].items():
        for pf, m in sorted(by_pf.items()):
            print(f"{stack:<6} {pf:>3} {m['overlap_fraction']:>8.3f} "
                  f"{m['effective_overlap_fraction']:>10.5f} "
                  f"{m['max_slack_iters']:>6} "
                  f"{m['async_pairs_enclosing_compute']:>6} "
                  f"{m['loops_without_compute']:>10}")
    print("\nbreak-even ring depth (18B zeropp):",
          {k: v["break_even_depth"]
           for k, v in res["projection"].items()})


if __name__ == "__main__":
    main()
