"""Runtime telemetry report: a real 8-device train run + serve ticks with
the obs/ subsystem on, exported in the BENCH schema.

One subprocess (simulated devices, see testing/subproc.py note) runs:

  1. a short telemetry-on training run through ``launch/train.train_loop``
     (``--metrics-dir`` path): jsonl event log, per-step wall-time
     histogram, per-label comm counters from the one-time jaxpr walk, and
     the measured-vs-projected gate in ASSERT mode — the run fails if the
     recorded per-step comm bytes drift from the analytic projection by
     more than 1% on any collective label;
  2. a serving burst through :class:`ServeEngine` (3 requests, 2 slots,
     slot recycling) so the snapshot carries the serve metrics surface
     (TTFT / per-token latency percentiles, occupancy, lifecycle counts).

The merged registry snapshot + gate report is printed as one BENCH json
line.  ``--write-snapshot`` refreshes ``snapshots/BENCH_runtime.json``
(committed so ``repro.obs.report diff`` has a baseline; wall-time leaves
drift run-to-run — the stable surface is the comm bytes, counter totals,
and gate verdict).
"""
from __future__ import annotations

import argparse
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
import argparse
import json
import tempfile
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

md = tempfile.mkdtemp(prefix="runtime_report_")
from repro.launch.train import train_loop
args = argparse.Namespace(
    arch="gpt-350m", reduced=True, mesh="4x2", variant="zeropp",
    steps=4, batch=16, seq=64, accum=1, lr=3e-3, seed=0,
    ckpt_dir=None, ckpt_every=0, ckpt_format="fp32", log_every=0,
    simulate_failure_at=None, metrics_dir=md, trace_steps=1,
    obs_gate=True)
out = train_loop(args)           # raises GateFailure on >1% comm drift
gate = out["gate"]

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import ServeEngine
from repro.train.policy import make_policy
from repro.train.state import param_specs

mesh = make_mesh((2, 4), ("data", "model"))
arch = get_config("qwen3-0.6b").reduced()
pol = make_policy(arch, tuple(mesh.axis_names))
model = Model(arch, pol.zcfg, world=8)
params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
p_specs = param_specs(model, tuple(mesh.axis_names))
params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
          for k, v in params.items()}
engine = ServeEngine(model, mesh, params, n_slots=2, kv_len=32,
                     batch_axes=(), kv_axes=("model",))
rng = np.random.default_rng(0)
for i, P in enumerate((4, 7, 5)):
    engine.submit(rng.integers(0, arch.vocab, P).astype(np.int32),
                  max_new_tokens=4, seed=i)
engine.run(max_steps=200)
stats = engine.stats()
assert stats["completed"] == 3 and stats["expired"] == 0, stats

from repro.obs.report import export_snapshot
doc = export_snapshot(extra={
    "gate": gate,
    "serve": stats,
    "config": {"train": {"arch": "gpt-350m", "variant": "zeropp",
                         "mesh": [4, 2], "steps": 4},
               "serve": {"arch": "qwen3-0.6b", "mesh": [2, 4],
                         "slots": 2, "requests": 3}}})
from repro.obs.report import device_info
doc["device"] = device_info()
print("RESULT " + json.dumps(doc))
"""

SNAPSHOT = os.path.join(os.path.dirname(__file__), "snapshots",
                        "BENCH_runtime.json")


def measure() -> Dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                       capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(
            f"runtime report subprocess failed:\n{r.stdout}\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in:\n{r.stdout}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-snapshot", action="store_true",
                    help=f"refresh {SNAPSHOT}")
    args, _ = ap.parse_known_args()

    doc = measure()
    rt = doc["runtime"]
    print("BENCH " + json.dumps(doc))
    print(f"# device: {doc['device']}")

    gate = rt["gate"]
    assert gate["ok"], gate           # belt-and-braces: subprocess asserted
    print("\n# measured vs projected per-device wire bytes (train step)")
    print(f"{'label':<26} {'measured':>12} {'projected':>12} {'rel':>8}")
    for lbl, row in sorted(gate["comm"]["labels"].items()):
        print(f"{lbl:<26} {row['measured']:>12.0f} "
              f"{row['projected']:>12.0f} {row['rel']:>8.4f}")
    met = rt["metrics"]
    wall = met.get("train.step.wall_ms", {})
    print(f"\ntrain: steps={met.get('train.steps')} "
          f"tokens={met.get('train.tokens')} "
          f"step p50={wall.get('p50', 0):.0f}ms")
    sv = rt["serve"]
    print(f"serve: completed={sv['completed']}/{sv['admitted']} "
          f"expired={sv['expired']} steps={sv['steps']} "
          f"ttft p50={sv['ttft_ms']['p50']:.0f}ms "
          f"tok/s={sv['tok_per_s'] and round(sv['tok_per_s'], 1)}")
    disp = {k: v for k, v in met.items()
            if k.startswith("kernels.dispatch.")}
    print(f"kernel dispatches: {disp}")

    if args.write_snapshot:
        with open(SNAPSHOT, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {SNAPSHOT}")
    return doc


if __name__ == "__main__":
    main()
