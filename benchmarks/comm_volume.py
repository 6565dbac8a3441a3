"""Paper Table 1: per-step communication volume, ZeRO-3 vs ZeRO++.

Two measurements:
  * analytic — ZeroConfig.comm_volume_per_step (the paper's 3M -> 0.75M)
  * measured — wire bytes from the traced step's jaxpr (true dtypes,
    exact mesh axis names), split by interconnect tier, for every variant.

The measured numbers come from a subprocess with 8 simulated devices (2x2x2
pod/data/model mesh), so "slow tier" = groups crossing the model ring.
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
import jax
import jax.numpy as jnp
from repro.configs import get_config
from repro.launch import dryrun as dr
from repro.launch.mesh import make_test_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.train import trainer as trainer_lib
from repro.train.policy import make_policy
import dataclasses as dc
from repro.configs.base import ShapeConfig

out = {}
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
axes = tuple(mesh.axis_names)
arch = get_config("gpt-350m").reduced(
    n_layers=4, d_model=256, vocab=512, n_heads=4, n_kv_heads=4,
    head_dim=64, d_ff=1024)
for variant in ("baseline", "zeropp", "qwz", "hpz", "qgz"):
    pol = make_policy(arch, axes, variant)
    model = Model(arch, pol.zcfg, world=8)
    opt_cfg = AdamWConfig(moments_dtype=pol.moments_dtype)
    ts = trainer_lib.build_train_step(model, mesh, opt_cfg, donate=False,
                                      global_batch=8)
    p_sh, o_sh = trainer_lib.state_shapes(model, opt_cfg)
    params = dr._abstract(p_sh, mesh, ts.in_specs[0])
    opt = dr._abstract(o_sh, mesh, ts.in_specs[1])
    shape = ShapeConfig("t", "train", 64, 8)
    batch = dr._abstract(dr.train_batch_shapes(model, shape), mesh,
                         ts.in_specs[2])
    res = dr._jaxpr_info(ts.fn, (params, opt, batch), mesh)
    from repro.core.zeropp import step_wire_by_label
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out[variant] = {
        "n_params": model.n_params(),
        "wire": res["collectives"]["per_tier_wire"],
        "per_op": {k: v["wire_bytes"]
                   for k, v in res["collectives"]["per_op"].items()},
        "wire_by_label": res["collectives"]["wire_by_label"],
        "projected_by_label": step_wire_by_label(
            model.comm_events(), model.zcfg, sizes),
    }
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""


def measured() -> Dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                       capture_output=True, text=True, timeout=1800)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"comm_volume subprocess failed:\n{r.stdout}\n{r.stderr}")


def analytic_table() -> Dict:
    from repro.core.zeropp import ZeroConfig, comm_volume_per_step
    M = 100_000_000  # 100M params
    rows = {}
    for name, z in [
        ("zero3", ZeroConfig.baseline()),
        ("zeropp", ZeroConfig()),
        ("qwz", ZeroConfig(hpz=False, qgz=False)),
        ("hpz", ZeroConfig(qwz=False, qgz=False)),
        ("qgz", ZeroConfig(qwz=False, hpz=False)),
    ]:
        rows[name] = comm_volume_per_step(M, z)
    return rows


def main(csv=True):
    rows = analytic_table()
    base = rows["zero3"]["total"]
    print("# Table 1 (analytic, M=100M params, bf16)")
    print("variant,fwd_allgather,bwd_allgather,grad_reduce,total,reduction")
    for name, r in rows.items():
        print(f"{name},{r['fwd_allgather']},{r['bwd_allgather']},"
              f"{r['grad_reduce']},{r['total']},"
              f"{base / max(r['total'], 1):.2f}x")

    print("# Table 1 (measured wire bytes from compiled HLO, 8 devices)")
    m = measured()
    print(f"# device: {m['device']}")
    base_slow = None
    print("variant,slow_tier_bytes,fast_tier_bytes,reduction_slow")
    for variant in ("baseline", "zeropp", "qwz", "hpz", "qgz"):
        w = m[variant]["wire"]
        slow = w["pod"] + w["data"]
        fast = w["model"]
        if variant == "baseline":
            base_slow = slow
        print(f"{variant},{slow:.0f},{fast:.0f},"
              f"{base_slow / max(slow, 1):.2f}x")

    # measured (jaxpr named-scope buckets) vs projected (the analytic
    # event model behind the runtime gate, obs/report.py) per collective
    # label — both sides count the same traced program, so they must
    # agree to 1% (in practice: to the byte) or one model is wrong
    print("# measured vs projected per-device wire bytes by label")
    print("variant,label,measured,projected,rel")
    worst = 0.0
    for variant in ("baseline", "zeropp", "qwz", "hpz", "qgz"):
        mb = m[variant]["wire_by_label"]
        pb = m[variant]["projected_by_label"]
        for lbl in sorted(set(mb) | set(pb)):
            if lbl == "other":
                continue
            mv, pv = mb.get(lbl, 0.0), pb.get(lbl, 0.0)
            rel = abs(mv - pv) / max(mv, pv, 1.0)
            worst = max(worst, rel)
            print(f"{variant},{lbl},{mv:.0f},{pv:.0f},{rel:.4f}")
    if worst > 0.01:
        raise AssertionError(
            f"measured vs projected comm bytes disagree (worst rel "
            f"{worst:.4f} > 0.01) — see table above")
    print(f"# measured==projected within 1% (worst rel {worst:.6f})")
    return m


if __name__ == "__main__":
    main()
