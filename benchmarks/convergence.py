"""Paper Fig. 14 / Table 5: convergence of ZeRO++ vs baseline vs
non-blocked quantization.

Trains the reduced GPT config on the deterministic synthetic LM (8
simulated devices, identical data order across variants) and compares loss
curves:
  * zeropp (blocked INT8/INT4) must track the ZeRO-3 baseline closely;
  * zeropp with NON-blocked (single-scale) weight quantization must be
    clearly worse / unstable — the paper's divergence result.

``--elastic`` instead compares an INTERRUPTED run (worker death mid-run,
resume from the latest async checkpoint via the elastic supervisor)
against the uninterrupted oracle: the replayed curve must be bit-exact.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

# Measured on simulated host devices: this process and the child it
# starts (which inherits the variable) never take an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.data.synthetic import SyntheticLM, make_batch
from repro.configs import get_config
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import warmup_cosine
from repro.train import trainer as trainer_lib
from repro.train.policy import make_policy
from repro.train.trainer import init_state, place_batch
from repro.launch.mesh import make_mesh

STEPS = int(os.environ.get("CONV_STEPS", "40"))
arch = get_config("gpt-350m").reduced()
mesh = make_mesh((4, 2), ("data", "model"))
lm = SyntheticLM(vocab=arch.vocab, seq_len=64, seed=11)
out = {"entropy_bound": lm.entropy_bound}
for name, variant, overrides in [
    ("baseline", "baseline", {}),
    ("zeropp", "zeropp", {}),
    ("zeropp_nonblocked", "zeropp", {"qwz_blocked": False}),
]:
    pol = make_policy(arch, tuple(mesh.axis_names), variant, **overrides)
    model = Model(arch, pol.zcfg, world=8)
    opt_cfg = AdamWConfig(lr=warmup_cosine(3e-3, 10, 10000),
                          moments_dtype=pol.moments_dtype)
    ts = trainer_lib.build_train_step(model, mesh, opt_cfg,
                                      global_batch=16)
    params, opt = init_state(model, mesh, opt_cfg, jax.random.PRNGKey(0))
    losses = []
    for i in range(STEPS):
        b = place_batch(make_batch(arch, lm, i, 16), mesh, ts.in_specs[2])
        params, opt, m = ts.fn(params, opt, b)
        losses.append(float(m["loss"]))
    out[name] = losses
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""


_ELASTIC_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import tempfile
from repro.testing.faults import StepFaults
from repro.train.elastic import ElasticConfig, Supervisor

STEPS = int(os.environ.get("CONV_STEPS", "24"))
DIE_AT = STEPS // 2
oracle = Supervisor(ElasticConfig(steps=STEPS, log=False)).run_supervised()
d = tempfile.mkdtemp(prefix="conv_elastic_")
hit = Supervisor(ElasticConfig(steps=STEPS, ckpt_dir=d, ckpt_every=4,
                               log=False),
                 faults=StepFaults({DIE_AT: "die"})).run_supervised()
out = {"die_at": DIE_AT, "restarts": hit["restarts"],
       "writer_stats": hit["writer_stats"],
       "oracle": [oracle["losses"][i] for i in range(STEPS)],
       "interrupted": [hit["losses"][i] for i in range(STEPS)]}
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""


def _run_snippet(snippet: str, steps: int):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["CONV_STEPS"] = str(steps)
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=3600)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"convergence run failed:\n{r.stdout}\n{r.stderr}")


def run(steps: int = 40):
    return _run_snippet(_SNIPPET, steps)


def run_elastic(steps: int = 24):
    """Interrupted-vs-uninterrupted: a worker death mid-run (resume from
    the latest async checkpoint) must not perturb convergence AT ALL —
    fp32 state + deterministic data makes the replayed curve bit-exact."""
    return _run_snippet(_ELASTIC_SNIPPET, steps)


def main_elastic(steps: int = 24):
    out = run_elastic(steps)
    print(f"# device: {out['device']}")
    o, h = out["oracle"], out["interrupted"]
    print(f"# elastic: worker death at step {out['die_at']} "
          f"(restarts={out['restarts']}) vs uninterrupted")
    print("step,uninterrupted,interrupted")
    for i in range(0, len(o), max(1, len(o) // 8)):
        print(f"{i},{o[i]!r},{h[i]!r}")
    diff = max(abs(a - b) for a, b in zip(o, h))
    print(f"max_abs_loss_diff,{diff!r}")
    print(f"bit_exact,{diff == 0.0}")
    ws = out["writer_stats"]
    print(f"async_writes,{ws['completed']} "
          f"steps_overlapped,{ws['steps_overlapped']}")
    return out


def main(steps: int = 40):
    out = run(steps)
    print(f"# device: {out['device']}")
    b = out["baseline"]
    z = out["zeropp"]
    n = out["zeropp_nonblocked"]
    print("# Fig 14 / Table 5 analogue (reduced GPT, synthetic LM)")
    print("step,baseline,zeropp,zeropp_nonblocked")
    for i in range(0, len(b), max(1, len(b) // 10)):
        print(f"{i},{b[i]:.4f},{z[i]:.4f},{n[i]:.4f}")
    print(f"final,{b[-1]:.4f},{z[-1]:.4f},{n[-1]:.4f}")
    gap = abs(z[-1] - b[-1]) / b[-1]
    print(f"zeropp_final_gap,{gap*100:.2f}%")
    print(f"nonblocked_final_gap,{(n[-1]-b[-1])/b[-1]*100:.2f}%")
    print(f"entropy_bound,{out['entropy_bound']:.4f}")
    return out


if __name__ == "__main__":
    if "--elastic" in sys.argv:
        main_elastic()
    else:
        main()
