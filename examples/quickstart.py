"""Quickstart: ZeRO++ training in ~40 lines.

Run (8 simulated devices on CPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.data.synthetic import SyntheticLM, make_batch
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.train import trainer
from repro.train.policy import make_policy
from repro.launch.mesh import make_mesh


def main():
    # 1. mesh: 'data' = slow tier, 'model' = fast tier (paper's intra-node)
    mesh = make_mesh((4, 2), ("data", "model"))

    # 2. architecture + ZeRO++ policy (qwZ INT8 + hpZ + qgZ INT4 by default)
    arch = get_config("gpt-350m").reduced()
    pol = make_policy(arch, mesh.axis_names)       # variant="zeropp"
    model = Model(arch, pol.zcfg, world=8)
    print(f"model: {model.n_params()/1e6:.1f}M params | "
          f"qwZ={pol.zcfg.qwz} hpZ={pol.zcfg.hpz} qgZ={pol.zcfg.qgz}")

    # 3. distributed train step (one shard_map over the mesh)
    opt_cfg = AdamWConfig(lr=3e-3, moments_dtype=pol.moments_dtype)
    step = trainer.build_train_step(model, mesh, opt_cfg, global_batch=16)
    params, opt = trainer.init_state(model, mesh, opt_cfg,
                                     jax.random.PRNGKey(0))

    # 4. deterministic synthetic LM data, train a few steps
    lm = SyntheticLM(vocab=arch.vocab, seq_len=64, seed=0)
    for i in range(10):
        batch = trainer.place_batch(make_batch(arch, lm, i, 16), mesh,
                                    step.in_specs[2])
        params, opt, metrics = step.fn(params, opt, batch)
        print(f"step {i}: loss {float(metrics['loss']):.4f} "
              f"gnorm {float(metrics['grad_norm']):.3f}")
    print(f"(best achievable loss = data entropy bound "
          f"{lm.entropy_bound:.3f})")


if __name__ == "__main__":
    main()
