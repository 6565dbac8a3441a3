"""Serving example: the continuous-batching engine (repro.serve).

Three requests with DIFFERENT prompt lengths run through one engine: they
are admitted into KV-pool slots, prefilled individually (prompt-length
buckets bound the compiled prefill shapes), and decoded TOGETHER by one
jitted decode step with a per-sequence ``cache_pos`` vector.  Tokens
stream per request as they are sampled.  Parameters stay ZeRO-sharded
(flat buffers over the whole mesh); every layer group is gathered per
step with qwZ INT8 — the serving analogue of the paper's forward path.

With --from-ckpt, parameters are written through the ZeroState per-shard
INT8 checkpoint format and the engine boots from it via the bf16 serving
load path (ServeEngine.from_checkpoint) — the deployment flow for a
trained model.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python examples/serve_decode.py --arch qwen3-0.6b \
      --temperature 0.8 --top-k 40 --top-p 0.95 --max-new-tokens 12
"""
import argparse
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.models.model import Model
from repro.serve import ServeEngine
from repro.train.policy import make_policy
from repro.train.state import ZeroState, param_specs
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--prompt-lens", default="5,12,9",
                    help="comma-separated prompt lengths (mixed in one run)")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-len", type=int, default=64,
                    help="KV pool capacity per slot")
    ap.add_argument("--slots", type=int, default=2,
                    help="decode batch size (fewer slots than requests "
                         "exercises slot recycling)")
    ap.add_argument("--from-ckpt", action="store_true",
                    help="roundtrip params through an INT8 per-shard "
                         "checkpoint and boot the engine from it")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="weight-gather ring depth for the serving path "
                         "(k>1 pays on slow interconnects; clamps to "
                         "n_layers-1; default: the policy's depth)")
    args = ap.parse_args()

    mesh = make_mesh((2, 2), ("data", "model"))
    arch = get_config(args.arch).reduced()
    pol = make_policy(arch, mesh.axis_names)
    model = Model(arch, pol.zcfg, world=4)

    # init + place ZeRO-sharded parameters
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    p_specs = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
              for k, v in params.items()}

    kw = dict(n_slots=args.slots, kv_len=args.kv_len,
              batch_axes=(), kv_axes=("model",), prefetch=args.prefetch)
    if args.from_ckpt:
        d = tempfile.mkdtemp(prefix="zeropp_serve_ckpt_")
        st = ZeroState(model, mesh, opt_cfg=None, params=params,
                       meta={"arch": arch.name})
        path = st.save(d, 0, fmt="int8")
        engine = ServeEngine.from_checkpoint(model, mesh, d, **kw)
        print(f"[serve] engine <- {path} (INT8 per-shard ckpt, bf16 load)")
    else:
        engine = ServeEngine(model, mesh, params, **kw)

    lens = [int(x) for x in args.prompt_lens.split(",")]
    rng = np.random.default_rng(args.seed)
    streams = {}

    def on_token(uid, tok):
        streams[uid].append(tok)
        print(f"  [stream] req {uid}: +{tok}  ({len(streams[uid])} tokens)")

    uids = []
    for i, P in enumerate(lens):
        prompt = rng.integers(0, arch.vocab, P).astype(np.int32)
        uid = engine.submit(prompt, max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed + i,
                            on_token=on_token)
        streams[uid] = []
        uids.append((uid, prompt))
        print(f"req {uid}: prompt_len={P} "
              f"bucket={engine.scheduler.bucket_for(P)}")

    results = engine.run(max_steps=1000)
    print(f"\n{args.slots} slots served {len(lens)} requests "
          f"(slot map: {engine.slot_history})")
    for uid, prompt in uids:
        print(f"req {uid}: prompt={prompt.tolist()} "
              f"generated={results[uid]}")

    st = engine.stats()

    def _ms(d):
        return (f"p50 {d['p50']:.1f}ms / p99 {d['p99']:.1f}ms"
                if d.get("p50") is not None else "n/a")

    tps = st["tok_per_s"]
    print(f"\n[serve] stats: admitted={st['admitted']} "
          f"completed={st['completed']} expired={st['expired']} "
          f"steps={st['steps']} occupancy={st['occupancy']:.2f}")
    print(f"[serve] TTFT {_ms(st['ttft_ms'])}  "
          f"per-token {_ms(st['tok_latency_ms'])}  "
          f"throughput {'n/a' if tps is None else f'{tps:.1f} tok/s'}")


if __name__ == "__main__":
    main()
