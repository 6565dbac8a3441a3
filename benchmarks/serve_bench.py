"""Serving throughput: decode tokens/sec and time-to-first-token vs batch
occupancy, baseline (bf16 gathers) vs qwZ (INT8 gathers) — plus the
paged-pool multi-tenant trace.

The engine's decode step is timed on the simulated 4-device CPU mesh at
several slot occupancies (1, half, full): tokens/sec = occupied slots /
median step wall-clock, so the plot shows how continuous batching
amortizes the per-step weight gathers.  TTFT is one prefill + first-token
sample at the smallest bucket.  CPU wall-clock is NOT accelerator
wall-clock — the comparison across variants and occupancies is the
signal, not the absolute numbers (Table-1 wire volumes + the measured
overlap fraction in throughput_model.py project the hardware picture).

The TRACE section replays a deterministic multi-tenant request trace
(mixed lengths, staged arrivals, two tenants sharing a 16-token system
prefix) against a slab pool and a paged pool holding the SAME number of
KV positions (equal HBM), and reports:

  * peak concurrent sequences each pool admits (the paged pool must hold
    >= 2x — pages admit at page granularity, slots at whole-sequence);
  * prefix-cache hits + chunked-prefill TTFT cold vs warm (the warm
    prefill runs strictly fewer chunks);
  * speculative decoding accepted-tokens-per-verify (self-draft);
  * p50/p99 TTFT and aggregate tok/s over the trace (wall-clock:
    reported, never snapshotted).

The structural fields (peaks, hits, chunk counts, accepted mean — all
deterministic host-side scheduling facts) are committed as
``snapshots/BENCH_serve.json``; ``--smoke`` gates against them and the
invariants above, ``--write-snapshot`` refreshes the file.

Runs in a subprocess with simulated devices (see testing/subproc.py note).
Emits a BENCH json line; ``python benchmarks/serve_bench.py`` prints a
table.
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
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import ServeEngine
from repro.train.policy import make_policy
from repro.train.state import param_specs

N_SLOTS, KV = 8, 64
mesh = make_mesh((2, 2), ("data", "model"))
arch = get_config("qwen3-0.6b").reduced()
out = {}
for variant in ("baseline", "qwz"):
    pol = make_policy(arch, mesh.axis_names, variant)
    model = Model(arch, pol.zcfg, world=4)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    sp = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, sp[k]))
              for k, v in params.items()}
    res = {"occupancy": {}}
    rng = np.random.default_rng(0)

    # TTFT: submit one request, time until its first streamed token
    eng = ServeEngine(model, mesh, params, n_slots=N_SLOTS, kv_len=KV)
    first = []
    eng.submit(rng.integers(0, arch.vocab, 8), max_new_tokens=1,
               on_token=lambda u, t: first.append(time.perf_counter()))
    t0 = time.perf_counter(); eng.step()       # includes prefill compile
    eng.run(max_steps=10)
    t0 = time.perf_counter()
    eng.submit(rng.integers(0, arch.vocab, 8), max_new_tokens=1,
               on_token=lambda u, t: first.append(time.perf_counter()))
    eng.step(); eng.run(max_steps=10)
    res["ttft_s"] = first[-1] - t0             # warm-compile TTFT

    for occ in (1, N_SLOTS // 2, N_SLOTS):
        eng = ServeEngine(model, mesh, params, n_slots=N_SLOTS, kv_len=KV)
        for r in range(occ):
            eng.submit(rng.integers(0, arch.vocab, 8), max_new_tokens=40)
        eng.step()                              # admissions + compile
        times = []
        while not eng.done:
            t = time.perf_counter()
            emitted = eng.step()
            times.append((time.perf_counter() - t, len(emitted)))
            if len(times) >= 24:
                break
        times = times[2:]                       # drop warmup steps
        med = sorted(t for t, _ in times)[len(times) // 2]
        res["occupancy"][occ] = {"step_s": med,
                                 "decode_tok_per_s": occ / med}
    out[variant] = res
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""


_TRACE_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import ServeEngine
from repro.train.policy import make_policy
from repro.train.state import param_specs

KV, PAGE, CHUNK = 64, 8, 8
mesh = make_mesh((2, 2), ("data", "model"))
arch = get_config("qwen3-0.6b").reduced()
pol = make_policy(arch, mesh.axis_names, param_dtype=jnp.float32,
                  compute_dtype=jnp.float32)
model = Model(arch, pol.zcfg, world=4)
params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
sp = param_specs(model, tuple(mesh.axis_names))
params = {k: jax.device_put(v, NamedSharding(mesh, sp[k]))
          for k, v in params.items()}

def paged_engine(**kw):
    return ServeEngine(model, mesh, params, kv_len=KV, pool="paged",
                       page_size=PAGE, chunk_size=CHUNK, **kw)

# deterministic multi-tenant trace: 2 tenants with a 16-token shared
# system prefix, unique suffixes of mixed length, staged arrivals (one
# warm-up request per tenant registers the prefix, then a 14-request
# flood reuses it)
rng = np.random.default_rng(0)
prefixes = [rng.integers(0, arch.vocab, 16).astype(np.int32)
            for _ in range(2)]
def make_req(i):
    suffix = rng.integers(0, arch.vocab, 3 + (i % 5)).astype(np.int32)
    return np.concatenate([prefixes[i % 2], suffix]), 6 + (i % 3)
WARM = [make_req(i) for i in range(2)]
FLOOD = [make_req(i) for i in range(2, 16)]

def run_trace(eng, paged):
    peak, toks = 0, 0
    t0 = time.perf_counter()
    for pr, n in WARM:
        eng.submit(pr, max_new_tokens=n)
    while not eng.done:
        toks += len(eng.step())
    for pr, n in FLOOD:
        eng.submit(pr, max_new_tokens=n)
    while not eng.done:
        toks += len(eng.step())
        conc = eng.n_active + (len(eng._prefilling) if paged else 0)
        peak = max(peak, conc)
    wall = time.perf_counter() - t0
    return peak, toks, wall

out = {}
# equal HBM: 4 slots x 64 positions slab == 32 pages x 8 positions paged
slab = ServeEngine(model, mesh, params, n_slots=4, kv_len=KV)
s_peak, s_toks, s_wall = run_trace(slab, False)
s_stats = slab.stats()
paged = paged_engine(n_slots=16, n_pages=32)
p_peak, p_toks, p_wall = run_trace(paged, True)
p_stats = paged.stats()
pool = p_stats["pool"]
out["equal_hbm"] = {
    "kv_positions": 4 * KV,
    "slab_slots": 4, "paged_pages": 32, "page_size": PAGE,
    "slab_peak_concurrent": s_peak, "paged_peak_concurrent": p_peak,
    "admission_ratio": p_peak / max(1, s_peak),
    "completed": {"slab": s_stats["completed"],
                  "paged": p_stats["completed"]},
    "prefix_hits": pool["prefix_hits"],
    "prefix_tokens_reused": pool["prefix_tokens_reused"],
}
out["wall"] = {   # wall-clock: reported, never snapshotted
    "slab": {"tok_per_s": s_toks / s_wall,
             "ttft_ms": s_stats["ttft_ms"],
             "tok_latency_ms": s_stats["tok_latency_ms"]},
    "paged": {"tok_per_s": p_toks / p_wall,
              "ttft_ms": p_stats["ttft_ms"],
              "tok_latency_ms": p_stats["tok_latency_ms"]},
}

# chunked-prefill TTFT, cold vs warm: the warm resubmission of a 3-chunk
# prompt matches 2 chunks of prefix pages and prefills only the last
eng = paged_engine(n_slots=2)
prompt = np.concatenate([prefixes[0],
                         rng.integers(0, arch.vocab, 8).astype(np.int32)])
t0 = time.perf_counter(); eng.submit(prompt, max_new_tokens=2)
eng.run(max_steps=50)
cold_ms = (time.perf_counter() - t0) * 1e3
cold_chunks = eng.stats()["prefill_chunks"]
t0 = time.perf_counter(); eng.submit(prompt, max_new_tokens=2)
eng.run(max_steps=50)
warm_ms = (time.perf_counter() - t0) * 1e3
warm_chunks = eng.stats()["prefill_chunks"] - cold_chunks
out["prefix_ttft"] = {"cold_chunks": cold_chunks,
                      "warm_chunks": warm_chunks,
                      "hits": eng.stats()["pool"]["prefix_hits"]}
out["wall"]["prefix_ttft_ms"] = {"cold": cold_ms, "warm": warm_ms}

# speculative decoding (self-draft: the drafter sets the stride, the
# acceptance distribution is a deterministic host-side fact)
spec = paged_engine(n_slots=8, draft=(model, params), spec_tokens=4)
for pr, n in FLOOD[:6]:
    spec.submit(pr, max_new_tokens=n)
spec.run(max_steps=200)
acc = spec.stats()["spec_accepted"]
out["spec"] = {"accepted_mean": acc["mean"], "rounds": acc["n"],
               "completed": spec.stats()["completed"]}
from repro.obs.report import device_info
out["device"] = device_info()
print("RESULT " + json.dumps(out))
"""

SNAPSHOT = os.path.join(os.path.dirname(__file__), "snapshots",
                        "BENCH_serve.json")


def _run_snippet(snippet: str) -> Dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"serve bench failed:\n{r.stdout}\n{r.stderr}")
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in:\n{r.stdout}")


def measure() -> Dict:
    return _run_snippet(_SNIPPET)


def measure_trace() -> Dict:
    return _run_snippet(_TRACE_SNIPPET)


def _structural(trace: Dict) -> Dict:
    """The deterministic scheduling facts — everything but wall-clock and
    the device it ran on."""
    return {k: v for k, v in trace.items() if k not in ("wall", "device")}


def _gate(trace: Dict) -> None:
    """Invariants the paged pool must deliver (raise on violation)."""
    eq = trace["equal_hbm"]
    assert eq["admission_ratio"] >= 2.0, (
        f"paged pool admitted only {eq['paged_peak_concurrent']} vs slab "
        f"{eq['slab_peak_concurrent']} at equal HBM")
    assert eq["prefix_hits"] >= 1 and eq["prefix_tokens_reused"] >= 16, eq
    assert eq["completed"]["paged"] == eq["completed"]["slab"] == 16, eq
    pt = trace["prefix_ttft"]
    assert pt["warm_chunks"] < pt["cold_chunks"] and pt["hits"] >= 1, pt
    assert trace["spec"]["accepted_mean"] > 1.0, trace["spec"]


def main(smoke: bool = False, write_snapshot: bool = False):
    out = {}
    if not smoke:
        res = measure()
        out["serve"] = res
    trace = measure_trace()
    out["serve_trace"] = trace
    print("BENCH " + json.dumps(out))

    if not smoke:
        res = dict(out["serve"])
        print(f"# device: {res.pop('device')}")
        print(f"\n{'variant':<10} {'ttft_ms':>9}  " +
              "  ".join(f"occ={o:>2} tok/s" for o in
                        sorted(int(k) for k in res['baseline']['occupancy'])))
        for variant, r in res.items():
            occ = {int(k): v for k, v in r["occupancy"].items()}
            row = "  ".join(f"{occ[o]['decode_tok_per_s']:>12.1f}"
                            for o in sorted(occ))
            print(f"{variant:<10} {r['ttft_s'] * 1e3:>9.1f}  {row}")

    eq = trace["equal_hbm"]
    w = trace["wall"]
    print(f"\n# multi-tenant trace on {trace['device']}")
    print(f"# multi-tenant trace (equal HBM: {eq['kv_positions']} KV "
          f"positions)")
    print(f"peak concurrent: slab={eq['slab_peak_concurrent']} "
          f"paged={eq['paged_peak_concurrent']} "
          f"(x{eq['admission_ratio']:.1f})")
    print(f"prefix cache: {eq['prefix_hits']} hits, "
          f"{eq['prefix_tokens_reused']} tokens reused; "
          f"cold {trace['prefix_ttft']['cold_chunks']} chunks -> warm "
          f"{trace['prefix_ttft']['warm_chunks']}")
    for kind in ("slab", "paged"):
        t = w[kind]["ttft_ms"]
        print(f"{kind:<6} tok/s={w[kind]['tok_per_s']:.1f} "
              f"ttft p50={t['p50']:.0f}ms p99={t['p99']:.0f}ms")
    print(f"speculative: {trace['spec']['accepted_mean']:.2f} accepted/"
          f"verify over {trace['spec']['rounds']} rounds")

    _gate(trace)
    if write_snapshot:
        with open(SNAPSHOT, "w") as fh:
            json.dump(_structural(trace), fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {SNAPSHOT}")
    elif smoke:
        with open(SNAPSHOT) as fh:
            want = json.load(fh)
        got = json.loads(json.dumps(_structural(trace)))
        assert got == want, (
            f"serve trace drifted from {SNAPSHOT}:\n{got}\nvs\n{want}")
        print("snapshot match: structural trace facts unchanged")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="trace only + gates + snapshot comparison")
    ap.add_argument("--write-snapshot", action="store_true",
                    help=f"refresh {SNAPSHOT}")
    a, _ = ap.parse_known_args()
    main(smoke=a.smoke, write_snapshot=a.write_snapshot)
