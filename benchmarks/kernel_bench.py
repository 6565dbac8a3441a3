"""Paper Table 3: impact of optimized / fused quantization kernels.

Measures, PER KERNEL BACKEND (kernels/ops.py: xla reference, interpret =
real Pallas kernel bodies through the interpreter, pallas when on TPU):

  * fused_reduce_quant — the qgZ inner op (paper §4.2 "reduces total
    memory traffic by 9x").  STAGED = three separate jit calls through the
    backend's unfused ops (dequant materializing fp32, reduce, requant),
    the PyTorch-op-sequence analogue; FUSED = the single
    ops.dequant_reduce_quant call.
  * dequant_gemm — the serving head.  STAGED = dequantize the whole INT8
    weight matrix to bf16 then einsum; FUSED = ops.dequant_matmul (scales
    applied per weight tile in VMEM, no bf16 weight matrix in HBM).
  * quantize_int8_gbps — blocked quant throughput of a big weight tensor.

Plus backend-independent ANALYTIC HBM-traffic ratios for both fusions.
Emits one BENCH json line (snapshot: benchmarks/snapshots/BENCH_kernels.json).
"""
from __future__ import annotations

import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant import QuantConfig, quantize_blockwise
from repro.kernels import ops, platform


def traffic_ratio(n_contrib: int, n_elems: int, bits: int, block: int):
    """qgZ inner op, bytes touched: staged (fp32 materialized between every
    stage) vs fused (inputs + outputs only)."""
    pay = n_contrib * (n_elems // (8 // bits))
    scales = n_contrib * (n_elems // block) * 4
    f32 = n_contrib * n_elems * 4
    out_pay = n_elems // (8 // bits)
    out_scales = (n_elems // block) * 4
    staged = (pay + scales + f32) + (f32 + n_elems * 4) \
        + (n_elems * 4 + out_pay + out_scales)
    fused = pay + scales + out_pay + out_scales
    return staged, fused, staged / fused


def gemm_traffic_ratio(T: int, N: int, K: int, block: int):
    """Serving head, bytes touched: staged (INT8 in, bf16 weight matrix
    written then re-read by the GEMM) vs fused (INT8 straight to the MXU).
    Activations/outputs are identical on both sides and included."""
    pay = N * K                       # int8
    scales = (N * K // block) * 4
    x = T * K * 4
    out = T * N * 4
    w_bf16 = N * K * 2
    staged = (pay + scales + w_bf16) + (w_bf16 + x + out)
    fused = pay + scales + x + out
    return staged, fused, staged / fused


def _time(fn, *args, reps=10):
    jax.block_until_ready(fn(*args))  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _bench_backend(p, s, cfg, x_act, w_pay, w_scales, wq, reps):
    """All three measurements under the CURRENTLY forced backend."""
    out = {}

    # qgZ inner fusion: staged = the backend's own unfused ops, 3 jits
    stage_deq = jax.jit(lambda p, s: ops.dequantize_blockwise(p, s, cfg))
    stage_red = jax.jit(lambda d: jnp.sum(d, axis=0))
    stage_q = jax.jit(lambda a: ops.quantize_blockwise(a, cfg))
    fused = jax.jit(lambda p, s: ops.dequant_reduce_quant(p, s, cfg, cfg))

    def staged(p, s):
        return stage_q(stage_red(stage_deq(p, s)))

    t_staged = _time(staged, p, s, reps=reps)
    t_fused = _time(fused, p, s, reps=reps)
    out["fused_reduce_quant"] = {
        "staged_us": t_staged * 1e6, "fused_us": t_fused * 1e6,
        "speedup": t_staged / t_fused}

    # serving head GEMM: staged = whole-matrix dequant + einsum, 2 jits
    kb = w_pay.size // w_scales.size
    gcfg = QuantConfig(bits=8, block_size=kb)
    g_deq = jax.jit(lambda p, s: ops.dequantize_blockwise(p, s, gcfg,
                                                          jnp.bfloat16))
    g_mm = jax.jit(lambda x, w: jnp.einsum(
        "tk,nk->tn", x, w, preferred_element_type=jnp.float32))
    g_fused = jax.jit(lambda x, p, s: ops.dequant_matmul(x, p, s))

    def g_staged(x, p, s):
        return g_mm(x, g_deq(p, s))

    t_gs = _time(g_staged, x_act, w_pay, w_scales, reps=reps)
    t_gf = _time(g_fused, x_act, w_pay, w_scales, reps=reps)
    out["dequant_gemm"] = {
        "staged_us": t_gs * 1e6, "fused_us": t_gf * 1e6,
        "speedup": t_gs / t_gf}

    # blocked-quant throughput
    qf = jax.jit(lambda w: ops.quantize_blockwise(
        w, QuantConfig(bits=8, block_size=256)))
    t_q = _time(qf, wq, reps=reps)
    out["quantize_int8_gbps"] = wq.size * 4 / t_q / 1e9
    return out


def main(smoke: bool = False):
    cfg = QuantConfig(bits=4, block_size=256)
    N, C = (4, 1 << 16) if smoke else (8, 1 << 20)
    T, NR, K = (16, 256, 1024) if smoke else (64, 2048, 4096)
    gemm_block = 256
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, C)).astype(np.float32))
    p, s = quantize_blockwise(x, cfg)
    x_act = jnp.asarray(rng.standard_normal((T, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((NR, K)).astype(np.float32))
    w_pay, w_scales = quantize_blockwise(
        w, QuantConfig(bits=8, block_size=gemm_block))
    wq = jnp.asarray(rng.standard_normal(
        (1, 1 << (18 if smoke else 22))).astype(np.float32))
    reps = 3 if smoke else 10

    backends = ["xla", "interpret"]
    if platform.is_tpu():
        backends.append("pallas")
    per_backend = {}
    for be in backends:
        with ops.use_backend(be):
            per_backend[be] = _bench_backend(p, s, cfg, x_act, w_pay,
                                             w_scales, wq, reps)

    st, fu, ratio = traffic_ratio(N, C, 4, 256)
    gst, gfu, gratio = gemm_traffic_ratio(T, NR, K, gemm_block)
    traffic = {
        "fused_reduce_quant": {"staged_bytes": st, "fused_bytes": fu,
                               "ratio": ratio},
        "dequant_gemm": {"staged_bytes": gst, "fused_bytes": gfu,
                         "ratio": gratio},
    }

    print("# Table 3 analogue: fused vs staged quantized hot-path kernels")
    print("backend,op,staged_us,fused_us,speedup")
    for be, r in per_backend.items():
        for op_name in ("fused_reduce_quant", "dequant_gemm"):
            d = r[op_name]
            print(f"{be},{op_name},{d['staged_us']:.0f},{d['fused_us']:.0f},"
                  f"{d['speedup']:.2f}x")
        print(f"{be},quantize_int8_gbps,{r['quantize_int8_gbps']:.1f}")
    print(f"analytic_traffic_ratio,fused_reduce_quant,{ratio:.1f}x")
    print(f"analytic_traffic_ratio,dequant_gemm,{gratio:.2f}x")

    res = {"backends": per_backend, "traffic": traffic,
           "shapes": {"reduce_quant": [N, C], "gemm": [T, NR, K],
                      "smoke": smoke}}
    print("BENCH " + json.dumps({"kernels": res}))
    return res


if __name__ == "__main__":
    import sys
    main(smoke="--smoke" in sys.argv)
