"""Pallas TPU kernel: fused INT8-weight x activation GEMM.

The serving decode head consumes qwZ-gathered INT8 weights.  The staged
path dequantizes the whole (N, K) weight matrix to bf16 in HBM and then
runs the GEMM — 2 B/elem written + 2 B/elem re-read that exist only to
feed the MXU.  This kernel applies the blockwise scales per weight tile
instead: INT8 rows stream from HBM once at 1 B/elem, are dequantized
in VMEM per (bn, K) tile, and hit the MXU directly.  HBM weight traffic
drops 4x -> 1x bytes (see benchmarks/kernel_bench.py for the analytic
ratio); the bf16 weight matrix never exists.

Numerics: dequantized tiles round through ``compute_dtype`` (bf16) before
the dot — the exact elementwise math of the staged
``dequantize_blockwise(..., bf16)`` + einsum — and products accumulate in
fp32 (``preferred_element_type``).  The only divergence from the staged
einsum is fp32 summation ORDER, so parity tests against
:func:`repro.kernels.ref.dequant_matmul_ref` use a tight allclose (~1 ulp
of the fp32 partial sums), not bit-equality; the ``xla`` backend in
kernels/ops.py IS the staged math and stays bit-identical.

Tiling: a grid step takes a (bt, K) activation tile and a (bn, K) weight
tile with its (bn, NB) scales — the whole contraction dim, so every scale
group stays inside the tile (the layout contract of core.quant /
quant_block.py) and the scales block is full-width, as the TPU tiling rule
requires of a dim narrower than 128 lanes.  ``bt`` and ``bn`` are aligned
tiles (multiples of 8/16 and 128) or the whole dim; a dim they do not
divide leaves a partial last tile whose out-of-range rows/columns are
never written (rows of x and of the weight are independent outputs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_MAX_ROWS = 256          # token tile
_TILE_BYTES = 1 << 21    # fp32 dequantized weight tile (VMEM working set)


def _gemm_kernel(x_ref, w_ref, s_ref, out_ref, *, kb, compute_dtype):
    w = w_ref[...]                                   # (bn, K) int8
    s = s_ref[...]                                   # (bn, NB) f32
    bn, k = w.shape
    wf = (w.reshape(bn, k // kb, kb).astype(jnp.float32)
          * s[..., None]).reshape(bn, k).astype(compute_dtype)
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], wf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def dequant_matmul_pallas(x: Array, payload: Array, scales: Array,
                          compute_dtype=jnp.bfloat16,
                          out_dtype=jnp.float32,
                          interpret: bool = False) -> Array:
    """``x @ dequant(payload).T`` with the scales applied per weight tile.

    x: (T, K) activations; payload: (N, K) int8; scales: (N, NB) f32 with
    K % NB == 0.  Returns (T, N) ``out_dtype``.
    """
    T, K = x.shape
    N, Kw = payload.shape
    assert Kw == K, (Kw, K)
    nb = scales.shape[-1]
    assert scales.shape == (N, nb) and K % nb == 0, (scales.shape, K)

    bt = min(T, _MAX_ROWS)
    bn = min(N, max(128, _TILE_BYTES // (4 * K) // 128 * 128))
    kernel = functools.partial(_gemm_kernel, kb=K // nb,
                               compute_dtype=compute_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(T, bt), pl.cdiv(N, bn)),
        in_specs=[
            pl.BlockSpec((bt, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, K), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, nb), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        interpret=interpret,
    )(x, payload, scales)
    return out if out_dtype == jnp.float32 else out.astype(out_dtype)
