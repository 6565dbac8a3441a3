"""Pallas TPU kernel: fused dequantize -> fp32 reduce -> (re)quantize.

This is the qgZ inner operator (paper §4.2): after each all-to-all hop,
every device holds N quantized contributions to its gradient slice; they
must be dequantized, summed in full precision, and (for the intra-node hop)
re-quantized for the next hop.  Running those as three separate ops costs
3x reads + 2x writes of the fp32 intermediate; fusing them into one kernel
touches HBM once per input byte and once per output byte — the fusion the
paper credits with "reduc[ing] total memory traffic by 9x".

Tiling: each contribution's length-C row is viewed as the same (rows, W)
slab the quant kernels use (quant_block.slab, sized for N of them per
step), and the grid walks its row tiles.  The contribution dim N (= GPUs
per node in the paper, mesh axis size here, <= 32) is the whole leading
block dim, so the reduction is a single VMEM-resident ``sum`` over it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import QuantConfig
from repro.kernels.quant_block import (_quant_body, _row_spec, _unpack4,
                                      slab)

Array = jax.Array


def _dequant_sum(p_ref, s_ref, block: int, pack: bool):
    """(N, rt, pw) payload + (N, rt, nbt) scales -> (rt, W) fp32 sum."""
    p = p_ref[...]
    if pack:
        p = jnp.stack([_unpack4(p[n]) for n in range(p.shape[0])])
    n, rt, w = p.shape
    deq = p.reshape(n, rt, w // block, block).astype(jnp.float32) \
        * s_ref[...][..., None]
    return jnp.sum(deq, axis=0).reshape(rt, w)  # fp32 reduce (§3.3)


def _reduce_kernel(p_ref, s_ref, out_ref, *, block, pack, out_dtype):
    out_ref[...] = _dequant_sum(p_ref, s_ref, block, pack).astype(out_dtype)


def _reduce_requant_kernel(p_ref, s_ref, *refs, block, pack_in, qmax_out,
                           pack_out):
    """With a (rt, W) tile of pre-drawn uniforms before the outputs, the
    requantization rounds stochastically (core.quant.stochastic_uniform),
    exactly like the standalone SR quant kernel — the PRNG stays outside
    the kernel so pallas/interpret/xla round identically per element."""
    *u_ref, out_p_ref, out_s_ref = refs
    acc = _dequant_sum(p_ref, s_ref, block, pack_in)
    u = u_ref[0][...] if u_ref else None
    q, s = _quant_body(acc, block, qmax_out, pack_out, u=u)
    out_p_ref[...] = q
    out_s_ref[...] = s


def _contrib_specs(n: int, rt: int, pw: int, nbt: int):
    return [pl.BlockSpec((n, rt, pw), lambda i: (0, i, 0)),
            pl.BlockSpec((n, rt, nbt), lambda i: (0, i, 0))]


def dequant_reduce_pallas(payload: Array, scales: Array, cfg: QuantConfig,
                          out_dtype=jnp.float32,
                          interpret: bool = False) -> Array:
    """Dequantize N contributions and sum: (N, P), (N, NB) -> (C,) fp32.

    Used for the final hop of qgZ (no requantization afterwards).
    """
    N, P = payload.shape
    pack = cfg.bits == 4
    C = P * 2 if pack else P
    block = cfg.block_size
    rows, w, rt = slab(C, block, depth=N)
    pw = w // 2 if pack else w
    kernel = functools.partial(_reduce_kernel, block=block, pack=pack,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rt),),
        in_specs=_contrib_specs(N, rt, pw, w // block),
        out_specs=_row_spec(rt, w),
        out_shape=jax.ShapeDtypeStruct((rows, w), out_dtype),
        interpret=interpret,
    )(payload.reshape(N, rows, pw), scales.reshape(N, rows, w // block))
    return out.reshape(C)


def dequant_reduce_quant_pallas(
    payload: Array, scales: Array,
    cfg_in: QuantConfig, cfg_out: QuantConfig,
    u: Optional[Array] = None,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """qgZ intra-hop fusion: (N, P), (N, NB) -> requantized (P'), (NB).

    ``cfg_in`` describes the incoming payload, ``cfg_out`` the outgoing
    (they share block_size; bits may differ, e.g. INT4 -> INT4).  ``u``
    is an optional (C,) uniform field for stochastic requantization,
    drawn OUTSIDE the kernel with the reference's segmentation
    (core.quant.stochastic_uniform) so every backend rounds bit-
    identically.
    """
    assert cfg_in.block_size == cfg_out.block_size
    N, P = payload.shape
    pack_in = cfg_in.bits == 4
    pack_out = cfg_out.bits == 4
    C = P * 2 if pack_in else P
    block = cfg_in.block_size
    rows, w, rt = slab(C, block, depth=N)
    pw_in = w // 2 if pack_in else w
    pw_out = w // 2 if pack_out else w
    in_specs = _contrib_specs(N, rt, pw_in, w // block)
    operands = [payload.reshape(N, rows, pw_in),
                scales.reshape(N, rows, w // block)]
    if u is not None:
        assert u.shape == (C,), (u.shape, C)
        in_specs.append(_row_spec(rt, w))
        operands.append(u.reshape(rows, w))
    kernel = functools.partial(_reduce_requant_kernel, block=block,
                               pack_in=pack_in, qmax_out=cfg_out.qmax,
                               pack_out=pack_out)
    out_p, out_s = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rt),),
        in_specs=in_specs,
        out_specs=[_row_spec(rt, pw_out), _row_spec(rt, w // block)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, pw_out), jnp.int8),
            jax.ShapeDtypeStruct((rows, w // block), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out_p.reshape(-1), out_s.reshape(-1)
