"""Pallas TPU kernels: blockwise quantize / dequantize (+ fused reorder).

These are the TPU adaptation of the paper's custom CUDA quantization library
(§4.2): the CUDA version chases vectorized 16B global-memory transactions
and register-file blocking; the TPU version streams VMEM tiles shaped for
the VPU, so each ``pallas_call`` instance reads one HBM tile exactly once.

Layout contract (shared with core.quant): the quantization block is a run of
``block_size`` *contiguous trailing* elements, so any buffer whose trailing
dim is a multiple of the block is, flattened, a sequence of whole blocks.
Every kernel views its operand as a 2-D *slab* ``(rows, W)`` (:func:`slab`)
with ``W`` a multiple of the block — blocks never cross rows, so scales
never cross tiles — and tiles it ``(row_tile, W)``.  That satisfies the
Mosaic tiling rule (block's last two dims divisible by (8, 128) or equal to
the array's): the column block is always the full width ``W`` (so the
``W // block``-wide scales block and the ``W // 2``-wide int4 payload block
are full-width too) and the row tile is a multiple of 32 (int8's sublane
packing) or all rows.  A row count the tile does not divide leaves a
partial last tile; rows are independent, so its out-of-range rows are
never written.

The fused reorder+quant kernel implements the paper's "tensor slice
reordering ... realized within a fused quantization and remapping kernel":
the (Y, X, L) -> (X, Y, L) transpose of qgZ is folded into the input
``BlockSpec.index_map``, so reordering costs zero extra memory traffic —
the Pallas analogue of fusing the remap into the quant kernel's loads.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.quant import QuantConfig

Array = jax.Array

_LANE = 128
_SUBLANE_INT8 = 32      # int8 packs 32 rows per (32, 128) native tile
_PACK_GROUP = 2 * _LANE  # int4 pairs are regrouped within 256-lane groups
_TILE_ELEMS = 1 << 17   # elements per grid step (caps the VMEM working set)
_MAX_WIDTH = 4096


def _divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap."""
    best = 1
    for d in range(1, int(n ** 0.5) + 1):
        if n % d == 0:
            for c in (d, n // d):
                if c <= cap and c > best:
                    best = c
    return best


def slab(n: int, block: int, depth: int = 1) -> Tuple[int, int, int]:
    """``(rows, W, row_tile)``: the 2-D view of a flat run of ``n`` elements.

    ``W`` divides ``n`` and is a multiple of ``block`` — and of the 256-lane
    int4 pack group when ``n`` allows, which real buffers always do
    (``ZeroConfig.align`` pads them to ``world * block``).  ``depth`` is the
    number of such slabs one grid step reads (the N contributions of the
    fused reduce), so ``depth * row_tile * W`` stays near ``_TILE_ELEMS``.
    ``row_tile`` is a multiple of 32, or every row when there are fewer.
    """
    unit = math.lcm(block, _PACK_GROUP)
    if n % unit:
        unit = block
    cap = max(unit, min(_MAX_WIDTH, _TILE_ELEMS // (_SUBLANE_INT8 * depth)))
    w = unit * _divisor_at_most(n // unit, cap // unit)
    rows = n // w
    rt = max(_SUBLANE_INT8,
             _TILE_ELEMS // (w * depth) // _SUBLANE_INT8 * _SUBLANE_INT8)
    return rows, w, min(rt, rows)


def _pair_perm(g: int) -> Array:
    """(g, g) 0/1 matrix whose column j < g/2 selects lane 2j and column
    g/2 + j selects lane 2j + 1: ``q @ perm`` moves the even lanes of each
    g-lane group to its first half and the odd lanes to its second half.
    Built in-kernel from iotas; exact on the MXU for int4 values."""
    h = g // 2
    i = lax.broadcasted_iota(jnp.int32, (g, g), 0)
    j = lax.broadcasted_iota(jnp.int32, (g, g), 1)
    src = 2 * jnp.where(j < h, j, j - h) + (j >= h).astype(jnp.int32)
    return (i == src).astype(jnp.bfloat16)


def _pack4(q: Array) -> Array:
    """(r, W) int values in [-8, 7] -> (r, W/2) int8, nibbles of adjacent
    elements (2j low, 2j+1 high) — core.quant.pack_int4's wire format.
    The even/odd lane split is a permutation matmul over g-lane groups
    (lane-strided slicing does not lower on TPU); g = 256 keeps both
    halves 128-lane aligned."""
    r, w = q.shape
    g = math.gcd(w, _PACK_GROUP)
    y = jnp.dot(q.reshape(r * w // g, g).astype(jnp.bfloat16), _pair_perm(g),
                preferred_element_type=jnp.float32).astype(jnp.int32)
    lo, hi = y[:, : g // 2], y[:, g // 2:]
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.int8).reshape(r, w // 2)


def _unpack4(p: Array) -> Array:
    """Inverse of :func:`_pack4`: (r, P) int8 -> (r, 2P) int32 in [-8, 7]."""
    r, pw = p.shape
    g = math.gcd(2 * pw, _PACK_GROUP)
    pi = p.astype(jnp.int32).reshape(r * pw * 2 // g, g // 2)
    lo = (pi << 28) >> 28            # arithmetic shifts sign-extend nibbles
    hi = (pi << 24) >> 28
    z = jnp.concatenate([lo, hi], axis=-1).astype(jnp.bfloat16)
    y = lax.dot_general(z, _pair_perm(g), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return y.astype(jnp.int32).reshape(r, 2 * pw)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

def _quant_body(x, block: int, qmax: float, pack: bool, u=None):
    """Shared math: (rt, W) float tile -> (payload, scales).

    ``u`` (optional, same tile shape as ``x``) is a pre-drawn uniform field
    for stochastic rounding: ``q = floor(s) + (u < s - floor(s))`` — the
    exact comparison core.quant._round performs, so a field produced by
    core.quant.stochastic_uniform rounds bit-identically to the reference.
    """
    rt, w = x.shape
    nb = w // block
    xb = x.astype(jnp.float32).reshape(rt, nb, block)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    scaled = xb * inv
    if u is None:
        q = jnp.round(scaled)
    else:
        ub = u.astype(jnp.float32).reshape(rt, nb, block)
        lo = jnp.floor(scaled)
        q = lo + (ub < scaled - lo).astype(jnp.float32)
    q = jnp.clip(q, -qmax, qmax).reshape(rt, w)
    q = _pack4(q.astype(jnp.int32)) if pack else q.astype(jnp.int8)
    return q, scale.reshape(rt, nb)


def _quant_kernel(*refs, block, qmax, pack):
    *in_refs, payload_ref, scale_ref = refs
    u = in_refs[1][...] if len(in_refs) == 2 else None
    q, s = _quant_body(in_refs[0][...], block, qmax, pack, u=u)
    payload_ref[...] = q
    scale_ref[...] = s


def _row_spec(rt: int, w: int) -> pl.BlockSpec:
    return pl.BlockSpec((rt, w), lambda i: (i, 0))


def quantize_pallas(x: Array, cfg: QuantConfig,
                    u: Array = None,
                    interpret: bool = False) -> Tuple[Array, Array]:
    """Blockwise quantize the trailing dim of a 2-D array.

    x: (R, C) float, C % cfg.block_size == 0.
    u: optional (R, C) float32 uniform field -> stochastic rounding (same
       layout as x; see core.quant.stochastic_uniform).
    Returns (payload int8 (R, C or C//2), scales f32 (R, C//block)).
    """
    R, C = x.shape
    block = cfg.block_size
    assert C % block == 0, (C, block)
    pack = cfg.bits == 4
    rows, w, rt = slab(R * C, block)
    pw = w // 2 if pack else w
    operands = [x.reshape(rows, w)]
    if u is not None:
        assert u.shape == x.shape, (u.shape, x.shape)
        operands.append(u.reshape(rows, w))
    kernel = functools.partial(_quant_kernel, block=block, qmax=cfg.qmax,
                               pack=pack)
    p, s = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rt),),
        in_specs=[_row_spec(rt, w)] * len(operands),
        out_specs=[_row_spec(rt, pw), _row_spec(rt, w // block)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, pw), jnp.int8),
            jax.ShapeDtypeStruct((rows, w // block), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return p.reshape(R, -1), s.reshape(R, -1)


# ---------------------------------------------------------------------------
# dequantize
# ---------------------------------------------------------------------------

def _dequant_body(p, s, block: int, pack: bool, out_dtype):
    q = _unpack4(p) if pack else p
    rt, w = q.shape
    x = q.reshape(rt, w // block, block).astype(jnp.float32) * s[..., None]
    return x.reshape(rt, w).astype(out_dtype)


def _dequant_kernel(p_ref, s_ref, out_ref, *, block, pack, out_dtype):
    out_ref[...] = _dequant_body(p_ref[...], s_ref[...], block, pack, out_dtype)


def dequantize_pallas(payload: Array, scales: Array, cfg: QuantConfig,
                      out_dtype=jnp.float32,
                      interpret: bool = False) -> Array:
    """Inverse of :func:`quantize_pallas`.  payload: (R, P); scales (R, NB)."""
    R, P = payload.shape
    pack = cfg.bits == 4
    C = P * 2 if pack else P
    block = cfg.block_size
    rows, w, rt = slab(R * C, block)
    pw = w // 2 if pack else w
    kernel = functools.partial(_dequant_kernel, block=block, pack=pack,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, rt),),
        in_specs=[_row_spec(rt, pw), _row_spec(rt, w // block)],
        out_specs=_row_spec(rt, w),
        out_shape=jax.ShapeDtypeStruct((rows, w), out_dtype),
        interpret=interpret,
    )(payload.reshape(rows, pw), scales.reshape(rows, w // block))
    return out.reshape(R, C)


# ---------------------------------------------------------------------------
# fused reorder (transpose) + quantize — qgZ step 1 (§3.3.3 + §4.2)
# ---------------------------------------------------------------------------

def quantize_reordered_pallas(x: Array, cfg: QuantConfig,
                              u: Array = None,
                              interpret: bool = False) -> Tuple[Array, Array]:
    """Transpose (Y, X, L) -> (X, Y, L) and quantize trailing dim, fused.

    Each (y, x) slice is viewed as an (Lr, W) slab.  The transpose is
    expressed purely in the input ``index_map`` — grid step (i, j, k)
    reads slab tile k of slice (y=j, x=i) and writes it to (i, j), so the
    reorder rides along with the quantization loads (no separate
    transpose pass).

    ``u`` (optional, stochastic rounding) is the uniform field drawn on the
    already-transposed shape (X, Y, L) — the layout the reference draws on
    after its ``swapaxes`` — so its BlockSpec is the identity (output-side)
    index_map, not the transposing one.
    """
    Y, X, L = x.shape
    block = cfg.block_size
    assert L % block == 0
    pack = cfg.bits == 4
    rows, w, rt = slab(L, block)
    pw = w // 2 if pack else w
    sq = pl.Squeezed()

    def spec(width, index_map):
        return pl.BlockSpec((sq, sq, rt, width), index_map)

    def out_map(i, j, k):
        return (i, j, k, 0)

    in_specs = [spec(w, lambda i, j, k: (j, i, k, 0))]
    operands = [x.reshape(Y, X, rows, w)]
    if u is not None:
        assert u.shape == (X, Y, L), (u.shape, (X, Y, L))
        in_specs.append(spec(w, out_map))
        operands.append(u.reshape(X, Y, rows, w))
    kernel = functools.partial(_quant_kernel, block=block, qmax=cfg.qmax,
                               pack=pack)
    p, s = pl.pallas_call(
        kernel,
        grid=(X, Y, pl.cdiv(rows, rt)),
        in_specs=in_specs,
        out_specs=[spec(pw, out_map), spec(w // block, out_map)],
        out_shape=[
            jax.ShapeDtypeStruct((X, Y, rows, pw), jnp.int8),
            jax.ShapeDtypeStruct((X, Y, rows, w // block), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return p.reshape(X, Y, -1), s.reshape(X, Y, -1)
