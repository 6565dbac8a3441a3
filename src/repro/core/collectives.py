"""ZeRO++ collectives, expressed as per-device shard_map code.

These are the three communication primitives of the paper plus their ZeRO-3
baselines.  All functions are written for use *inside* ``jax.shard_map`` and
take mesh axis names explicitly so the same code serves the single-pod
``("data","model")`` and multi-pod ``("pod","data","model")`` meshes.

Axis convention (see DESIGN.md §2): ``intra_axis`` is the fastest
interconnect tier (the paper's intra-node NVLink; our ``'model'`` axis) and
``inter_axes`` the slower tiers (cross-node IB; our ``('pod','data')``).

  * :func:`qwz_all_gather`   — blockwise-INT8-quantized all-gather (qwZ, §3.1)
  * :func:`hpz_all_gather`   — intra-node-only all-gather of the secondary
                               partition (hpZ, §3.2)
  * :func:`qgz_reduce_scatter` — hierarchical 2-hop all-to-all quantized
                               gradient reduce-scatter with tensor-slice
                               reordering (qgZ, §3.3)
  * baselines: plain bf16/fp32 all-gather and psum_scatter (ZeRO-3, Alg. 1)
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.quant import (
    QuantConfig,
    quantize_global,
    dequantize_global,
)
# hot-path quantization goes through the kernel dispatcher: Pallas kernels on
# TPU (incl. the fused reorder+quant and dequant-reduce-quant of paper §4.2),
# bit-identical pure-jnp on CPU.
# Module import (not from-import): kernels.ops also reaches back into
# repro.core lazily, so names must resolve at call time, not import time.
from repro.kernels import ops as _kops
# Telemetry labels: every collective wrapper below runs under a
# ``zero.<op>`` named_scope.  The scope is trace-time only (zero runtime
# cost) but survives into the jaxpr ``name_stack`` — through scan bodies
# and custom_vjp transposition — so launch/jaxpr_analysis.py can attribute
# wire bytes per collective and obs/report.py can gate measured-vs-
# projected comm volume.  Keep these names in sync with
# zeropp.WIRE_LABELS and DESIGN.md §8.
from repro.obs.trace import annotate as _annotate

dequant_reduce = lambda *a, **k: _kops.dequant_reduce(*a, **k)  # noqa: E731
dequant_reduce_quant = lambda *a, **k: _kops.dequant_reduce_quant(*a, **k)  # noqa: E731
dequantize_blockwise = lambda *a, **k: _kops.dequantize_blockwise(*a, **k)  # noqa: E731
quantize_blockwise = lambda *a, **k: _kops.quantize_blockwise(*a, **k)  # noqa: E731
quantize_reordered = lambda *a, **k: _kops.quantize_reordered(*a, **k)  # noqa: E731

Array = jax.Array
Axes = Union[str, Tuple[str, ...]]


def _axes_tuple(axes: Axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axis_size(axes: Axes) -> int:
    n = 1
    for a in _axes_tuple(axes):
        n *= lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Baseline ZeRO-3 collectives (Algorithm 1 of the paper)
# ---------------------------------------------------------------------------

def _pin(x: Array) -> Array:
    """optimization_barrier: stop XLA from hoisting a consumer's dtype
    convert to the producer side of a collective (observed on CPU: bf16
    gathers silently became f32 gathers = 2x wire bytes)."""
    return lax.optimization_barrier(x)


def gather_bf16(x: Array, axes: Axes, axis: int = 0) -> Array:
    """all_gather that provably moves 2-byte lanes.

    bf16 is bitcast to u16 for the gather: the CPU backend legalizes bf16
    collectives to f32 (2x wire bytes — poisons both the dry-run accounting
    and an actual CPU run), and XLA convert-hoisting can do the same on any
    backend.  Bit-level identity; free on TPU.
    """
    if x.dtype != jnp.bfloat16:
        return _pin(lax.all_gather(x, _axes_tuple(axes), axis=axis,
                                   tiled=True))
    u = lax.bitcast_convert_type(x, jnp.uint16)
    g = lax.all_gather(u, _axes_tuple(axes), axis=axis, tiled=True)
    return _pin(lax.bitcast_convert_type(g, jnp.bfloat16))


def baseline_all_gather(shard: Array, axes: Axes, out_dtype=None) -> Array:
    """Full-precision all-gather of a flat parameter shard (ZeRO-3 fwd/bwd)."""
    with _annotate("zero.baseline_gather"):
        full = gather_bf16(shard, axes)
        return full if out_dtype is None else full.astype(out_dtype)


def baseline_reduce_scatter(grad: Array, axes: Axes) -> Array:
    """Full-precision reduce-scatter of a flat local gradient (ZeRO-3)."""
    with _annotate("zero.baseline_reduce"):
        return lax.psum_scatter(grad, _axes_tuple(axes), scatter_dimension=0,
                                tiled=True)


# ---------------------------------------------------------------------------
# qwZ — quantized weight all-gather (§3.1)
# ---------------------------------------------------------------------------

def qwz_all_gather(
    shard: Array,
    axes: Axes,
    cfg: QuantConfig,
    out_dtype=jnp.bfloat16,
    blocked: bool = True,
) -> Array:
    """All-gather a flat weight shard with in-flight blockwise quantization.

    Each device quantizes its own shard once (one kernel, not one per hop),
    gathers the INT8 payload + scales, and dequantizes the concatenation.
    Communication: 0.5·M payload + scales instead of M (bf16), matching the
    paper's 2× reduction.

    ``blocked=False`` uses a single per-shard scale — the paper's Fig. 2 /
    Fig. 14 "non-blocked" ablation that destroys convergence.
    """
    n = shard.shape[0]
    with _annotate("zero.qwz_gather"):
        if blocked:
            if n % cfg.block_size:
                raise ValueError(
                    f"shard len {n} % block {cfg.block_size} != 0")
            payload, scales = quantize_blockwise(shard, cfg)
            payload_g = lax.all_gather(payload, _axes_tuple(axes), tiled=True)
            scales_g = lax.all_gather(scales, _axes_tuple(axes), tiled=True)
            return dequantize_blockwise(payload_g, scales_g, cfg, out_dtype)
        payload, scale = quantize_global(shard, cfg.bits)
        payload_g = lax.all_gather(payload, _axes_tuple(axes), tiled=True)
        scale_g = lax.all_gather(scale[None], _axes_tuple(axes))  # (world,)
        world = axis_size(axes)
        per = payload_g.shape[0] // world
        vals = dequantize_global(
            payload_g.reshape(world, per), scale_g.reshape(world, 1),
            cfg.bits, out_dtype)
        return vals.reshape(-1)


def qwz_all_gather_quant(
    shard: Array,
    axes: Axes,
    cfg: QuantConfig,
) -> Tuple[Array, Array]:
    """qwZ all-gather that STAYS quantized: (payload_g, scales_g).

    Same wire traffic as :func:`qwz_all_gather`, but the trailing dequant is
    omitted so a fused consumer (the serving INT8 dequant-GEMM head,
    kernels/dequant_matmul.py) can apply the scales inside its own tile
    loop — the gathered bf16 weight matrix never materializes in HBM.
    """
    n = shard.shape[0]
    if n % cfg.block_size:
        raise ValueError(f"shard len {n} % block {cfg.block_size} != 0")
    with _annotate("zero.qwz_gather"):
        payload, scales = quantize_blockwise(shard, cfg)
        payload_g = lax.all_gather(payload, _axes_tuple(axes), tiled=True)
        scales_g = lax.all_gather(scales, _axes_tuple(axes), tiled=True)
        return payload_g, scales_g


# ---------------------------------------------------------------------------
# hpZ — hierarchical (secondary) partition all-gather (§3.2)
# ---------------------------------------------------------------------------

def flat_rank(axes: Axes) -> Array:
    """This device's rank within the flattened (row-major) axis group."""
    rank = jnp.int32(0)
    for a in _axes_tuple(axes):
        rank = rank * lax.axis_size(a) + lax.axis_index(a)
    return rank


def hpz_all_gather(secondary_shard: Array, intra_axes: Axes,
                   out_dtype=None) -> Array:
    """Backward all-gather over the *fast-tier* axes only.

    The secondary partition replicates the full weights within each
    ``intra_axes`` group, so this gather moves zero bytes on the slow axes —
    the paper's "M → 0 inter-node" claim.  ``intra_axes`` is normally the
    single ``'model'`` axis (the paper's node), but may span multiple axes
    (e.g. ``('data','model')`` = a whole pod) — the paper's "extended to
    support multiple compute nodes" secondary group.
    """
    with _annotate("zero.hpz_gather"):
        full = gather_bf16(_pin(secondary_shard), intra_axes)
        return full if out_dtype is None else full.astype(out_dtype)


def slice_secondary(full: Array, intra_axes: Axes) -> Array:
    """Re-partition gathered weights into this device's secondary shard.

    Paper §3.2.1: "once the weights are consumed during the forward pass,
    they are partitioned based on the secondary partition".  Slicing the
    already-gathered tensor costs no communication.
    """
    x = axis_size(intra_axes)
    idx = flat_rank(intra_axes)
    sec_len = full.shape[0] // x
    # pin: the slice is saved as a bwd residual — without the barrier XLA
    # may store it pre-converted to the consumer dot's dtype (f32), doubling
    # both the residual memory and the hpZ re-gather bytes
    return _pin(lax.dynamic_slice_in_dim(full, idx * sec_len, sec_len))


# ---------------------------------------------------------------------------
# qgZ — quantized hierarchical all-to-all gradient reduce-scatter (§3.3)
# ---------------------------------------------------------------------------

def _quantize_slices(x: Array, cfg: QuantConfig,
                     key: Optional[Array]) -> Tuple[Array, Array]:
    """Blockwise-quantize the trailing dim of a (..., L) slice stack."""
    return quantize_blockwise(x, cfg, key)


def _pack_scales(payload: Array, scales: Array) -> Array:
    """Append the fp32 block scales to the int8 payload, trailing dim.

    The scales are bitcast to 4 int8 lanes each (lossless) and
    concatenated after the payload so ONE all-to-all moves both — same
    wire bytes as two messages (all_to_all volume is linear in message
    size), one less collective launch per hop.  Inverse:
    :func:`_unpack_scales`.
    """
    sb = lax.bitcast_convert_type(scales, jnp.int8)        # (..., NB, 4)
    sb = sb.reshape(*scales.shape[:-1], scales.shape[-1] * 4)
    return jnp.concatenate([payload, sb], axis=-1)


def _unpack_scales(msg: Array, payload_len: int) -> Tuple[Array, Array]:
    """Split a :func:`_pack_scales` message back into (payload, scales)."""
    payload = msg[..., :payload_len]
    sb = msg[..., payload_len:]
    nb = sb.shape[-1] // 4
    scales = lax.bitcast_convert_type(
        sb.reshape(*sb.shape[:-1], nb, 4), jnp.float32)
    return payload, scales


def qgz_reduce_scatter(
    grad: Array,
    intra_axis: str,
    inter_axes: Axes,
    cfg: QuantConfig,
    out_dtype=jnp.float32,
    key: Optional[Array] = None,
) -> Array:
    """Replacement for gradient reduce-scatter (paper §3.3, Figs. 5-9).

    Per-device algorithm, for a world of Y (inter) × X (intra) devices and a
    flat local gradient of n = world·L elements:

      1. reshape to slices ``(Y, X, L)`` — slice (y, x) is destined for the
         device at inter-coordinate y, intra-coordinate x — and transpose to
         ``(X, Y, L)``.  The transpose *is* the paper's tensor-slice
         reordering Eq. (1)→(2); without it the intra hop would deliver the
         wrong slices (Fig. 8).
      2. blockwise-quantize (INT4 by default) → intra-node all-to-all over
         ``intra_axis`` → dequantize → **reduce in full precision** over the
         X contributions.  Data per device shrinks from M/Z to M/(Z·X).
      3. re-quantize the partial sums → inter-node all-to-all over
         ``inter_axes`` → dequantize → final reduction over the Y node
         contributions.

    Exactly two quantize/dequantize pairs touch any value (vs. `world` pairs
    for a quantized ring), and every reduction runs in fp32 — the paper's
    accuracy-preservation argument.  Cross-slow-link volume is M/Z·(bits/16)
    = 0.25·M for INT4 vs M for bf16 reduce-scatter.

    Returns this device's fully-reduced gradient shard, length L, summed
    (not averaged) over the world.
    """
    inter_axes = _axes_tuple(inter_axes) if inter_axes else ()
    X = lax.axis_size(intra_axis)
    Y = axis_size(inter_axes) if inter_axes else 1
    world = X * Y
    n = grad.shape[0]
    if n % (world * cfg.block_size):
        raise ValueError(
            f"grad len {n} must be a multiple of world*block "
            f"({world}*{cfg.block_size})")
    L = n // world

    k1 = k2 = None
    if key is not None:
        k1, k2 = jax.random.split(key)

    with _annotate("zero.qgz_reduce"):
        # -- step 1: slice + reorder (Eq. 1 -> Eq. 2), fused with quant ----
        # (X, Y, L): grouped by destination intra coordinate.  On TPU the
        # transpose rides inside the quant kernel's BlockSpec index_map
        # (§4.2 "fused quantization and remapping kernel").
        slices = grad.reshape(Y, X, L)
        payload, scales = quantize_reordered(slices, cfg, k1)

        # -- step 2: intra-node hop over the fast axis ---------------------
        # scales ride the SAME all-to-all message as the payload (bitcast
        # to int8 lanes, split off on receipt): identical wire bytes, one
        # collective launch per hop instead of two
        msg = lax.all_to_all(_pack_scales(payload, scales), intra_axis,
                             split_axis=0, concat_axis=0)
        payload, scales = _unpack_scales(msg, payload.shape[-1])
        # payload[x'] is peer x''s contribution to my (Y, L) slice group

        if not inter_axes:  # single-tier world: already the final slice
            X_ = payload.shape[0]
            out = dequant_reduce(payload.reshape(X_, -1),
                                 scales.reshape(X_, -1), cfg)
            return out.reshape(Y, L)[0].astype(out_dtype)

        # fused dequant -> fp32 reduce -> requant (one kernel; §4.2 fusion)
        X_ = payload.shape[0]
        payload2, scales2 = dequant_reduce_quant(
            payload.reshape(X_, -1), scales.reshape(X_, -1), cfg, cfg, k2)
        payload2 = payload2.reshape(Y, -1)                      # (Y, Lp)
        scales2 = scales2.reshape(Y, -1)

        # -- step 3: inter-node hop over the slow axes ---------------------
        # packed payload+scales again: one message per hop
        msg2 = lax.all_to_all(_pack_scales(payload2, scales2)[:, None],
                              inter_axes, split_axis=0,
                              concat_axis=1)                    # (1, Y, .)
        payload2, scales2 = _unpack_scales(msg2[0], payload2.shape[-1])
        out = dequant_reduce(payload2, scales2, cfg)            # (L,) fp32
        return out.astype(out_dtype)


def qgz_reduce_scatter_1hop(
    grad: Array,
    axes: Axes,
    cfg: QuantConfig,
    out_dtype=jnp.float32,
    key: Optional[Array] = None,
) -> Array:
    """The paper's intermediate design (Fig. 5 right / Fig. 6): flat 1-hop
    all-to-all.  Single quantize/dequantize pair, but each node emits
    N·M/Z of cross-node traffic — kept for the benchmark that reproduces
    the paper's volume-blowup argument (§3.3.2).
    """
    world = axis_size(axes)
    n = grad.shape[0]
    if n % (world * cfg.block_size):
        raise ValueError(
            f"grad len {n} must be a multiple of world*block "
            f"({world}*{cfg.block_size})")
    L = n // world
    with _annotate("zero.qgz_reduce1hop"):
        slices = grad.reshape(world, L)
        payload, scales = _quantize_slices(slices, cfg, key)
        msg = lax.all_to_all(_pack_scales(payload, scales),
                             _axes_tuple(axes), split_axis=0, concat_axis=0)
        payload, scales = _unpack_scales(msg, payload.shape[-1])
        deq = dequantize_blockwise(payload, scales, cfg)
        return jnp.sum(deq, axis=0).astype(out_dtype)


def qgz_quantized_ring_reduce_scatter(
    grad: Array,
    axes: Axes,
    cfg: QuantConfig,
    out_dtype=jnp.float32,
) -> Array:
    """Naive quantized *ring* reduce-scatter (paper Fig. 5 left): quantize →
    send → dequantize → reduce, repeated ``world-1`` times.  Error compounds
    once per hop; used only by the convergence benchmark to reproduce the
    paper's accuracy argument, never for training.
    """
    axes_t = _axes_tuple(axes)
    world = axis_size(axes)
    n = grad.shape[0]
    L = n // world
    # ring over the flattened axis: permute accumulated chunk to the next rank
    perm = [(i, (i + 1) % world) for i in range(world)]

    # flatten multi-axis rank
    rank = jnp.int32(0)
    for a in axes_t:
        rank = rank * lax.axis_size(a) + lax.axis_index(a)

    def hop(i, acc):
        # acc: fp32 (L,) partial sum for slice s_r(i) = (rank - 1 - i) mod W;
        # send it on, receive the neighbour's, add our local contribution.
        q, s = quantize_blockwise(acc, cfg)
        q = lax.ppermute(q, axes_t, perm)
        s = lax.ppermute(s, axes_t, perm)
        recv = dequantize_blockwise(q, s, cfg)
        idx = jnp.mod(rank - 2 - i, world)
        mine = lax.dynamic_slice_in_dim(grad, idx * L, L)
        return recv + mine.astype(jnp.float32)

    idx0 = jnp.mod(rank - 1, world)
    acc0 = lax.dynamic_slice_in_dim(grad, idx0 * L, L).astype(jnp.float32)
    # after world-1 hops each device holds the fully-reduced slice `rank`
    with _annotate("zero.qgz_ring"):
        acc = lax.fori_loop(0, world - 1, hop, acc0)
    return acc.astype(out_dtype)
