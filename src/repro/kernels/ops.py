"""Backend-dispatching wrappers over the quantization kernels.

This module is the SEAM between the numerical hot path and its
implementations: every quantized byte on the training and serving hot path
(core/collectives.py qwZ/qgZ, the serving INT8 head GEMM in
models/model.py) calls through here, and nothing outside ``repro.kernels``
imports a kernel module directly.  Backends (see kernels/platform.py for
resolution and the off-TPU error contract):

  ``pallas``    compiled Pallas TPU kernels (TPU only, hard error elsewhere)
  ``interpret`` the same kernel bodies through the Pallas interpreter —
                CPU CI executes the real kernel code, bit-for-bit
  ``xla``       pure-jnp references (core.quant / kernels.ref); alias "ref"

Selection: ``set_backend()`` / ``use_backend()`` here, the
``REPRO_KERNEL_BACKEND`` environment variable, or the platform default
(tpu: pallas, else xla).  The legacy ``FORCE`` module global is still
honoured (oldest precedence name for ``set_backend``).

Stochastic rounding threads PRNG keys into the kernels by drawing the
uniform field OUTSIDE the pallas_call (``core.quant.stochastic_uniform``
reproduces the reference's segmentation and key-split structure exactly)
and passing it as an extra tiled input: the in-kernel comparison
``u < s - floor(s)`` is then bit-identical to the jnp reference, so the
determinism-through-dispatch contract (fixed key -> identical payloads on
every backend) holds with the kernels actually running.  This covers the
fused ``dequant_reduce_quant`` too: its (C,) accumulator is requantized
with a uniform field drawn on the reference's 1-D segmentation, so the
intra-hop stochastic requant runs in-kernel on every backend.  The one
deliberate xla route left is ``cfg.stochastic`` with ``key=None``, which
goes to the reference to hit its loud "needs a PRNG key" assert.
"""
from __future__ import annotations

import contextlib
from typing import TYPE_CHECKING, Optional, Tuple

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # runtime import would be circular: core.collectives imports us
    from repro.core.quant import QuantConfig

from repro.kernels import platform
from repro.kernels import ref as _ref
from repro.kernels import quant_block as _qb
from repro.kernels import fused_dequant_reduce_quant as _fq
# stdlib-only metrics (obs.metrics imports neither jax nor repro): safe at
# the bottom of the import graph.  Counts routing DECISIONS — inside jit
# the wrapper body runs once per trace, so these are dispatch counts, not
# per-step execution counts (exactly what backend-selection debugging needs).
from repro.obs.metrics import count_dispatch as _count_dispatch

Array = jax.Array

# Programmatic override; None defers to $REPRO_KERNEL_BACKEND, then the
# platform default.  Prefer set_backend()/use_backend() over writing this.
FORCE: Optional[str] = None


def set_backend(name: Optional[str]) -> None:
    """Pin the kernel backend process-wide (None restores resolution via
    env/platform).  Validates eagerly: 'pallas' off-TPU raises here, at
    configuration time, not at the first hot-path call."""
    global FORCE
    if name is not None:
        platform.resolve(name)  # validate, incl. the off-TPU error
    FORCE = name


@contextlib.contextmanager
def use_backend(name: Optional[str]):
    """Scoped :func:`set_backend` (tests, benchmarks)."""
    global FORCE
    old = FORCE
    set_backend(name)
    try:
        yield
    finally:
        FORCE = old


def backend() -> str:
    """The backend the next kernel call will dispatch to."""
    return platform.resolve(FORCE)


def _as2d(x: Array) -> Tuple[Array, Tuple[int, ...]]:
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    return x.reshape(n, x.shape[-1]), lead


def quantize_blockwise(x: Array, cfg: QuantConfig,
                       key: Optional[Array] = None) -> Tuple[Array, Array]:
    """Blockwise quantize the trailing dim (qwZ shard quantize; qgZ hop 1)."""
    mode = backend()
    if mode == "xla" or (cfg.stochastic and key is None):
        # second arm: reference raises the loud "needs a PRNG key" assert
        from repro.core.quant import quantize_blockwise as q
        _count_dispatch("quantize_blockwise", "xla")
        return q(x, cfg, key)
    _count_dispatch("quantize_blockwise", mode)
    u = None
    if cfg.stochastic:
        from repro.core.quant import stochastic_uniform
        u = stochastic_uniform(x.shape, cfg, key)
    x2, lead = _as2d(x)
    u2 = None if u is None else u.reshape(x2.shape)
    p, s = _qb.quantize_pallas(x2, cfg, u=u2,
                               interpret=(mode == "interpret"))
    return p.reshape(*lead, p.shape[-1]), s.reshape(*lead, s.shape[-1])


def dequantize_blockwise(payload: Array, scales: Array, cfg: QuantConfig,
                         out_dtype=jnp.float32) -> Array:
    """Inverse of :func:`quantize_blockwise`; writes ``out_dtype`` (the qwZ
    gather passes bf16) directly — no fp32 materialization of the output."""
    mode = backend()
    _count_dispatch("dequantize_blockwise", mode)
    if mode == "xla":
        from repro.core.quant import dequantize_blockwise as d
        return d(payload, scales, cfg, out_dtype)
    p2, lead = _as2d(payload)
    s2, _ = _as2d(scales)
    x = _qb.dequantize_pallas(p2, s2, cfg, out_dtype,
                              interpret=(mode == "interpret"))
    return x.reshape(*lead, x.shape[-1])


def quantize_reordered(x: Array, cfg: QuantConfig,
                       key: Optional[Array] = None) -> Tuple[Array, Array]:
    """(Y, X, L) -> transpose to (X, Y, L), quantize trailing dim — qgZ
    step 1 with the remap folded into the kernel's BlockSpec index_map."""
    mode = backend()
    if mode == "xla" or (cfg.stochastic and key is None):
        xt = jnp.swapaxes(x, 0, 1)
        from repro.core.quant import quantize_blockwise as q
        _count_dispatch("quantize_reordered", "xla")
        return q(xt, cfg, key)
    _count_dispatch("quantize_reordered", mode)
    u = None
    if cfg.stochastic:
        # the reference draws on the transposed (X, Y, L) layout
        from repro.core.quant import stochastic_uniform
        Y, X, L = x.shape
        u = stochastic_uniform((X, Y, L), cfg, key)
    return _qb.quantize_reordered_pallas(x, cfg, u=u,
                                         interpret=(mode == "interpret"))


def dequant_reduce(payload: Array, scales: Array, cfg: QuantConfig,
                   out_dtype=jnp.float32) -> Array:
    """Sum N quantized contributions in fp32: (N, P), (N, NB) -> (C,)."""
    mode = backend()
    _count_dispatch("dequant_reduce", mode)
    if mode == "xla":
        return _ref.dequant_reduce_ref(payload, scales, cfg, out_dtype)
    return _fq.dequant_reduce_pallas(payload, scales, cfg, out_dtype,
                                     interpret=(mode == "interpret"))


def dequant_reduce_quant(payload: Array, scales: Array, cfg_in: QuantConfig,
                         cfg_out: QuantConfig,
                         key: Optional[Array] = None) -> Tuple[Array, Array]:
    """Fused dequant -> fp32 reduce -> requant (qgZ intra-hop, §4.2)."""
    mode = backend()
    if mode == "xla" or (cfg_out.stochastic and key is None):
        # second arm: reference raises the loud "needs a PRNG key" assert
        acc = _ref.dequant_reduce_ref(payload, scales, cfg_in, jnp.float32)
        from repro.core.quant import quantize_blockwise as q
        _count_dispatch("dequant_reduce_quant", "xla")
        return q(acc, cfg_out, key)
    _count_dispatch("dequant_reduce_quant", mode)
    u = None
    if cfg_out.stochastic:
        # the reference requantizes the flat (C,) accumulator, so the
        # uniform field uses its 1-D segmentation — this closed the last
        # stochastic xla fallback (DESIGN.md §7)
        from repro.core.quant import stochastic_uniform
        C = payload.shape[1] * 2 if cfg_in.bits == 4 else payload.shape[1]
        u = stochastic_uniform((C,), cfg_out, key)
    return _fq.dequant_reduce_quant_pallas(payload, scales, cfg_in, cfg_out,
                                           u=u,
                                           interpret=(mode == "interpret"))


def dequant_matmul(x: Array, payload: Array, scales: Array,
                   compute_dtype=jnp.bfloat16,
                   out_dtype=jnp.float32) -> Array:
    """Fused INT8-weight x activation GEMM: ``x @ dequant(payload).T``.

    x: (T, K) activations; payload: (N, K) int8 rows; scales: (N, NB)
    fp32 with K % NB == 0 (each row's K splits into NB scale groups).
    Dequantized weights round through ``compute_dtype`` (bf16) before the
    MXU — exactly the staged gather-dequant-einsum math — so the ``xla``
    backend is bit-identical to the staged path; the kernel applies the
    scales per weight tile in VMEM (INT8 rows stream from HBM at 1 B/elem,
    never materializing the bf16 weight matrix).
    """
    mode = backend()
    _count_dispatch("dequant_matmul", mode)
    if mode == "xla":
        return _ref.dequant_matmul_ref(x, payload, scales,
                                       compute_dtype=compute_dtype,
                                       out_dtype=out_dtype)
    from repro.kernels import dequant_matmul as _dm
    return _dm.dequant_matmul_pallas(x, payload, scales,
                                     compute_dtype=compute_dtype,
                                     out_dtype=out_dtype,
                                     interpret=(mode == "interpret"))
