"""Depth-k prefetch-ring layer schedule for the ZeRO++ engine.

:func:`repro.core.zeropp.zero_apply` runs every collective synchronously on
the critical path: gather layer *i*, compute layer *i*, gather layer *i+1*,
... — the "no overlap" worst case that ``benchmarks/throughput_model.py``
models.  The paper's throughput numbers assume the DeepSpeed schedule where
the next layer's all-gather is in flight *under* the current layer's
compute.  This module generalizes that schedule to a configurable
lookahead: a ring of ``k = ZeroConfig.prefetch`` gathered weight buffers
carried through a ``lax.scan`` (see DESIGN.md §3 for buffer lifetimes):

  forward   the carry holds a ring of k gathered (qwZ-dequantized) layer
            weights; iteration *i* issues layer *i+k*'s gather into the
            ring slot it just consumed, BEFORE computing layer *i* from
            the ring head, so the gather has k iterations of compute to
            complete under (k=1 is the classic double buffer; k>1 is for
            interconnects where one layer's compute cannot cover a full
            quantized gather).
  backward  the reverse scan mirrors the ring on the hpZ fast tier
            (layer *i-k*'s gather under layer *i*'s recompute+vjp) and
            carries a second ring of k unreduced gradients so layer
            *i+k*'s qgZ reduce-scatter retires k steps behind.

``optimization_barrier`` discipline: each iteration ends by pinning the
(compute result, updated ring[s]) tuple TOGETHER.  The joint barrier
forces the in-flight collectives to complete inside the iteration (XLA
cannot sink them into a later iteration or resurrect them at their use
site) while leaving them mutually independent — exactly the structure the
latency-hiding scheduler needs to emit async-start early and async-done
late.  Nothing creates a dependency *between* a collective and the
compute; that would serialize them and reproduce the synchronous schedule.

``ZeroConfig.prefetch = 0`` selects the synchronous reference schedule
(a scan over per-layer :func:`zero_apply`); every depth >= 1 issues
identical collectives on identical values in identical per-layer order, so
losses AND gradients match the reference bit for bit at every depth
(tests/test_schedule.py sweeps prefetch ∈ {0,1,2,3} and beyond the layer
count — ``ZeroConfig.effective_prefetch`` clamps the ring to n-1 slots).

MoE stacks use the same machinery at TWO granularities (DESIGN.md §3):
the layer scan rings the next layers' shared (attn/router/shared-expert)
gathers exactly as above, with the routed-expert chunk stack riding
through ``xs`` unpeeked; inside each layer, :func:`zero_chunk_scan` runs
the expert-chunk pipeline with its own ring.  Two knobs close the MoE
holes the plain nesting leaves:

  * ``spec`` (routing-ahead dispatch) — the layer scan speculatively
    gathers layer *i+k*'s FIRST expert chunk alongside its shared buffer
    (experts are gathered in full regardless of routing), so chunk 0 no
    longer waits on the router: the last synchronous expert gather moves
    off the critical path.  The backward recompute re-gathers chunk 0
    itself — values identical, so gradients are untouched.
  * ``f_fwd``/``f_bwd`` (hpZ-aware nested recompute) — the forward saves
    each layer's expert-chunk SECONDARY shards through the outer scan's
    residuals (:func:`zero_chunk_scan` ``collect_secondary``), and the
    backward recompute rebuilds the chunk pipeline from them on the hpZ
    fast tier (:func:`zero_chunk_scan_hpz`) instead of re-gathering every
    chunk on the slow qwZ tier.  The hpZ roundtrip is exact, so outputs
    and gradients are bit-identical; only the tier the recompute bytes
    ride changes.

Cost of the uniform scan body: the forward issues k wasted gathers (the
last k iterations prefetch layers 0..k-1 again, results discarded) and the
backward k dummy reduce-scatters (of zeros) and k wasted fast-tier gathers
— O(k/n_layers) extra wire bytes, all of it off the critical path.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import collectives as cl
from repro.core.zeropp import ZeroConfig, fwd_gather, grad_reduce, zero_apply
from repro.obs.trace import annotate

Array = jax.Array


# ---------------------------------------------------------------------------
# pytree helpers: cotangents for mixed float/int trees
# ---------------------------------------------------------------------------

def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def _split_floats(tree):
    """Partition a pytree into (float leaves, int leaves); each side keeps
    the full tree structure with ``None`` in the other side's positions."""
    floats = jax.tree.map(lambda x: x if _is_float(x) else None, tree)
    ints = jax.tree.map(lambda x: None if _is_float(x) else x, tree)
    return floats, ints


def _merge(floats, ints):
    """Inverse of :func:`_split_floats`."""
    f_leaves, treedef = jax.tree.flatten(floats, is_leaf=lambda x: x is None)
    i_leaves, _ = jax.tree.flatten(ints, is_leaf=lambda x: x is None)
    return jax.tree.unflatten(
        treedef, [f if f is not None else i
                  for f, i in zip(f_leaves, i_leaves)])


def _float0_like(x, extra_leading: Tuple[int, ...] = ()):
    """The cotangent jax expects for a non-differentiable (integer) leaf."""
    return np.zeros(extra_leading + tuple(x.shape), jax.dtypes.float0)


def _int_cotangents(tree, extra_leading: Tuple[int, ...] = ()):
    return jax.tree.map(lambda x: _float0_like(x, extra_leading), tree)


# ---------------------------------------------------------------------------
# backward-pass gather (hpZ fast tier, or the paper's second global gather)
# ---------------------------------------------------------------------------

def _bwd_gather(shard: Array, z: ZeroConfig) -> Array:
    if z.hpz:
        return cl.hpz_all_gather(shard, z.secondary_axes)
    return fwd_gather(shard, z)


def _bwd_src(stacked: Array, res_ws, z: ZeroConfig):
    """Per-layer shard stack the backward gathers from: the secondary
    (intra-node) shards saved by the forward when hpZ is on, else the
    primary shards themselves (the paper's second global gather)."""
    return res_ws if z.hpz else stacked


# ---------------------------------------------------------------------------
# ring plumbing (shared by the fwd/bwd scans)
# ---------------------------------------------------------------------------

# ``step.ring_*`` scopes name the ring's own operations in the profiler
# trace's op names (bench/scopes.py); they change no instruction.

def _ring_read(ring: Array, slot: Array) -> Array:
    with annotate("step.ring_read"):
        return lax.dynamic_index_in_dim(ring, slot, axis=0, keepdims=False)


def _ring_write(ring: Array, buf: Array, slot: Array) -> Array:
    with annotate("step.ring_write"):
        return lax.dynamic_update_index_in_dim(ring, buf, slot, axis=0)


def _ring_seed(slots: Sequence[Array]) -> Array:
    with annotate("step.ring_seed"):
        return jnp.stack(slots)


def _bwd_ring_seed(src, n: int, k: int, gather: Callable) -> Array:
    """Seed the backward weight ring: slot ``i % k`` holds step *i*'s
    gathered weights for the first k reverse iterations (i = n-k..n-1)."""
    slots: List[Optional[Array]] = [None] * k
    for i in range(n - k, n):
        p = jax.tree.map(
            lambda s: lax.dynamic_index_in_dim(s, i, axis=0, keepdims=False),
            src)
        slots[i % k] = gather(p)
    return _ring_seed(slots)


def _ring_grad_tail(dWring_f: Array, dprevs: Array, n: int, k: int,
                    z: ZeroConfig) -> Array:
    """Stitch the full gradient stack back together after a reverse ring
    scan: steps 0..k-1's unreduced gradients are left in the final ring
    (slot j = step j) and reduced here; dprevs[i] is step i+k's in-scan
    reduce (the top k slots were the dummy zero-reduces)."""
    head = [grad_reduce(dWring_f[j], z)[None].astype(dprevs.dtype)
            for j in range(k)]
    with annotate("step.ring_tail"):
        return jnp.concatenate(head + [dprevs[: n - k]], axis=0)


# ---------------------------------------------------------------------------
# the prefetched scan primitive
# ---------------------------------------------------------------------------

def zero_apply_scan(f: Callable, z: ZeroConfig, *,
                    f_fwd: Optional[Callable] = None,
                    f_bwd: Optional[Callable] = None,
                    spec: Optional[Callable] = None,
                    bwd_spec: Optional[Callable] = None):
    """Scan ``f`` over stacked per-layer primary shards, ZeRO++ style.

    ``f(W_full, h, x, *bargs) -> (h_next, y)`` where

      * ``W_full``  — the layer's gathered full weights (flat),
      * ``h``       — the scan carry (activations),
      * ``x``       — this layer's slice of the per-layer inputs pytree
                      ``xs`` (pass ``xs=None`` when there are none),
      * ``bargs``   — broadcast (layer-invariant) arrays, e.g. rope tables,
      * ``y``       — per-layer output, stacked into ``ys``.

    Returns ``run(stacked, h0, xs, *bargs, W0=None) -> (h_final, ys)``,
    differentiable w.r.t. ``stacked``, ``h0``, and every float leaf of
    ``xs``/``bargs``.  ``f`` is recomputed in the backward pass (activation
    checkpointing), exactly like :func:`zero_apply`.  ``W0``, if given, is
    a pre-gathered full buffer for step 0 — the ring seed skips step 0's
    gather (used by the chunk pipeline's speculative chunk-0 path; its
    gradient path is owned by the engine, so the buffer itself gets a zero
    cotangent).

    ``z.effective_prefetch(n) >= 1`` uses the depth-k ring schedule
    (``k = min(z.prefetch, n-1)``); 0 (or local mode) the synchronous
    reference.  All depths produce bit-identical outputs and gradients.

    Three optional knobs reshape the prefetched schedule WITHOUT changing
    its math (the synchronous reference always runs plain ``f``):

      * ``spec(xs, i) -> shard`` — a per-layer speculative-gather source;
        the forward ring pre-gathers ``spec(xs, i+k)`` alongside layer
        *i+k*'s weights and hands the result to ``f_fwd`` (routing-ahead
        dispatch: the MoE chunk-0 expert shard).
      * ``f_fwd(W, W_spec, h, x, *bargs) -> (h2, y, aux)`` — the
        prefetched-forward body.  ``W_spec`` is the ring's speculative
        buffer (None when ``spec`` is None); ``aux`` is an extra residual
        pytree threaded to the backward (None when ``f_bwd`` is None).
        Required whenever ``spec`` or ``f_bwd`` is given; must be
        value-identical to ``f`` modulo the extra plumbing.
      * ``f_bwd(W, h, x, aux, *bargs) -> (h2, y)`` — the recompute body
        the backward differentiates, consuming the saved ``aux`` (the MoE
        expert-chunk secondary shards: the nested recompute then rides
        the hpZ fast tier instead of re-gathering on qwZ).
      * ``bwd_spec(auxs, i) -> shard`` — the backward mirror of ``spec``:
        a per-layer speculative-gather source drawn from the STACKED saved
        residuals.  The reverse scan pre-gathers
        ``bwd_gather(bwd_spec(auxs, i-k))`` alongside layer *i-k*'s
        weights (one extra slot ring) and hands the buffer to ``f_bwd`` as
        keyword ``W0`` — so the nested recompute's chunk 0 is seeded from
        a ring slot filled k iterations early instead of issuing its own
        synchronous fast-tier gather (the backward twin of routing-ahead
        dispatch).  Same collective on the same saved value, one
        iteration earlier: gradients stay bit-identical.  Requires
        ``f_bwd``.
    """
    if (spec is not None or f_bwd is not None) and f_fwd is None:
        raise ValueError("zero_apply_scan: spec/f_bwd require f_fwd")
    if bwd_spec is not None and f_bwd is None:
        raise ValueError("zero_apply_scan: bwd_spec requires f_bwd")

    def run_sync(stacked, h0, xs, *bargs):
        ap = zero_apply(lambda W, h, x, *b: f(W, h, x, *b), z)

        def body(h, sx):
            p, x = sx
            h2, y = ap(p, h, x, *bargs)
            return h2, y

        return lax.scan(body, h0, (stacked, xs))

    def run(stacked, h0, xs, *bargs, W0: Optional[Array] = None):
        n = stacked.shape[0]
        if z.effective_prefetch(n) < 1:
            return run_sync(stacked, h0, xs, *bargs)
        w0_meta = None if W0 is None else (W0.shape, W0.dtype)
        return _prefetched(f, z, f_fwd, f_bwd, spec, bwd_spec, w0_meta)(
            stacked, h0, xs, tuple(bargs), W0)

    return run


def _prefetched(f: Callable, z: ZeroConfig, f_fwd, f_bwd, spec, bwd_spec,
                w0_meta):
    """The depth-k ring custom_vjp core (distributed, n >= 2)."""

    @jax.custom_vjp
    def scanned(stacked, h0, xs, bargs, W0):
        out, _ = scanned_fwd(stacked, h0, xs, bargs, W0)
        return out

    def scanned_fwd(stacked, h0, xs, bargs, W0):
        n = stacked.shape[0]
        k = z.effective_prefetch(n)
        # seed the ring with layers 0..k-1 (slot j = layer j); the body
        # then reads slot i%k (layer i) and refills it with layer i+k
        seed = [W0 if (j == 0 and W0 is not None)
                else fwd_gather(stacked[j], z) for j in range(k)]
        ring0 = _ring_seed(seed)
        if spec is not None:
            sring0 = _ring_seed([fwd_gather(spec(xs, j), z)
                                 for j in range(k)])

        def body(carry, sx):
            if spec is not None:
                h, ring, sring = carry
            else:
                h, ring = carry
            i, x = sx
            slot = jnp.remainder(i, k)
            nxt = jnp.remainder(i + k, n)
            # prefetch layer i+k's gather FIRST: the jaxpr issues it before
            # this layer's matmuls, and nothing makes the compute depend on
            # it.  The last k iterations re-gather layers 0..k-1
            # (discarded).
            p_next = lax.dynamic_index_in_dim(stacked, nxt, axis=0,
                                              keepdims=False)
            W_next = fwd_gather(p_next, z)
            W = _ring_read(ring, slot)
            if spec is not None:
                s_next = fwd_gather(spec(xs, nxt), z)
                W_spec = _ring_read(sring, slot)
            if f_fwd is not None:
                h2, y, aux = f_fwd(W, W_spec if spec is not None else None,
                                   h, x, *bargs)
            else:
                h2, y = f(W, h, x, *bargs)
                aux = None
            with annotate("step.residual"):
                if z.hpz:
                    # re-partition the gathered weights into this
                    # device's secondary shard: zero extra communication
                    # (paper §3.2.1)
                    res_w = cl.slice_secondary(W, z.secondary_axes)
                else:
                    res_w = jnp.zeros((0,), W.dtype)  # bwd re-gathers primary
            ring2 = _ring_write(ring, W_next, slot)
            # joint pin: in-flight gathers and compute all finish inside
            # this iteration but stay mutually independent (overlappable)
            if spec is not None:
                sring2 = _ring_write(sring, s_next, slot)
                h2, ring2, sring2 = lax.optimization_barrier(
                    (h2, ring2, sring2))
                carry2 = (h2, ring2, sring2)
            else:
                h2, ring2 = lax.optimization_barrier((h2, ring2))
                carry2 = (h2, ring2)
            outs = (y, res_w, h) if f_bwd is None else (y, res_w, h, aux)
            return carry2, outs

        init = (h0, ring0, sring0) if spec is not None else (h0, ring0)
        carry_out, outs = lax.scan(
            body, init, (jnp.arange(n, dtype=jnp.int32), xs))
        if f_bwd is None:
            ys, res_ws, h_ins = outs
            auxs = None
        else:
            ys, res_ws, h_ins, auxs = outs
        return (carry_out[0], ys), (stacked, res_ws, h_ins, xs, bargs, auxs)

    def scanned_bwd(res, ct):
        stacked, res_ws, h_ins, xs, bargs, auxs = res
        ct_h, ct_ys = ct
        n = stacked.shape[0]
        k = z.effective_prefetch(n)
        src = _bwd_src(stacked, res_ws, z)

        xs_f, xs_i = _split_floats(xs)
        bargs_f, bargs_i = _split_floats(bargs)

        if f_bwd is None:
            def f_flt(W, h, x_f, b_f, x_i, aux, W0_l):
                return f(W, h, _merge(x_f, x_i), *_merge(b_f, bargs_i))
        elif bwd_spec is None:
            # the recompute body consumes the saved per-layer residual
            # (e.g. expert-chunk secondary shards) as a constant: its
            # gradient path is owned by the engine's collectives, never
            # by differentiating the gather
            def f_flt(W, h, x_f, b_f, x_i, aux, W0_l):
                return f_bwd(W, h, _merge(x_f, x_i), aux,
                             *_merge(b_f, bargs_i))
        else:
            def f_flt(W, h, x_f, b_f, x_i, aux, W0_l):
                return f_bwd(W, h, _merge(x_f, x_i), aux,
                             *_merge(b_f, bargs_i), W0=W0_l)

        Wring0 = _bwd_ring_seed(src, n, k, lambda p: _bwd_gather(p, z))
        if bwd_spec is not None:
            # backward speculative ring: slot i%k carries the pre-gathered
            # chunk-0 buffer f_bwd's nested recompute would otherwise
            # gather synchronously at its own seed
            sw_slots: List[Optional[Array]] = [None] * k
            for j in range(n - k, n):
                sw_slots[j % k] = _bwd_gather(bwd_spec(auxs, j), z)
            sWring0 = _ring_seed(sw_slots)
        zero_b = jax.tree.map(
            lambda v: jnp.zeros(v.shape, v.dtype), bargs_f)
        # dW of layer i+k rides a second ring: its reduce-scatter runs
        # inside layer i's iteration, overlapped with the recompute+vjp.
        # The first k (i = n-1..n-k) iterations reduce zeros (discarded).
        full = stacked.shape[1] * cl.axis_size(z.dp_axes)
        with annotate("step.ring_seed"):
            dWring0 = jnp.zeros((k, full), jnp.float32)
        aux_xs = auxs if f_bwd is not None \
            else jnp.zeros((n,), jnp.float32)

        def body(carry, sx):
            if bwd_spec is not None:
                g_h, Wring, dWring, bg, sWring = carry
            else:
                g_h, Wring, dWring, bg = carry
            i, x_f, x_i, h_in, ct_y, aux = sx
            slot = jnp.remainder(i, k)
            prev = jnp.remainder(i - k, n)
            # 1. reduce layer i+k's pending gradient     [no dep on 3.]
            dprev = grad_reduce(_ring_read(dWring, slot), z)
            # 2. prefetch layer i-k's backward gather    [no dep on 3.]
            p_prev = jax.tree.map(
                lambda s: lax.dynamic_index_in_dim(
                    s, prev, axis=0, keepdims=False),
                src)
            W_prev = _bwd_gather(p_prev, z)
            if bwd_spec is not None:
                # 2b. ... and layer i-k's speculative chunk-0 buffer
                s_prev = _bwd_gather(bwd_spec(auxs, prev), z)
                W0_l = _ring_read(sWring, slot)
            else:
                W0_l = None
            # 3. recompute layer i and differentiate (remat)
            W = _ring_read(Wring, slot)
            _, vjp_fn = jax.vjp(
                lambda w, hh, xf, bf: f_flt(w, hh, xf, bf, x_i, aux, W0_l),
                W, h_in, x_f, bargs_f)
            dW, dh, dx_f, db_f = vjp_fn((g_h, ct_y))
            bg = jax.tree.map(jnp.add, bg, db_f)
            dWflat = dW.reshape(-1).astype(jnp.float32)
            Wring2 = _ring_write(Wring, W_prev, slot)
            dWring2 = _ring_write(dWring, dWflat, slot)
            # joint pin: collectives (1., 2.) and compute (3.) all complete
            # inside this iteration, mutually independent
            if bwd_spec is not None:
                sWring2 = _ring_write(sWring, s_prev, slot)
                dh, Wring2, dWring2, dprev, sWring2 = \
                    lax.optimization_barrier(
                        (dh, Wring2, dWring2, dprev, sWring2))
                return (dh, Wring2, dWring2, bg, sWring2), (dprev, dx_f)
            dh, Wring2, dWring2, dprev = lax.optimization_barrier(
                (dh, Wring2, dWring2, dprev))
            return (dh, Wring2, dWring2, bg), (dprev, dx_f)

        init = (ct_h, Wring0, dWring0, zero_b, sWring0) \
            if bwd_spec is not None else (ct_h, Wring0, dWring0, zero_b)
        (dh0, _, dWring_f, bg, *_), (dprevs, dxs_f) = lax.scan(
            body, init,
            (jnp.arange(n, dtype=jnp.int32), xs_f, xs_i, h_ins, ct_ys,
             aux_xs),
            reverse=True)
        dstacked = _ring_grad_tail(dWring_f, dprevs, n, k, z)
        dxs = _merge(dxs_f, _int_cotangents(xs_i, (n,)))
        dbargs = _merge(bg, _int_cotangents(bargs_i))
        dW0 = None if w0_meta is None \
            else jnp.zeros(w0_meta[0], w0_meta[1])
        return dstacked, dh0, dxs, dbargs, dW0

    def fwd(stacked, h0, xs, bargs, W0):
        return scanned_fwd(stacked, h0, xs, bargs, W0)

    scanned.defvjp(fwd, scanned_bwd)
    return scanned


# ---------------------------------------------------------------------------
# carry-less chunk pipeline (MoE expert chunks)
# ---------------------------------------------------------------------------

def _chunk_runner(engine, f: Callable, z: ZeroConfig):
    """Adapt a carry-less per-chunk ``f(W_full, x, *bargs) -> y`` onto a
    scan engine by threading a dummy scalar carry."""
    run = engine(lambda W, h, x, *b: (h, f(W, x, *b)), z)

    def run_chunks(stacked, xs, *bargs, W0: Optional[Array] = None):
        _, ys = run(stacked, jnp.zeros((), jnp.float32), xs, *bargs, W0=W0)
        return ys

    return run_chunks


def zero_chunk_scan(f: Callable, z: ZeroConfig, *,
                    collect_secondary: bool = False):
    """Chunked-parameter pipeline: ``f(W_full, x, *bargs) -> y`` scanned
    over stacked per-chunk primary shards with the depth-k ring schedule
    of :func:`zero_apply_scan` (chunk c+k's gather issued under chunk c's
    compute; per-chunk qgZ reduces retired k steps behind in backward).

    Chunks are independent — there is no carry.  Returns
    ``run(stacked, xs, *bargs, W0=None) -> ys``, differentiable w.r.t.
    ``stacked`` and the float leaves of ``xs``/``bargs``; ``W0`` is an
    optional pre-gathered chunk-0 buffer (the routing-ahead speculative
    gather).  Used for the MoE routed-expert chunks, where the per-chunk
    slot buffers are rebuilt from the token activations inside each
    chunk's own gather scope (models/model.py).

    ``collect_secondary=True`` additionally returns the stack of per-chunk
    secondary (hpZ) shards sliced from the gathered weights —
    ``run(...) -> (ys, sec)`` — zero extra communication, to be saved
    through an outer residual and replayed by :func:`zero_chunk_scan_hpz`
    in the nested recompute.
    """
    if not collect_secondary:
        return _chunk_runner(zero_apply_scan, f, z)

    def f2(W, h, x, *b):
        y = f(W, x, *b)
        if z.hpz and z.distributed:
            sec = cl.slice_secondary(W, z.secondary_axes)
        else:
            sec = jnp.zeros((0,), W.dtype)
        return h, (y, sec)

    run = zero_apply_scan(f2, z)

    def run_chunks(stacked, xs, *bargs, W0: Optional[Array] = None):
        _, (ys, secs) = run(stacked, jnp.zeros((), jnp.float32), xs,
                            *bargs, W0=W0)
        return ys, secs

    return run_chunks


def zero_chunk_scan_inference(f: Callable, z: ZeroConfig):
    """Serving-path :func:`zero_chunk_scan`: same forward pipeline, no vjp."""
    return _chunk_runner(zero_scan_inference, f, z)


def zero_chunk_scan_hpz(f: Callable, z: ZeroConfig):
    """Nested-recompute chunk pipeline fed from saved secondary shards.

    ``run(stacked, sec, xs, *bargs, W0=None) -> ys`` — the same math as
    :func:`zero_chunk_scan`, but every chunk's full weights are rebuilt
    with an intra-node hpZ all-gather of ``sec`` (the stack saved by
    ``zero_chunk_scan(collect_secondary=True)``) instead of the primary
    qwZ-tier gather.  The hpZ roundtrip reconstructs the forward weights
    exactly, so outputs and the qgZ-reduced d(stacked) are bit-identical
    to the primary-tier pipeline; only the interconnect tier the
    recompute's wire bytes ride changes.  ``sec`` is a schedule detail,
    not a differentiable input: its cotangent is zero (the expert
    gradient flows through d(stacked), exactly as in the primary
    pipeline).  ``W0``, if given, is chunk 0's already-gathered full
    weights (the outer scan's ``bwd_spec`` ring slot): the ring seed then
    skips its own synchronous chunk-0 gather — one fewer fast-tier gather
    on the recompute's critical path, same value, zero cotangent.
    Requires ``z.hpz``; the forward uses the same depth-k ring, the
    backward the mirrored reverse ring with pipelined reduces.
    """
    if not (z.hpz and z.distributed):
        raise ValueError("zero_chunk_scan_hpz requires distributed hpZ")

    def _gather(s):
        return cl.hpz_all_gather(s, z.secondary_axes)

    def make(w0_meta):
        return _chunk_hpz_vjp(f, z, _gather, w0_meta)

    def run(stacked, sec, xs, *bargs, W0: Optional[Array] = None):
        w0_meta = None if W0 is None else (W0.shape, W0.dtype)
        return make(w0_meta)(stacked, sec, xs, tuple(bargs), W0)

    return run


def _chunk_hpz_vjp(f: Callable, z: ZeroConfig, _gather, w0_meta):
    """The hpZ chunk pipeline's custom_vjp (one instance per W0 arity)."""

    @jax.custom_vjp
    def scanned(stacked, sec, xs, bargs, W0):
        out, _ = scanned_fwd(stacked, sec, xs, bargs, W0)
        return out

    def scanned_fwd(stacked, sec, xs, bargs, W0):
        nc = sec.shape[0]
        k = z.effective_prefetch(nc)
        if k < 1:
            def body_sync(_, sx):
                s_c, x = sx
                return (), f(_gather(s_c), x, *bargs)

            _, ys = lax.scan(body_sync, (), (sec, xs))
            return ys, (stacked, sec, xs, bargs)

        seed = [W0 if (j == 0 and w0_meta is not None)
                else _gather(sec[j]) for j in range(k)]
        ring0 = _ring_seed(seed)

        def body(ring, sx):
            i, x = sx
            slot = jnp.remainder(i, k)
            s_next = lax.dynamic_index_in_dim(
                sec, jnp.remainder(i + k, nc), axis=0, keepdims=False)
            W_next = _gather(s_next)
            y = f(_ring_read(ring, slot), x, *bargs)
            ring2 = _ring_write(ring, W_next, slot)
            y, ring2 = lax.optimization_barrier((y, ring2))
            return ring2, y

        _, ys = lax.scan(body, ring0,
                         (jnp.arange(nc, dtype=jnp.int32), xs))
        return ys, (stacked, sec, xs, bargs)

    def scanned_bwd(res, ct_ys):
        stacked, sec, xs, bargs = res
        nc = sec.shape[0]
        k = z.effective_prefetch(nc)
        xs_f, xs_i = _split_floats(xs)
        bargs_f, bargs_i = _split_floats(bargs)

        def f_flt(W, x_f, b_f, x_i):
            return f(W, _merge(x_f, x_i), *_merge(b_f, bargs_i))

        zero_b = jax.tree.map(
            lambda v: jnp.zeros(v.shape, v.dtype), bargs_f)

        if k < 1:
            def body_sync(bg, sx):
                s_c, x_f, x_i, ct_y = sx
                W = _gather(s_c)
                _, vjp_fn = jax.vjp(
                    lambda w, xf, bf: f_flt(w, xf, bf, x_i),
                    W, x_f, bargs_f)
                dW, dx_f, db_f = vjp_fn(ct_y)
                bg = jax.tree.map(jnp.add, bg, db_f)
                return bg, (grad_reduce(dW.reshape(-1), z), dx_f)

            bg, (drows, dxs_f) = lax.scan(
                body_sync, zero_b, (sec, xs_f, xs_i, ct_ys), reverse=True)
            dstacked = drows
        else:
            Wring0 = _bwd_ring_seed(sec, nc, k, _gather)
            full = stacked.shape[1] * cl.axis_size(z.dp_axes)
            with annotate("step.ring_seed"):
                dWring0 = jnp.zeros((k, full), jnp.float32)

            def body(carry, sx):
                Wring, dWring, bg = carry
                i, x_f, x_i, ct_y = sx
                slot = jnp.remainder(i, k)
                dprev = grad_reduce(_ring_read(dWring, slot), z)
                s_prev = lax.dynamic_index_in_dim(
                    sec, jnp.remainder(i - k, nc), axis=0, keepdims=False)
                W_prev = _gather(s_prev)
                W = _ring_read(Wring, slot)
                _, vjp_fn = jax.vjp(
                    lambda w, xf, bf: f_flt(w, xf, bf, x_i),
                    W, x_f, bargs_f)
                dW, dx_f, db_f = vjp_fn(ct_y)
                bg = jax.tree.map(jnp.add, bg, db_f)
                dWflat = dW.reshape(-1).astype(jnp.float32)
                Wring2 = _ring_write(Wring, W_prev, slot)
                dWring2 = _ring_write(dWring, dWflat, slot)
                Wring2, dWring2, dprev = lax.optimization_barrier(
                    (Wring2, dWring2, dprev))
                return (Wring2, dWring2, bg), (dprev, dx_f)

            (_, dWring_f, bg), (dprevs, dxs_f) = lax.scan(
                body, (Wring0, dWring0, zero_b),
                (jnp.arange(nc, dtype=jnp.int32), xs_f, xs_i, ct_ys),
                reverse=True)
            dstacked = _ring_grad_tail(dWring_f, dprevs, nc, k, z)

        dxs = _merge(dxs_f, _int_cotangents(xs_i, (nc,)))
        dbargs = _merge(bg, _int_cotangents(bargs_i))
        dW0 = None if w0_meta is None \
            else jnp.zeros(w0_meta[0], w0_meta[1])
        return dstacked, jnp.zeros_like(sec), dxs, dbargs, dW0

    scanned.defvjp(scanned_fwd, scanned_bwd)
    return scanned


# ---------------------------------------------------------------------------
# inference variant (no gradient machinery)
# ---------------------------------------------------------------------------

def zero_scan_inference(f: Callable, z: ZeroConfig, *,
                        spec: Optional[Callable] = None):
    """Serving-path prefetched scan: same forward ring schedule as
    :func:`zero_apply_scan`, no residuals, no vjp.

    ``f(W_full, h, x, *bargs) -> (h_next, y)``; returns
    ``run(stacked, h0, xs, *bargs, W0=None) -> (h_final, ys)``.  With
    ``spec`` the body is called ``f(W, W_spec, h, x, *bargs)`` (W_spec is
    None on the synchronous path, where no speculative gather exists).
    """

    def call(W, W_spec, h, x, *bargs):
        if spec is not None:
            return f(W, W_spec, h, x, *bargs)
        return f(W, h, x, *bargs)

    def run(stacked, h0, xs, *bargs, W0: Optional[Array] = None):
        n = stacked.shape[0]
        k = z.effective_prefetch(n)
        if k < 1:
            def body_sync(h, sx):
                p, x = sx
                W = fwd_gather(p, z) if z.distributed \
                    else p.astype(z.compute_dtype)
                return call(W, None, h, x, *bargs)

            return lax.scan(body_sync, h0, (stacked, xs))

        seed = [W0 if (j == 0 and W0 is not None)
                else fwd_gather(stacked[j], z) for j in range(k)]
        ring0 = _ring_seed(seed)
        if spec is not None:
            sring0 = _ring_seed([fwd_gather(spec(xs, j), z)
                                 for j in range(k)])

        def body(carry, sx):
            if spec is not None:
                h, ring, sring = carry
            else:
                h, ring = carry
            i, x = sx
            slot = jnp.remainder(i, k)
            nxt = jnp.remainder(i + k, n)
            p_next = lax.dynamic_index_in_dim(stacked, nxt, axis=0,
                                              keepdims=False)
            W_next = fwd_gather(p_next, z)
            W = _ring_read(ring, slot)
            if spec is not None:
                s_next = fwd_gather(spec(xs, nxt), z)
                W_spec = _ring_read(sring, slot)
                h2, y = f(W, W_spec, h, x, *bargs)
            else:
                h2, y = f(W, h, x, *bargs)
            ring2 = _ring_write(ring, W_next, slot)
            if spec is not None:
                sring2 = _ring_write(sring, s_next, slot)
                h2, ring2, sring2 = lax.optimization_barrier(
                    (h2, ring2, sring2))
                return (h2, ring2, sring2), y
            h2, ring2 = lax.optimization_barrier((h2, ring2))
            return (h2, ring2), y

        init = (h0, ring0, sring0) if spec is not None else (h0, ring0)
        carry_out, ys = lax.scan(
            body, init, (jnp.arange(n, dtype=jnp.int32), xs))
        return carry_out[0], ys

    return run
