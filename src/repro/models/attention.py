"""GQA attention: training (optionally sequence-parallel), prefill, decode.

Sequence parallelism (activations sharded on the sequence dim over the
``seq_axes`` mesh axes) follows the all-gather-KV scheme: queries stay
local, K/V are gathered across the sequence shards — cheap under GQA where
the KV heads are a small fraction of Q heads.  Decode uses an exact 2-pass
split-KV softmax (pmax/psum), the TPU analogue of flash-decoding, so the
KV cache can shard its *sequence* dimension over any set of mesh axes
regardless of head counts.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array
NEG_INF = -1e30


def _gather_seq(x: Array, seq_axes: Sequence[str]) -> Array:
    """All-gather a (B, S_loc, ...) tensor along dim 1 over seq_axes.

    bf16 moves as u16 bits so no backend/optimizer can upcast the wire
    dtype (see collectives.gather_bf16); the bitcast is not differentiable,
    so the VJP (reduce-scatter of the cotangent) is supplied explicitly.
    """
    if not seq_axes:
        return x
    return _gather_seq_vjp(x, tuple(seq_axes))


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_seq_vjp(x, seq_axes):
    from repro.core.collectives import gather_bf16
    for ax in seq_axes:
        x = gather_bf16(x, ax, axis=1)
    return x


def _gather_seq_fwd(x, seq_axes):
    return _gather_seq_vjp(x, seq_axes), None


def _gather_seq_bwd(seq_axes, _, g):
    for ax in reversed(seq_axes):
        g = lax.psum_scatter(g, ax, scatter_dimension=1, tiled=True)
    return (g,)


_gather_seq_vjp.defvjp(_gather_seq_fwd, _gather_seq_bwd)


def seq_shard_offset(s_local: int, seq_axes: Sequence[str]) -> Array:
    """Global position of this device's first sequence element."""
    off = jnp.int32(0)
    for ax in seq_axes:
        off = off * lax.axis_size(ax) + lax.axis_index(ax)
    return off * s_local


def mha(
    q: Array,                     # (B, Sq, H, hd) local query shard
    k: Array,                     # (B, Sq, K, hd) local key shard
    v: Array,                     # (B, Sq, K, hd)
    *,
    seq_axes: Sequence[str] = (),
    causal: bool = True,
    window: int = 0,              # >0: sliding-window (local) attention
    softmax_scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    kv_chunk: int = 1024,         # flash path kicks in above this length
    impl: str = "xla",            # xla | pallas (flash kernel, §Perf)
) -> Array:
    """Training/prefill attention with optional sequence parallelism.

    Short sequences use the dense path; long sequences use the chunked
    online-softmax (flash) path with a hand-written VJP, keeping the
    working set at O(Sq·kv_chunk) instead of O(Sq·S) — mandatory for the
    32k/500k shapes where the dense logits would be tens of GB.

    ``impl="pallas"`` routes to the Pallas flash kernel (logit tiles stay
    in VMEM; HBM sees Q/K/V/O only).  The kernel computes its own absolute
    positions, so it requires unsharded sequence (batch-first layout);
    sequence-parallel cells fall back to the jnp flash path.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    scale = softmax_scale or hd ** -0.5

    kg = _gather_seq(k, seq_axes)   # (B, S, K, hd)
    vg = _gather_seq(v, seq_axes)
    S = kg.shape[1]

    q_pos = seq_shard_offset(Sq, seq_axes) + jnp.arange(Sq)

    if impl == "pallas" and not seq_axes and S >= 512 and S % 512 == 0 \
            and Sq % 512 == 0:
        from repro.kernels.flash_ops import flash_attention_kernel
        return flash_attention_kernel(q, kg, vg, scale, causal, window,
                                      logit_softcap)

    if S > kv_chunk and S % kv_chunk == 0:
        return flash_attention(q, kg, vg, q_pos, scale=scale, causal=causal,
                               window=window, logit_softcap=logit_softcap,
                               kv_chunk=kv_chunk)

    k_pos = jnp.arange(S)
    # GQA: repeat KV heads up to H
    rep = H // K
    kgr = jnp.repeat(kg, rep, axis=2)
    vgr = jnp.repeat(vg, rep, axis=2)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kgr,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    mask = jnp.ones((Sq, S), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vgr)


# ---------------------------------------------------------------------------
# chunked online-softmax attention (flash) with hand-written VJP
# ---------------------------------------------------------------------------

def _chunk_logits(q, kc, k0, q_pos, scale, causal, window, softcap):
    """(B,H,Sq,kc) masked fp32 logits for one KV chunk starting at k0."""
    B, Sq, H, hd = q.shape
    K = kc.shape[2]
    rep = H // K
    kcr = jnp.repeat(kc, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kcr,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    k_pos = k0 + jnp.arange(kc.shape[1])
    mask = jnp.ones((Sq, kc.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(mask[None, None], logits, NEG_INF)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, kg, vg, q_pos, scale, causal, window, logit_softcap,
                    kv_chunk):
    out, _, _ = _flash_fwd_impl(q, kg, vg, q_pos, scale, causal, window,
                                logit_softcap, kv_chunk)
    return out


def _flash_fwd_impl(q, kg, vg, q_pos, scale, causal, window, softcap,
                    kv_chunk):
    B, Sq, H, hd = q.shape
    S, K = kg.shape[1], kg.shape[2]
    nk = S // kv_chunk
    ks = jnp.moveaxis(kg.reshape(B, nk, kv_chunk, K, hd), 1, 0)
    vs = jnp.moveaxis(vg.reshape(B, nk, kv_chunk, K, hd), 1, 0)
    k0s = jnp.arange(nk) * kv_chunk

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, k0 = xs
        logits = _chunk_logits(q, kc, k0, q_pos, scale, causal, window,
                               softcap)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        rep = H // K
        vcr = jnp.repeat(vc, rep, axis=2)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vcr)
        acc = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l, acc), ()

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (ks, vs, k0s))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    out = jnp.moveaxis(out, 1, 2)  # (B, Sq, H, hd)
    return out, m, l_safe


def _flash_fwd(q, kg, vg, q_pos, scale, causal, window, softcap, kv_chunk):
    out, m, l = _flash_fwd_impl(q, kg, vg, q_pos, scale, causal, window,
                                softcap, kv_chunk)
    return out, (q, kg, vg, q_pos, out, m, l)


def _flash_bwd(scale, causal, window, softcap, kv_chunk, res, dout):
    q, kg, vg, q_pos, out, m, l = res
    B, Sq, H, hd = q.shape
    S, K = kg.shape[1], kg.shape[2]
    nk = S // kv_chunk
    rep = H // K

    do = jnp.moveaxis(dout, 1, 2).astype(jnp.float32)    # (B,H,Sq,hd)
    o = jnp.moveaxis(out, 1, 2).astype(jnp.float32)
    D = jnp.sum(do * o, axis=-1)                          # (B,H,Sq)

    ks = jnp.moveaxis(kg.reshape(B, nk, kv_chunk, K, hd), 1, 0)
    vs = jnp.moveaxis(vg.reshape(B, nk, kv_chunk, K, hd), 1, 0)
    k0s = jnp.arange(nk) * kv_chunk

    def step(dq, xs):
        kc, vc, k0 = xs
        logits = _chunk_logits(q, kc, k0, q_pos, scale, causal, window,
                               softcap)
        p = jnp.exp(logits - m[..., None]) / l[..., None]  # (B,H,Sq,kc)
        vcr = jnp.repeat(vc, rep, axis=2)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, vcr.astype(jnp.float32))
        dl = p * (dp - D[..., None])                       # d logits (capped)
        if softcap:
            # logits = softcap * tanh(raw / softcap); recompute tanh term.
            # Masked positions hold NEG_INF (dl is already 0 there) — zero
            # the chain factor explicitly so 0 * inf doesn't produce NaN.
            t = logits / softcap
            chain = jnp.where(logits <= NEG_INF / 2, 0.0, 1.0 - t * t)
            dl = dl * chain
        kcr = jnp.repeat(kc, rep, axis=2)
        dq_c = jnp.einsum("bhqk,bkhd->bhqd", dl,
                          kcr.astype(jnp.float32)) * scale
        dk_h = jnp.einsum("bhqk,bhqd->bkhd", dl,
                          jnp.moveaxis(q, 1, 2).astype(jnp.float32)) * scale
        p32 = p
        dv_h = jnp.einsum("bhqk,bhqd->bkhd", p32, do)
        # GQA: fold the repeated head dim back onto the K kv-heads
        dk_c = dk_h.reshape(B, kv_chunk, K, rep, hd).sum(axis=3)
        dv_c = dv_h.reshape(B, kv_chunk, K, rep, hd).sum(axis=3)
        return dq + dq_c, (dk_c, dv_c)

    dq0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    dq, (dks, dvs) = lax.scan(step, dq0, (ks, vs, k0s))
    dq = jnp.moveaxis(dq, 1, 2).astype(q.dtype)           # (B,Sq,H,hd)
    dkg = jnp.moveaxis(dks, 0, 1).reshape(B, S, K, hd).astype(kg.dtype)
    dvg = jnp.moveaxis(dvs, 0, 1).reshape(B, S, K, hd).astype(vg.dtype)
    return dq, dkg, dvg, None


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def per_seq_pos(cache_pos: Array, batch: int) -> Array:
    """Normalize ``cache_pos`` to a per-sequence (B,) int32 vector.

    The decode path is continuously batched: every sequence in the batch
    may sit at a different position (see serve/engine.py).  A scalar is
    accepted for the uniform-position case and broadcast.
    """
    p = jnp.asarray(cache_pos, jnp.int32)
    if p.ndim == 0:
        return jnp.broadcast_to(p, (batch,))
    assert p.shape == (batch,), (p.shape, batch)
    return p


def decode_attend(
    q: Array,                     # (B, 1, H, hd) new-token queries
    k_cache: Array,               # (B, S_loc, K, hd) local KV-seq shard
    v_cache: Array,
    cache_pos: Array,             # (B,) or () int32: pos of the newest token
    *,
    kv_seq_axes: Sequence[str] = (),
    softmax_scale: Optional[float] = None,
    window: int = 0,
    logit_softcap: float = 0.0,
    slot_positions: Optional[Array] = None,  # (B,S_loc) or (S_loc,) slot pos
) -> Array:
    """Exact split-KV decode attention (2-pass max/sum-exp combine).

    Each device scores its local KV shard, then the global max, normalizer
    and weighted values are combined with pmax/psum over ``kv_seq_axes``.
    ``cache_pos`` is per-sequence: sequences at different positions (the
    continuous-batching workload) share one decode step, each row masking
    its own valid prefix.  ``slot_positions`` supports ring-buffer caches
    (sliding-window layers): slot s holds the token at that global position
    (may be negative = empty).
    """
    B, _, H, hd = q.shape
    S_loc, K = k_cache.shape[1], k_cache.shape[2]
    scale = softmax_scale or hd ** -0.5
    cache_pos = per_seq_pos(cache_pos, B)

    rep = H // K
    kk = jnp.repeat(k_cache, rep, axis=2)  # (B, S_loc, H, hd)
    vv = jnp.repeat(v_cache, rep, axis=2)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if slot_positions is None:
        pos = seq_shard_offset(S_loc, kv_seq_axes) + jnp.arange(S_loc)
    else:
        pos = slot_positions
    if pos.ndim == 1:
        pos = pos[None, :]                             # -> (1|B, S_loc)
    valid = (pos >= 0) & (pos <= cache_pos[:, None])   # (B, S_loc)
    if window:
        valid &= pos > cache_pos[:, None] - window
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)

    m = jnp.max(logits, axis=-1)                       # (B,H,1)
    if kv_seq_axes:
        m = lax.pmax(m, tuple(kv_seq_axes))
    e = jnp.exp(logits - m[..., None])
    e = jnp.where(valid[:, None, None, :], e, 0.0)
    denom = jnp.sum(e, axis=-1)                        # (B,H,1)
    num = jnp.einsum("bhqk,bkhd->bqhd", e.astype(q.dtype), vv)
    if kv_seq_axes:
        denom = lax.psum(denom, tuple(kv_seq_axes))
        num = lax.psum(num, tuple(kv_seq_axes))
    out = num / jnp.moveaxis(denom, 1, 2)[..., None].astype(num.dtype)
    return out.astype(q.dtype)


def _kv_axes_world(kv_seq_axes: Sequence[str]) -> int:
    w = 1
    for ax in kv_seq_axes:
        w *= lax.axis_size(ax)
    return w


def paged_insert(
    k_cache: Array,               # (N_pages, page_loc, K, hd) local shard
    v_cache: Array,
    k_new: Array,                 # (B, T, K, hd)
    v_new: Array,
    positions: Array,             # (B, T) int32 global write positions
    table: Array,                 # (B, Pm) int32 physical page ids, -1 empty
    kv_seq_axes: Sequence[str] = (),
) -> Tuple[Array, Array]:
    """Scatter new K/V into a paged arena through per-slot page tables.

    The arena's page dim is unsharded; the *within-page* token dim is
    sharded over ``kv_seq_axes`` (global page size = page_loc x world, this
    device owning within-page offsets [d_off, d_off + page_loc)).  Writes
    whose logical page maps to -1 (inactive row / past the reservation) or
    whose within-page offset belongs to another shard are dropped — this
    is what lets one batched step carry prefilling/idle rows without ever
    touching pages they don't own.
    """
    N, page_loc = k_cache.shape[0], k_cache.shape[1]
    B, T = positions.shape
    Pm = table.shape[1]
    page = page_loc * _kv_axes_world(kv_seq_axes)
    d_off = seq_shard_offset(page_loc, kv_seq_axes)

    lp = positions // page                                     # (B, T)
    phys = jnp.take_along_axis(table, jnp.clip(lp, 0, Pm - 1), axis=1)
    loc = positions % page - d_off
    ok = (phys >= 0) & (lp >= 0) & (lp < Pm) & (loc >= 0) & (loc < page_loc)
    rows = jnp.where(ok, phys, N).reshape(-1)                  # N = out of range
    cols = jnp.clip(loc, 0, page_loc - 1).reshape(-1)

    def upd(cache, new):
        flat = new.reshape(B * T, new.shape[2], new.shape[3])
        return cache.at[rows, cols].set(flat.astype(cache.dtype),
                                        mode="drop")

    return upd(k_cache, k_new), upd(v_cache, v_new)


def paged_attend(
    q: Array,                     # (B, T, H, hd) chunk queries
    k_cache: Array,               # (N_pages, page_loc, K, hd) local shard
    v_cache: Array,
    positions: Array,             # (B, T) int32 query positions
    table: Array,                 # (B, Pm) int32 physical page ids, -1 empty
    *,
    kv_seq_axes: Sequence[str] = (),
    softmax_scale: Optional[float] = None,
    logit_softcap: float = 0.0,
) -> Array:
    """Exact split-KV attention over a paged arena (2-pass pmax/psum).

    Gathers each row's pages into a (B, Pm*page_loc) causal view; key
    positions are reconstructed from logical page index x page size +
    within-page offset, with -1 marking unmapped pages.  Multi-token rows
    (T > 1: chunked prefill, speculative verify) get per-query causal
    masks against their own just-inserted keys.  Unlike decode_attend the
    normalizer is clamped: an all-(-1) table row (idle slot riding the
    batched step) attends to nothing and yields zeros, not NaN.
    """
    B, T, H, hd = q.shape
    N, page_loc, K = k_cache.shape[0], k_cache.shape[1], k_cache.shape[2]
    Pm = table.shape[1]
    page = page_loc * _kv_axes_world(kv_seq_axes)
    d_off = seq_shard_offset(page_loc, kv_seq_axes)
    scale = softmax_scale or hd ** -0.5
    S = Pm * page_loc

    safe = jnp.maximum(table, 0)
    kk = k_cache[safe].reshape(B, S, K, hd)
    vv = v_cache[safe].reshape(B, S, K, hd)
    rep = H // K
    kk = jnp.repeat(kk, rep, axis=2)                           # (B, S, H, hd)
    vv = jnp.repeat(vv, rep, axis=2)

    kpos = jnp.where(
        (table >= 0)[:, :, None],
        jnp.arange(Pm, dtype=jnp.int32)[None, :, None] * page + d_off
        + jnp.arange(page_loc, dtype=jnp.int32)[None, None, :],
        -1,
    ).reshape(B, S)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    valid = (kpos >= 0)[:, None, :] & \
        (kpos[:, None, :] <= positions[:, :, None])            # (B, T, S)
    logits = jnp.where(valid[:, None, :, :], logits, NEG_INF)

    m = jnp.max(logits, axis=-1)                               # (B, H, T)
    if kv_seq_axes:
        m = lax.pmax(m, tuple(kv_seq_axes))
    e = jnp.exp(logits - m[..., None])
    e = jnp.where(valid[:, None, :, :], e, 0.0)
    denom = jnp.sum(e, axis=-1)                                # (B, H, T)
    num = jnp.einsum("bhqk,bkhd->bqhd", e.astype(q.dtype), vv)
    if kv_seq_axes:
        denom = lax.psum(denom, tuple(kv_seq_axes))
        num = lax.psum(num, tuple(kv_seq_axes))
    denom = jnp.maximum(denom, 1e-30)
    out = num / jnp.moveaxis(denom, 1, 2)[..., None].astype(num.dtype)
    return out.astype(q.dtype)


def cache_insert(
    k_cache: Array,               # (B, S_loc, K, hd)
    v_cache: Array,
    k_new: Array,                 # (B, 1, K, hd)
    v_new: Array,
    cache_pos: Array,             # (B,) or () int32 global write position
    kv_seq_axes: Sequence[str] = (),
) -> Tuple[Array, Array]:
    """Write each sequence's new K/V into whichever device owns its slot.

    ``cache_pos`` is per-sequence, so every batch row writes at its own
    slot (rows whose slot lives on another KV shard are left untouched
    there and written by the owner).
    """
    B, S_loc = k_cache.shape[0], k_cache.shape[1]
    off = seq_shard_offset(S_loc, kv_seq_axes)
    pos = per_seq_pos(cache_pos, B)
    local_idx = jnp.clip(pos - off, 0, S_loc - 1)
    mine = (pos >= off) & (pos < off + S_loc)

    def upd(cache, new):
        def one(c, n, i, m):   # c: (S_loc, K, hd), n: (1, K, hd)
            u = lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), i,
                                                axis=0)
            return jnp.where(m, u, c)

        return jax.vmap(one)(cache, new, local_idx, mine)

    return upd(k_cache, k_new), upd(v_cache, v_new)
