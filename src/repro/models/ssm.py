"""State-space sequence mixers: Mamba-2 (SSD) and RG-LRU (Griffin).

Both are diagonal linear recurrences ``h_t = a_t ⊙ h_{t-1} + b_t``; under
sequence parallelism each device scans its local shard and the boundary
states are combined with an exchanged prefix (states are tiny compared to
activations, so a gather of per-shard (decay, state) pairs is ~free).

Mamba-2 uses the SSD chunked formulation (arXiv:2405.21060 §6): intra-chunk
attention-like matmuls (MXU-friendly) plus an inter-chunk state recurrence
via associative scan.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


# ---------------------------------------------------------------------------
# cross-shard prefix for diagonal linear recurrences
# ---------------------------------------------------------------------------

def shard_prefix_state(decay_total: Array, state_final: Array,
                       seq_axes: Sequence[str]) -> Array:
    """Incoming state for this device's shard.

    decay_total: (...,) product of decays over the local shard.
    state_final: (...,) local final state assuming zero incoming state.
    Returns h_in = sum_{r<me} (prod_{r<t<me} decay_t) state_r.
    """
    if not seq_axes:
        return jnp.zeros_like(state_final)
    axes = tuple(seq_axes)
    # stack both tensors along a leading shard dim, ordered by flattened rank
    d = decay_total[None]
    s = state_final[None]
    for ax in reversed(axes):
        d = lax.all_gather(d, ax, axis=0, tiled=True)
        s = lax.all_gather(s, ax, axis=0, tiled=True)
    n = d.shape[0]
    rank = jnp.int32(0)
    for ax in axes:
        rank = rank * lax.axis_size(ax) + lax.axis_index(ax)
    # sequential prefix over the (static, small) shard count:
    # h_in(0)=0; h_in(k) = d_{k-1}·h_in(k-1) + s_{k-1}
    h_all = [jnp.zeros_like(state_final)]
    for k in range(1, n):
        h_all.append(d[k - 1] * h_all[k - 1] + s[k - 1])
    h_stack = jnp.stack(h_all)  # (n, ...)
    return h_stack[rank]


def gather_conv_halo(x: Array, taps: int, seq_axes: Sequence[str]) -> Array:
    """History (B, taps, C) for a causal conv: previous shard's tail."""
    B, S, C = x.shape
    tail = x[:, S - taps:, :][None]  # (1, B, taps, C)
    if not seq_axes:
        return jnp.zeros((B, taps, C), x.dtype)
    t = tail
    for ax in reversed(tuple(seq_axes)):
        t = lax.all_gather(t, ax, axis=0, tiled=True)
    n = t.shape[0]
    rank = jnp.int32(0)
    for ax in seq_axes:
        rank = rank * lax.axis_size(ax) + lax.axis_index(ax)
    prev = jnp.where(rank > 0, jnp.clip(rank - 1, 0, n - 1), 0)
    halo = t[prev]  # (B, taps, C)
    return jnp.where(rank > 0, halo, jnp.zeros_like(halo))


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------

class SSDState(NamedTuple):
    h: Array          # (B, nh, N, hp) recurrent state
    conv: Array       # (B, W-1, conv_dim) conv history


def ssd_scan(
    x: Array,        # (B, S, nh, hp)
    dt: Array,       # (B, S, nh)  (already softplus'd, >0)
    A: Array,        # (nh,)       (negative)
    Bm: Array,       # (B, S, G, N)
    Cm: Array,       # (B, S, G, N)
    *,
    chunk: int,
    h0: Optional[Array] = None,        # (B, nh, N, hp)
    seq_axes: Sequence[str] = (),
) -> Tuple[Array, Array]:
    """Chunked SSD: returns (y, final_state).

    y[t] = C_t · h_t,   h_t = exp(dt_t A) h_{t-1} + dt_t B_t ⊗ x_t
    """
    Bsz, S, nh, hp = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    hg = nh // G  # heads per B/C group

    f32 = jnp.float32
    xc = x.reshape(Bsz, nc, chunk, nh, hp)
    dtc = dt.reshape(Bsz, nc, chunk, nh).astype(f32)
    Bc = Bm.reshape(Bsz, nc, chunk, G, N)
    Cc = Cm.reshape(Bsz, nc, chunk, G, N)

    a = dtc * A.astype(f32)[None, None, None]         # (B,nc,Q,nh), <0
    cum = jnp.cumsum(a, axis=2)                        # inclusive
    decay_last = jnp.exp(cum[:, :, -1])                # (B,nc,nh)

    # ---- intra-chunk (quadratic in chunk -> MXU-friendly) -----------------
    # CB[b,c,i,j,g] = C_i · B_j
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc.astype(f32), Bc.astype(f32))
    CBh = jnp.repeat(CB, hg, axis=-1)                  # (B,nc,Q,Q,nh)
    seg = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])  # (B,nc,i,j,nh)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    M = jnp.where(tri[None, None, :, :, None], CBh * seg, 0.0)
    M = M * dtc[:, :, None, :, :]                      # j-weighted by dt_j
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc.astype(f32))

    # ---- chunk boundary states -------------------------------------------
    # S_c[h,n,p] = sum_j exp(cum_last - cum_j) dt_j B_j[n] x_j[p]
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dtc         # (B,nc,Q,nh)
    Bh = jnp.repeat(Bc, hg, axis=3)                    # (B,nc,Q,nh,N)
    S_state = jnp.einsum("bcjh,bcjhn,bcjhp->bchnp",
                         w, Bh.astype(f32), xc.astype(f32))

    # ---- inter-chunk recurrence (associative scan over chunks) ------------
    dl = decay_last[:, :, :, None, None]               # (B,nc,nh,1,1)

    def comb(c1, c2):
        d1, s1 = c1
        d2, s2 = c2
        return d1 * d2, d2 * s1 + s2

    d_acc, s_acc = lax.associative_scan(comb, (dl, S_state), axis=1)
    # exclusive prefix: state entering chunk c (from local chunks only)
    h_in_local = jnp.concatenate(
        [jnp.zeros_like(s_acc[:, :1]), s_acc[:, :-1]], axis=1)
    d_prefix = jnp.concatenate(
        [jnp.ones_like(d_acc[:, :1]), d_acc[:, :-1]], axis=1)  # (B,nc,nh,1,1)

    # ---- cross-shard / carried-in state -----------------------------------
    decay_dev = d_acc[:, -1, :, 0, 0]                  # (B,nh) total local decay
    state_dev = s_acc[:, -1]                           # (B,nh,N,hp)
    if seq_axes:
        h0_in = shard_prefix_state(decay_dev[..., None, None], state_dev,
                                   seq_axes)
    else:
        h0_in = jnp.zeros_like(state_dev)
    if h0 is not None:
        # carried-in state decays through all shards preceding this one
        h0_in = h0_in + (_total_prefix_decay(decay_dev, seq_axes)[..., None, None]
                         * h0.astype(f32))

    h_in = h_in_local + d_prefix * h0_in[:, None]      # (B,nc,nh,N,hp)

    # ---- inter-chunk output contribution ----------------------------------
    Ch = jnp.repeat(Cc, hg, axis=3)                    # (B,nc,Q,nh,N)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp",
                         Ch.astype(f32) * jnp.exp(cum)[..., None], h_in)

    y = (y_intra + y_inter).reshape(Bsz, S, nh, hp)
    h_final = decay_dev[..., None, None] * h0_in + state_dev
    return y.astype(x.dtype), h_final


def _total_prefix_decay(decay_dev: Array, seq_axes: Sequence[str]) -> Array:
    """Product of decays over all shards strictly before this one."""
    if not seq_axes:
        return jnp.ones_like(decay_dev)
    d = decay_dev[None]
    for ax in reversed(tuple(seq_axes)):
        d = lax.all_gather(d, ax, axis=0, tiled=True)
    n = d.shape[0]
    rank = jnp.int32(0)
    for ax in seq_axes:
        rank = rank * lax.axis_size(ax) + lax.axis_index(ax)
    cum = jnp.cumprod(d, axis=0)
    prefix = jnp.concatenate([jnp.ones_like(cum[:1]), cum[:-1]], axis=0)
    return prefix[rank]


def ssd_step(
    x: Array,        # (B, nh, hp) single token
    dt: Array,       # (B, nh)
    A: Array,        # (nh,)
    Bm: Array,       # (B, G, N)
    Cm: Array,       # (B, G, N)
    h: Array,        # (B, nh, N, hp)
) -> Tuple[Array, Array]:
    """Single decode step of the SSD recurrence."""
    f32 = jnp.float32
    G = Bm.shape[1]
    hg = x.shape[1] // G
    decay = jnp.exp(dt.astype(f32) * A.astype(f32)[None])          # (B,nh)
    Bh = jnp.repeat(Bm, hg, axis=1).astype(f32)                     # (B,nh,N)
    Ch = jnp.repeat(Cm, hg, axis=1).astype(f32)
    upd = (dt.astype(f32)[..., None, None] * Bh[..., None]
           * x.astype(f32)[:, :, None, :])                          # (B,nh,N,hp)
    h_new = decay[..., None, None] * h + upd
    y = jnp.einsum("bhn,bhnp->bhp", Ch, h_new)
    return y.astype(x.dtype), h_new


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------

def rglru_scan(
    x: Array,          # (B, S, D) post-conv activations
    r: Array,          # (B, S, D) recurrence gate in (0,1)
    i: Array,          # (B, S, D) input gate in (0,1)
    log_a: Array,      # (D,) negative log-decay parameter (=-c*softplus(Λ))
    *,
    h0: Optional[Array] = None,
    seq_axes: Sequence[str] = (),
) -> Tuple[Array, Array]:
    """h_t = a_t h_{t-1} + sqrt(1-a_t^2) (i_t ⊙ x_t),  a_t = exp(log_a · r_t)."""
    f32 = jnp.float32
    log_at = log_a.astype(f32)[None, None] * r.astype(f32)  # (B,S,D) <= 0
    a = jnp.exp(log_at)
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_at), 0.0, 1.0)) \
        * (i.astype(f32) * x.astype(f32))

    def comb(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_acc, h_local = lax.associative_scan(comb, (a, b), axis=1)

    decay_dev = a_acc[:, -1]         # (B,D)
    state_dev = h_local[:, -1]       # (B,D)
    h_in = shard_prefix_state(decay_dev, state_dev, seq_axes) \
        if seq_axes else jnp.zeros_like(state_dev)
    if h0 is not None:
        h_in = h_in + _total_prefix_decay(decay_dev, seq_axes) * h0.astype(f32)
    h = h_local + a_acc * h_in[:, None]
    h_final = decay_dev * h_in + state_dev
    return h.astype(x.dtype), h_final


def rglru_step(x, r, i, log_a, h):
    """Single decode step.  x/r/i: (B, D); h: (B, D)."""
    f32 = jnp.float32
    log_at = log_a.astype(f32)[None] * r.astype(f32)
    a = jnp.exp(log_at)
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_at), 0.0, 1.0)) \
        * (i.astype(f32) * x.astype(f32))
    h_new = a * h + b
    return h_new.astype(x.dtype), h_new
