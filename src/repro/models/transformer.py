"""The model stack: pattern-scan decoder supporting all assigned families.

A model is a sequence of blocks tiled from ``cfg.pattern`` (e.g. gemma-3 is
5 local + 1 global per period; recurrentgemma is rec/rec/local).  Parameters
live in flat ZeRO buffers:

  embed   : (E_pad,)            token embedding (absent for stub-fed archs)
  blocks  : (n_periods, P_pad)  scanned, one period of the pattern per step
  rem     : (R_pad,)            the L % period leftover layers (if any)
  head    : (H_pad,)            final norm + unembed

Every group is applied through the ZeRO++ engine (``zero_apply``), so each
scan step performs: qwZ-gather(period params) → compute → [bwd: hpZ gather +
qgZ reduce-scatter].  Activations shard batch over ``batch_axes`` and
sequence over ``seq_axes``; decode KV caches shard their sequence dim over
``kv_axes``.  Modality frontends (audio EnCodec frames, VLM patches) are
STUBS: the input pipeline provides precomputed embeddings (and M-RoPE
position streams) directly, per the assignment.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.core.partition import ParamSpec
from repro.core.zeropp import ZeroConfig, zero_apply, zero_apply_inference
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

Array = jax.Array


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _attn_entries(cfg: ArchConfig, pre: str) -> List[Tuple[str, Tuple[int, ...]]]:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    e = [(pre + "ln1", (d,)),
         (pre + "wq", (d, H * hd)), (pre + "wk", (d, K * hd)),
         (pre + "wv", (d, K * hd)), (pre + "wo", (H * hd, d))]
    if cfg.qkv_bias:
        e += [(pre + "bq", (H * hd,)), (pre + "bk", (K * hd,)),
              (pre + "bv", (K * hd,))]
    if cfg.qk_norm:
        e += [(pre + "qn", (hd,)), (pre + "kn", (hd,))]
    return e


def _mlp_entries(cfg: ArchConfig, pre: str) -> List[Tuple[str, Tuple[int, ...]]]:
    d = cfg.d_model
    return [(pre + "ln2", (d,)), (pre + "wgu", (d, 2 * cfg.d_ff)),
            (pre + "wdn", (cfg.d_ff, d))]


def _moe_entries(cfg: ArchConfig, pre: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Router + shared experts only: the routed expert weights live in their
    own chunked parameter groups (see :func:`expert_entries`) so the engine
    gathers them a chunk at a time instead of all E experts at once."""
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_ff
    e = [(pre + "ln2", (d,)), (pre + "router", (d, E))]
    if cfg.n_shared:
        e += [(pre + "sgu", (d, 2 * f * cfg.n_shared)),
              (pre + "sdn", (f * cfg.n_shared, d))]
    return e


def expert_entries(cfg: ArchConfig) -> List[Tuple[str, Tuple[int, ...]]]:
    """One expert CHUNK's parameters (E/expert_chunks experts)."""
    d, f = cfg.d_model, cfg.moe_ff
    ec = cfg.n_experts // cfg.expert_chunks
    return [("egu", (ec, d, 2 * f)), ("edn", (ec, f, d))]


def _ssd_entries(cfg: ArchConfig, pre: str) -> List[Tuple[str, Tuple[int, ...]]]:
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    return [(pre + "ln", (d,)),
            (pre + "inp", (d, 2 * di + 2 * gn + nh)),
            (pre + "cw", (cfg.conv_width, cfg.conv_dim)),
            (pre + "alog", (nh,)), (pre + "dskip", (nh,)),
            (pre + "dtb", (nh,)),
            (pre + "onrm", (di,)), (pre + "outp", (di, d))]


def _rec_entries(cfg: ArchConfig, pre: str) -> List[Tuple[str, Tuple[int, ...]]]:
    d, dr = cfg.d_model, cfg.d_rnn
    return [(pre + "ln1", (d,)),
            (pre + "px", (d, dr)), (pre + "pg", (d, dr)),
            (pre + "cw", (cfg.conv_width, dr)),
            (pre + "wa", (dr, dr)), (pre + "ba", (dr,)),
            (pre + "wx", (dr, dr)), (pre + "bx", (dr,)),
            (pre + "loga", (dr,)),
            (pre + "po", (dr, d))] + _mlp_entries(cfg, pre)


def block_entries(cfg: ArchConfig, kind: str, pre: str):
    if kind in ("attn", "local"):
        return _attn_entries(cfg, pre) + _mlp_entries(cfg, pre)
    if kind == "moe":
        return _attn_entries(cfg, pre) + _moe_entries(cfg, pre)
    if kind == "ssd":
        return _ssd_entries(cfg, pre)
    if kind == "rec":
        return _rec_entries(cfg, pre)
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RunSpec:
    """Static run-mode description (shardings + mode)."""
    mode: str = "train"                # train | prefill | decode | paged
    seq_axes: Tuple[str, ...] = ()     # activation sequence sharding
    kv_axes: Tuple[str, ...] = ()      # cache sequence sharding
    kv_len: int = 0                    # decode: global cache capacity
    attn_impl: str = "xla"             # xla | pallas (flash kernel)


def _sub(p: Dict[str, Array], pre: str) -> Dict[str, Array]:
    n = len(pre)
    return {k[n:]: v for k, v in p.items() if k.startswith(pre)}


def _attn_block(cfg, kind, p, h, rs: RunSpec, pos, cache):
    """Attention mixer (+ cache handling); returns (mix_out, new_cache)."""
    B, S, d = h.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    hn = nn.rms_norm(h, p["ln1"])
    q = hn @ p["wq"]
    k = hn @ p["wk"]
    v = hn @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = nn.rms_norm(q, p["qn"])
        k = nn.rms_norm(k, p["kn"])
    cos, sin = pos["rope"]
    q = nn.apply_rope(q, cos, sin)
    k = nn.apply_rope(k, cos, sin)

    window = cfg.window if kind == "local" else 0
    if rs.mode == "paged":
        # Paged serving: the cache is a page arena shared by every slot,
        # addressed through pos["page_table"].  One step shape covers
        # decode (T=1), speculative verify (T=gamma+1) and chunked prefill
        # (B=1, T=chunk): insert the chunk's keys, then attend causally at
        # pos["positions"].  Sliding-window layers keep the slab ring
        # buffer path — the engine gates paged mode to attn-only stacks.
        assert kind != "local", "paged serving does not support window layers"
        kc, vc = attn.paged_insert(cache["k"], cache["v"], k, v,
                                   pos["positions"], pos["page_table"],
                                   rs.kv_axes)
        o = attn.paged_attend(q, kc, vc, pos["positions"],
                              pos["page_table"], kv_seq_axes=rs.kv_axes,
                              logit_softcap=cfg.logit_softcap)
        new_cache = {"k": kc, "v": vc}
    elif rs.mode == "decode":
        cap_g = cache["k"].shape[1] * _axes_prod(rs.kv_axes)  # global capacity
        t = attn.per_seq_pos(pos["cache_pos"], B)        # (B,)
        slot = jnp.mod(t, cap_g)                         # (B,)
        kc, vc = attn.cache_insert(cache["k"], cache["v"], k, v, slot,
                                   rs.kv_axes)
        off = attn.seq_shard_offset(kc.shape[1], rs.kv_axes)
        gslot = off + jnp.arange(kc.shape[1])             # (S_loc,)
        # ring slot -> global position, per sequence: (B, S_loc)
        spos = t[:, None] - jnp.mod(t[:, None] - gslot[None, :], cap_g)
        o = attn.decode_attend(q, kc, vc, t, kv_seq_axes=rs.kv_axes,
                               window=window,
                               logit_softcap=cfg.logit_softcap,
                               slot_positions=spos)
        new_cache = {"k": kc, "v": vc}
    else:
        o = attn.mha(q, k, v, seq_axes=rs.seq_axes, causal=True,
                     window=window, logit_softcap=cfg.logit_softcap,
                     impl=rs.attn_impl)
        new_cache = _build_prefill_cache(cfg, kind, k, v, rs) \
            if rs.mode == "prefill" else cache
    o = o.reshape(B, S, H * hd) @ p["wo"]
    return o, new_cache


def _chunk_for(S: int, chunk: int) -> int:
    """Largest divisor of S that is <= chunk (SSD chunk must tile S)."""
    c = min(chunk, S)
    while S % c:
        c -= 1
    return c


def _axes_prod(axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= lax.axis_size(a)
    return n


def _build_prefill_cache(cfg, kind, k, v, rs: RunSpec):
    """Convert prefill K/V shards into the decode cache layout."""
    B, S_loc, K, hd = k.shape
    if kind in ("attn", "moe"):
        # full cache, same sharding as prefill activations (kv_axes==seq_axes)
        return {"k": k, "v": v}
    # local layer: ring buffer of the last `window` positions
    W = cfg.window
    kg = k
    vg = v
    for ax in rs.seq_axes:
        kg = lax.all_gather(kg, ax, axis=1, tiled=True)
        vg = lax.all_gather(vg, ax, axis=1, tiled=True)
    S = kg.shape[1]
    slots = jnp.arange(W)
    src = (S - 1) - jnp.mod((S - 1) - slots, W)   # position held by each slot
    kr = jnp.take(kg, src, axis=1)
    vr = jnp.take(vg, src, axis=1)
    # keep only this device's slot shard
    n = _axes_prod(rs.kv_axes)
    loc = W // n
    off = attn.seq_shard_offset(loc, rs.kv_axes)
    return {"k": lax.dynamic_slice_in_dim(kr, off, loc, axis=1),
            "v": lax.dynamic_slice_in_dim(vr, off, loc, axis=1)}


def _mlp_block(cfg, kind, p, h, rs: RunSpec):
    """Feed-forward half (dense); returns (out, aux)."""
    hn = nn.rms_norm(h, p["ln2"])
    return nn.swiglu(hn, p["wgu"], p["wdn"], act=cfg.act), jnp.float32(0)


def moe_pre_block(cfg, p, h, rs: RunSpec, pos, cache):
    """MoE layer up to (and excluding) the routed experts.

    Runs under ONE zero_apply gather: attention + post-attn norm + router
    logits + shared experts.  Returns everything the (separately gathered)
    expert chunks need: (h_after_attn, hn2d, router_logits, shared_y,
    new_cache).
    """
    B, S, d = h.shape
    mix, new_cache = _attn_block(cfg, "moe", p, h, rs, pos, cache)
    h = h + mix
    hn = nn.rms_norm(h, p["ln2"])
    hn2 = hn.reshape(B * S, d)
    logits = hn2 @ p["router"]
    if cfg.n_shared:
        shared_y = moe_lib.shared_ffn(hn2, p["sgu"], p["sdn"]).reshape(B, S, d)
    else:
        shared_y = jnp.zeros_like(h)
    return h, hn2, logits, shared_y, new_cache


def _ssd_block(cfg, p, h, rs: RunSpec, cache):
    B, S, d = h.shape
    di, nh, hp = cfg.d_inner, cfg.ssm_heads, cfg.ssm_headdim
    G, N, Wc = cfg.ssm_groups, cfg.ssm_state, cfg.conv_width
    gn = G * N
    hn = nn.rms_norm(h, p["ln"])
    zxbcdt = hn @ p["inp"]
    z, xBC, dt_raw = jnp.split(zxbcdt, [di, di + cfg.conv_dim], axis=-1)
    # xBC = [x (di), B (gn), C (gn)] passed through the causal conv together
    if rs.mode == "decode":
        carry = cache["conv"]
        y_c, new_conv = nn.causal_conv1d(xBC, p["cw"], carry)
    else:
        halo = ssm_lib.gather_conv_halo(xBC, Wc - 1, rs.seq_axes)
        y_c, tail = nn.causal_conv1d(xBC, p["cw"], halo)
        new_conv = tail
    xBC = jax.nn.silu(y_c)
    x, Bm, Cm = jnp.split(xBC, [di, di + gn], axis=-1)
    x = x.reshape(B, S, nh, hp)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dtb"].astype(jnp.float32))

    if rs.mode == "decode":
        y, h_new = ssm_lib.ssd_step(x[:, 0], dt[:, 0], -jnp.exp(p["alog"]),
                                    Bm[:, 0], Cm[:, 0], cache["h"])
        y = y[:, None]
        new_cache = {"h": h_new, "conv": new_conv}
    else:
        h0 = cache["h"] if (cache and "h" in cache) else None
        y, h_fin = ssm_lib.ssd_scan(x, dt, -jnp.exp(p["alog"]), Bm, Cm,
                                    chunk=_chunk_for(S, cfg.ssm_chunk), h0=h0,
                                    seq_axes=rs.seq_axes)
        new_cache = None
        if rs.mode == "prefill":
            new_cache = {"h": _last_shard_value(h_fin, rs.seq_axes),
                         "conv": _last_shard_value(new_conv, rs.seq_axes)}
    y = y + p["dskip"].astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(h.dtype)
    y = nn.rms_norm(y * jax.nn.silu(z), p["onrm"])
    return y @ p["outp"], new_cache


def select_positions(h: Array, pos: Array, seq_axes: Sequence[str]) -> Array:
    """Per-sequence select: h[b, pos[b], :] under sequence sharding.

    h: (B, S_loc, d), pos: (B,) GLOBAL positions.  Each device one-hot
    reduces its local shard (exact: the sum touches one 1.0 and zeros) and
    the owner's value is psum-combined.  Returns (B, 1, d).  Prefill uses
    this to read last-REAL-token logits from right-padded prompts
    (serve/engine.py buckets prompt lengths, so S may exceed the prompt).
    """
    B, S_loc, _ = h.shape
    off = attn.seq_shard_offset(S_loc, seq_axes)
    idx = pos - off                                   # (B,) local index
    oh = (jnp.arange(S_loc)[None, :] == idx[:, None]).astype(h.dtype)
    v = jnp.einsum("bs,bsd->bd", oh, h)[:, None, :]
    if seq_axes:
        v = lax.psum(v, tuple(seq_axes))
    return v


def _last_shard_value(x: Array, seq_axes: Sequence[str]) -> Array:
    """Replicate the LAST sequence shard's value to all shards (state handoff)."""
    if not seq_axes:
        return x
    n = _axes_prod(seq_axes)
    rank = jnp.int32(0)
    for ax in seq_axes:
        rank = rank * lax.axis_size(ax) + lax.axis_index(ax)
    sel = (rank == n - 1).astype(x.dtype)
    return lax.psum(x * sel, tuple(seq_axes))


def _rec_block(cfg, p, h, rs: RunSpec, cache):
    B, S, d = h.shape
    dr, Wc = cfg.d_rnn, cfg.conv_width
    hn = nn.rms_norm(h, p["ln1"])
    xb = hn @ p["px"]
    gate = hn @ p["pg"]
    if rs.mode == "decode":
        xc, new_conv = nn.causal_conv1d(xb, p["cw"], cache["conv"])
    else:
        halo = ssm_lib.gather_conv_halo(xb, Wc - 1, rs.seq_axes)
        xc, tail = nn.causal_conv1d(xb, p["cw"], halo)
        new_conv = tail
    r = jax.nn.sigmoid(xc @ p["wa"] + p["ba"])
    i = jax.nn.sigmoid(xc @ p["wx"] + p["bx"])
    if rs.mode == "decode":
        y, h_new = ssm_lib.rglru_step(xc[:, 0], r[:, 0], i[:, 0], p["loga"],
                                      cache["h"])
        y = y[:, None]
        new_cache = {"h": h_new, "conv": new_conv}
    else:
        h0 = cache["h"] if (cache and "h" in cache) else None
        y, h_fin = ssm_lib.rglru_scan(xc, r, i, p["loga"], h0=h0,
                                      seq_axes=rs.seq_axes)
        new_cache = None
        if rs.mode == "prefill":
            new_cache = {"h": _last_shard_value(h_fin, rs.seq_axes),
                         "conv": _last_shard_value(new_conv, rs.seq_axes)}
    mix = (y * jax.nn.gelu(gate, approximate=True)) @ p["po"]
    return mix, new_cache


def apply_block(cfg: ArchConfig, kind: str, p: Dict[str, Array], h: Array,
                rs: RunSpec, pos, cache):
    """One block with residuals; returns (h, new_cache, aux).

    ``moe`` blocks are driven by the Model directly (moe_pre_block +
    chunked expert gathers + moe_combine), not through this helper.
    """
    aux = jnp.float32(0)
    if kind in ("attn", "local"):
        mix, new_cache = _attn_block(cfg, kind, p, h, rs, pos, cache)
        h = h + mix
        y, aux = _mlp_block(cfg, kind, p, h, rs)
        h = h + y
    elif kind == "ssd":
        mix, new_cache = _ssd_block(cfg, p, h, rs, cache)
        h = h + mix
    elif kind == "rec":
        mix, new_cache = _rec_block(cfg, p, h, rs, cache)
        h = h + mix
        y, _ = _mlp_block(cfg, "dense", p, h, rs)
        h = h + y
    else:
        raise ValueError(kind)
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# cache construction (global shapes, outside shard_map)
# ---------------------------------------------------------------------------

def init_cache_shapes(cfg: ArchConfig, kind: str, batch: int, kv_len: int,
                      dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    K, hd = cfg.n_kv_heads, cfg.d_head
    if kind == "attn":
        s = (batch, kv_len, K, hd)
        return {"k": jax.ShapeDtypeStruct(s, dtype),
                "v": jax.ShapeDtypeStruct(s, dtype)}
    if kind == "local":
        s = (batch, min(cfg.window, kv_len), K, hd)
        return {"k": jax.ShapeDtypeStruct(s, dtype),
                "v": jax.ShapeDtypeStruct(s, dtype)}
    if kind == "ssd":
        return {"h": jax.ShapeDtypeStruct(
                    (batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim),
                    jnp.float32),
                "conv": jax.ShapeDtypeStruct(
                    (batch, cfg.conv_width - 1, cfg.conv_dim), dtype)}
    if kind == "rec":
        return {"h": jax.ShapeDtypeStruct((batch, cfg.d_rnn), jnp.float32),
                "conv": jax.ShapeDtypeStruct(
                    (batch, cfg.conv_width - 1, cfg.d_rnn), dtype)}
    if kind == "moe":
        return init_cache_shapes(cfg, "attn", batch, kv_len, dtype)
    raise ValueError(kind)
