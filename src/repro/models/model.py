"""Model driver: parameter groups, init, loss / prefill / decode.

``Model`` owns the flat ZeRO parameter groups and drives the pattern scan
over blocks through the ZeRO++ engine.  It is mode- and mesh-agnostic:
the trainer/server wraps its methods in shard_map; smoke tests call them
directly with ``ZeroConfig.local()``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig
from repro.core.partition import ParamSpec
from repro.core.schedule import (zero_apply_scan, zero_chunk_scan,
                                 zero_chunk_scan_hpz,
                                 zero_chunk_scan_inference,
                                 zero_scan_inference)
from repro.core.zeropp import (
    ZeroConfig,
    fwd_gather_quant,
    qwz_gemm_eligible,
    zero_apply,
    zero_apply_inference,
)
from repro.kernels import ops as kops
from repro.models import attention as attn_lib
from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.models.transformer import (RunSpec, apply_block, block_entries,
                                      expert_entries, init_cache_shapes,
                                      moe_pre_block, _sub)

Array = jax.Array


def _inv_softplus(y):
    return float(np.log(np.expm1(y)))


def _spec_chunk0(xs, i):
    """Speculative-gather source for the MoE layer ring: layer ``i``'s
    FIRST expert-chunk primary shard (routing-ahead dispatch — experts
    are gathered in full regardless of routing, so the gather can issue
    under earlier layers' compute).  ``xs`` is the layer scan's stacked
    inputs: the expert stack itself (train/prefill) or (experts, caches)
    (decode)."""
    eflat = xs[0] if isinstance(xs, tuple) else xs
    return lax.dynamic_index_in_dim(eflat, i, axis=0, keepdims=False)[0]


def _bwd_spec_chunk0(auxs, i):
    """Backward mirror of :func:`_spec_chunk0` for the reverse ring:
    layer ``i``'s first expert-chunk SECONDARY shard, drawn from the
    stacked per-layer residuals (the sec stacks saved by the forward).
    The outer backward scan hpZ-gathers it one iteration early so the
    nested recompute's chunk ring seeds from a ring slot instead of
    issuing its own synchronous fast-tier gather."""
    return lax.dynamic_index_in_dim(auxs, i, axis=0, keepdims=False)[0]


class Model:
    def __init__(self, cfg: ArchConfig, zcfg: ZeroConfig, world: int = 1):
        self.cfg = cfg
        self.zcfg = zcfg
        self.world = world
        period = cfg.pattern
        self.period = period
        self.n_periods = cfg.n_layers // len(period)
        self.rem = cfg.n_layers % len(period)
        align = zcfg.align(world) if zcfg.distributed else zcfg.align(1)

        self.is_moe = "moe" in period
        if self.is_moe:
            # assigned MoE archs are pure-MoE stacks; the chunked expert
            # path assumes one MoE layer per scan step
            assert period == ("moe",), "moe must be the whole pattern"
            self.expert_spec = ParamSpec(tuple(expert_entries(cfg)),
                                         align=align)
        else:
            self.expert_spec = None

        pe: List = []
        for i, kind in enumerate(period):
            pe += block_entries(cfg, kind, f"{i}.")
        self.period_spec = ParamSpec(tuple(pe), align=align)
        if self.rem:
            re_ = []
            for i, kind in enumerate(period[: self.rem]):
                re_ += block_entries(cfg, kind, f"{i}.")
            self.rem_spec = ParamSpec(tuple(re_), align=align)
        else:
            self.rem_spec = None
        if not cfg.embed_inputs:
            self.embed_spec = ParamSpec((("emb", (cfg.vocab, cfg.d_model)),),
                                        align=align)
        else:
            self.embed_spec = None
        self.head_spec = ParamSpec((("fnorm", (cfg.d_model,)),), align=align)
        # unembedding: TRANSPOSED (V, d), split into vocab-row chunks that
        # are gathered one at a time (streaming log-sum-exp across chunks)
        nv = cfg.unemb_chunks or self._auto_unemb_chunks()
        assert cfg.vocab % nv == 0, (cfg.vocab, nv)
        self.unemb_chunks = nv
        self.vchunk = cfg.vocab // nv
        self.unemb_spec = ParamSpec(
            (("unemb", (self.vchunk, cfg.d_model)),), align=align)

        self.n_moe_layers = sum(1 for k in period for _ in [0] if k == "moe") \
            * self.n_periods + sum(1 for k in period[: self.rem] if k == "moe")

    def with_prefetch(self, k: int) -> "Model":
        """A shallow copy of this model with ring depth ``k`` (layer AND
        chunk scans).  Specs are shared (immutable); only the schedule
        changes — serving uses this to deepen the decode-path ring on
        slow interconnects without rebuilding the model."""
        import copy
        m = copy.copy(self)
        m.zcfg = dataclasses.replace(self.zcfg, prefetch=k)
        return m

    def _auto_unemb_chunks(self, target_bytes: int = 512 * 2 ** 20) -> int:
        cfg = self.cfg
        total = cfg.vocab * cfg.d_model * 2  # bf16 gathered
        want = max(1, -(-total // target_bytes))
        # floor of 4 for big vocabularies: the streaming-LSE logits tile is
        # (T, V/nv) fp32, so nv also bounds the logits working set
        if cfg.vocab >= 32768:
            want = max(want, 4)
        nv = want
        while cfg.vocab % nv:
            nv += 1
        return nv

    # ------------------------------------------------------------------ init

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """GLOBAL flat buffer shapes (dry-run uses these directly)."""
        out: Dict[str, Tuple[int, ...]] = {}
        if self.embed_spec:
            out["embed"] = (self.embed_spec.padded_size,)
        out["blocks"] = (self.n_periods, self.period_spec.padded_size)
        if self.is_moe:
            out["experts"] = (self.n_periods, self.cfg.expert_chunks,
                              self.expert_spec.padded_size)
        if self.rem_spec:
            out["rem"] = (self.rem_spec.padded_size,)
        out["head"] = (self.head_spec.padded_size,)
        out["unemb"] = (self.unemb_chunks, self.unemb_spec.padded_size)
        return out

    def n_params(self) -> int:
        n = self.period_spec.size * self.n_periods + self.head_spec.size
        n += self.unemb_spec.size * self.unemb_chunks
        if self.is_moe:
            n += self.expert_spec.size * self.cfg.expert_chunks \
                * self.n_periods
        if self.rem_spec:
            n += self.rem_spec.size
        if self.embed_spec:
            n += self.embed_spec.size
        return n

    def comm_events(self, accum: int = 1) -> list:
        """Enumerate every ZeRO engine collective one training step issues.

        Returns ``[{"kind", "elems", "count", "site"}, ...]`` where kind is
        fwd_gather / bwd_gather / grad_reduce, elems the GLOBAL flat buffer
        length, and count how many times that collective runs per step.
        ``zeropp.step_wire_by_label`` folds this into per-label wire bytes —
        the analytic projection that the runtime jaxpr-measured counters
        are gated against (obs/report.py), so the counting here mirrors
        core/schedule.py exactly:

          * a depth-k layer/chunk ring issues n + k gathers per phase
            (k ring-seed + n body prefetches) and n + k reduces (the first
            k are the ring's dummy zero-reduces — still real wire);
          * a W0-seeded chunk ring (speculative chunk-0 buffer) skips one
            seed gather;
          * the synchronous path (effective prefetch 0) issues exactly n;
          * with hpZ, backward re-gathers ride the fast tier, EXCEPT the
            MoE prefetch-0 nested recompute, whose per-chunk zero_apply
            re-runs the qwZ forward gather before its hpZ backward one.
        """
        z = self.zcfg
        ev: list = []
        if not z.distributed:
            return ev

        def add(kind, elems, count, site):
            if count > 0:
                ev.append({"kind": kind, "elems": int(elems),
                           "count": float(count) * accum, "site": site})

        # single zero_apply sites: 1 gather / 1 bwd gather / 1 reduce each
        sites = []
        if self.embed_spec:
            sites.append(("embed", self.embed_spec.padded_size, 1))
        if self.rem_spec:
            sites.append(("rem", self.rem_spec.padded_size, 1))
        sites.append(("head", self.head_spec.padded_size, 1))
        sites.append(("unemb", self.unemb_spec.padded_size,
                      self.unemb_chunks))
        for site, e, c in sites:
            add("fwd_gather", e, c, site)
            add("bwd_gather", e, c, site)
            add("grad_reduce", e, c, site)

        n = self.n_periods
        k = z.effective_prefetch(n)
        P = self.period_spec.padded_size
        add("fwd_gather", P, n + k, "blocks.fwd")
        add("bwd_gather", P, n + k, "blocks.bwd")
        add("grad_reduce", P, n + k, "blocks.reduce")

        if not self.is_moe:
            return ev

        nc = self.cfg.expert_chunks
        kc = z.effective_prefetch(nc)
        E = self.expert_spec.padded_size
        hpz_remat = z.hpz and z.distributed
        spec_on = k >= 1 and kc >= 1  # routing-ahead chunk-0 ring active

        if spec_on:
            add("fwd_gather", E, n + k, "blocks.spec")
        # chunk pipeline, forward: W0 seed skip when the spec ring feeds it
        add("fwd_gather", E, n * (nc + kc - (1 if spec_on else 0)),
            "experts.fwd")

        if k >= 1:
            if hpz_remat:
                # nested hpZ recompute (zero_chunk_scan_hpz): its own fwd
                # replay + its bwd ring, all on the fast tier
                if spec_on:
                    add("bwd_gather", E, n + k, "blocks.bwd_spec")
                add("bwd_gather", E,
                    n * (nc + kc - (1 if spec_on else 0)),
                    "experts.bwd_recompute")
                add("bwd_gather", E, n * (nc + kc), "experts.bwd")
            else:
                # recompute differentiates plain zero_chunk_scan: a fresh
                # forward pass (qwZ tier) plus its backward ring
                add("fwd_gather", E, n * (nc + kc), "experts.bwd_recompute")
                add("bwd_gather", E, n * (nc + kc), "experts.bwd")
            add("grad_reduce", E, n * (nc + kc), "experts.reduce")
        else:
            # prefetch-0: per-layer zero_apply recompute runs each chunk's
            # own zero_apply — qwZ fwd re-gather THEN hpZ/bwd gather
            add("fwd_gather", E, n * nc, "experts.bwd_recompute")
            add("bwd_gather", E, n * nc, "experts.bwd")
            add("grad_reduce", E, n * nc, "experts.reduce")
        return ev

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: shared + top_k experts)."""
        cfg = self.cfg
        if not cfg.n_experts:
            return self.n_params()
        per_expert = 3 * cfg.d_model * cfg.moe_ff
        inactive = (cfg.n_experts - cfg.top_k) * per_expert
        return self.n_params() - inactive * cfg.n_layers

    def _init_fn(self, name: str):
        cfg = self.cfg
        base = name.split(".")[-1]
        rng_scaled = lambda std: (lambda k, s: jax.random.normal(k, s) * std)
        if base == "emb":
            return rng_scaled(0.02)
        if base == "unemb":  # stored (V_chunk, d): scale by 1/sqrt(d)
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[-1])
        if base in ("wq", "wk", "wv", "wgu", "router", "px", "pg", "wa",
                    "wx", "inp"):
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[0])
        if base in ("wo", "wdn", "po", "outp", "sdn"):
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[0])
        if base in ("egu", "sgu"):
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[-2])
        if base == "edn":
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[-2])
        if base == "cw":
            return lambda k, s: jax.random.normal(k, s) / np.sqrt(s[0])
        if base == "alog":
            return lambda k, s: jnp.log(jax.random.uniform(k, s, minval=1.0,
                                                           maxval=16.0))
        if base == "dskip":
            return lambda k, s: jnp.ones(s)
        if base == "dtb":
            lo, hi = _inv_softplus(1e-3), _inv_softplus(0.1)
            return lambda k, s: jax.random.uniform(k, s, minval=lo, maxval=hi)
        if base == "loga":
            return lambda k, s: jax.random.uniform(k, s, minval=-0.8,
                                                   maxval=-0.01)
        return None  # zeros: norms, biases

    def init_params(self, key: Array, dtype=None) -> Dict[str, Array]:
        """GLOBAL flat buffers (small models / examples; dry-run never calls)."""
        dtype = dtype or self.zcfg.param_dtype
        out: Dict[str, Array] = {}
        ks = jax.random.split(key, 4 + 2 * self.n_periods)
        if self.embed_spec:
            fns = {n: self._init_fn(n) for n, _ in self.embed_spec.entries}
            out["embed"] = self.embed_spec.init(ks[0], fns, jnp.float32).astype(dtype)
        bufs = []
        fns = {n: self._init_fn(n) for n, _ in self.period_spec.entries}
        for g in range(self.n_periods):
            bufs.append(self.period_spec.init(ks[2 + g], fns, jnp.float32))
        out["blocks"] = jnp.stack(bufs).astype(dtype)
        if self.is_moe:
            efns = {n: self._init_fn(n) for n, _ in self.expert_spec.entries}
            ebufs = []
            for g in range(self.n_periods):
                kc = jax.random.split(ks[2 + self.n_periods + g],
                                      self.cfg.expert_chunks)
                ebufs.append(jnp.stack([
                    self.expert_spec.init(kc[c], efns, jnp.float32)
                    for c in range(self.cfg.expert_chunks)]))
            out["experts"] = jnp.stack(ebufs).astype(dtype)
        if self.rem_spec:
            fns = {n: self._init_fn(n) for n, _ in self.rem_spec.entries}
            out["rem"] = self.rem_spec.init(ks[1], fns, jnp.float32).astype(dtype)
        fns = {n: self._init_fn(n) for n, _ in self.head_spec.entries}
        out["head"] = self.head_spec.init(ks[-1], fns, jnp.float32).astype(dtype)
        ufns = {n: self._init_fn(n) for n, _ in self.unemb_spec.entries}
        kv = jax.random.split(ks[-2], self.unemb_chunks)
        out["unemb"] = jnp.stack([
            self.unemb_spec.init(kv[c], ufns, jnp.float32)
            for c in range(self.unemb_chunks)]).astype(dtype)
        return out

    # ------------------------------------------------------------- positions

    def _rope_tables(self, batch: Dict[str, Array], rs: RunSpec,
                     s_local: int, cache_pos: Optional[Array] = None):
        cfg = self.cfg
        if cfg.mrope:
            pos = batch["positions"]  # (3, B, S_loc) from the frontend stub
            cos, sin = nn.mrope_tables(pos, cfg.d_head, cfg.rope_theta)
        else:
            if rs.mode == "decode":
                # per-sequence positions: (B,) -> (B, 1) rope tables
                p = cache_pos[:, None]
            else:
                p = attn_lib.seq_shard_offset(s_local, rs.seq_axes) \
                    + jnp.arange(s_local)
            cos, sin = nn.rope_table(p, cfg.d_head, cfg.rope_theta)
        return lax.stop_gradient(cos), lax.stop_gradient(sin)

    # ----------------------------------------------------------- moe layer

    def _moe_layer(self, rs: RunSpec, train: bool, W, eflat, h, cos, sin,
                   cache_pos, cache, W_spec=None, sec=None,
                   collect_sec: bool = False):
        """One MoE layer given the layer's already-gathered shared weights.

        The LAYER-level engine (zero_apply_scan for training,
        zero_scan_inference for serving) owns the shared-param gather: with
        ``prefetch=k>=1`` layer i+k's qwZ gather is in flight under this
        layer's routing/expert compute, and in backward the hpZ gathers /
        qgZ reduces of the shared params ride the mirrored reverse ring
        exactly like a dense block.  Inside the layer:

          pre     (gathered): attn + ln2 + router logits + shared experts
          dispatch (pure):    sort-based token->slot routing, indices only
          chunks  (nc-deep zero_chunk_scan): each chunk rebuilds its slot
                              buffer from the token activations and runs
                              the grouped GEMMs; chunk c+k's expert-weight
                              gather is issued under chunk c's expert_ffn
                              (prefetch=0: synchronous per-chunk gathers)
          combine (pure):     gated scatter back to tokens

        Three engine-owned hooks (see core/schedule.py, DESIGN.md §3):

          * ``W_spec`` — layer chunk 0's expert weights, pre-gathered by
            the outer ring under the PREVIOUS layers' compute (routing-
            ahead dispatch: experts are gathered in full regardless of
            routing, so the gather need not wait for the router).  Chunk 0
            seeds the chunk ring from it; without it, dispatch gates the
            first gather.  Every expert-weight byte after chunk 0 is
            ring-buffered either way.
          * ``collect_sec`` — also return the stack of per-chunk secondary
            (hpZ) shards, to be threaded through the outer scan's
            residuals.
          * ``sec`` — a saved secondary stack: the chunk pipeline replays
            from it on the hpZ fast tier (zero_chunk_scan_hpz) instead of
            re-gathering on qwZ — the nested-recompute path.

        Keeping only (h, hn2, indices) as inter-gather values bounds the
        per-layer activation residual to O(T·d), not O(T·k·capacity·d).
        Returns (h_out, new_cache, aux_loss, sec_stack-or-None).
        """
        cfg, z = self.cfg, self.zcfg
        B, S = h.shape[0], h.shape[1]
        d = cfg.d_model
        nc = cfg.expert_chunks
        Ec = cfg.n_experts // nc

        p = _sub(self.period_spec.unpack(W.astype(z.compute_dtype)), "0.")
        posd = {"rope": (cos, sin), "cache_pos": cache_pos}
        h2, hn2, logits, shared_y, new_cache = moe_pre_block(
            cfg, p, h, rs, posd, cache)

        capacity = None
        if rs.mode != "train":  # serving must be drop-free (decode==prefill)
            capacity = moe_lib.serve_capacity(
                hn2.shape[0], cfg.top_k, cfg.n_experts)
        disp = moe_lib.moe_dispatch(
            hn2, logits, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, capacity=capacity)
        chunk_slots = Ec * disp.cap

        def chunk_f(Wc, c, hn2, dest, src_tok, g_sorted):
            pc = self.expert_spec.unpack(Wc.astype(z.compute_dtype))
            buf = moe_lib.build_chunk_buf(hn2, dest, src_tok,
                                          c * chunk_slots, chunk_slots)
            out = moe_lib.expert_ffn(buf.reshape(Ec, disp.cap, d),
                                     pc["egu"], pc["edn"])
            # gate multiply INSIDE the chunk: router grads come from the
            # chunk's own recompute, and the outer combine stays index-only
            g = moe_lib.build_chunk_gates(g_sorted, dest, c * chunk_slots,
                                          chunk_slots)
            return out * g.reshape(Ec, disp.cap, 1).astype(out.dtype)

        cidx = jnp.arange(nc, dtype=jnp.int32)
        sec_out = None
        if not train:
            outs = zero_chunk_scan_inference(chunk_f, z)(
                eflat, cidx, hn2, disp.dest, disp.src_tok, disp.g_sorted,
                W0=W_spec)
        elif sec is not None:
            # nested recompute: replay the chunk pipeline from the saved
            # secondary shards — every gather on the hpZ fast tier.
            # W_spec here is the outer bwd_spec ring's pre-gathered
            # chunk-0 buffer (None on the unprefetched path).
            outs = zero_chunk_scan_hpz(chunk_f, z)(
                eflat, sec, cidx, hn2, disp.dest, disp.src_tok,
                disp.g_sorted, W0=W_spec)
        elif collect_sec:
            outs, sec_out = zero_chunk_scan(chunk_f, z,
                                            collect_secondary=True)(
                eflat, cidx, hn2, disp.dest, disp.src_tok, disp.g_sorted,
                W0=W_spec)
        else:
            outs = zero_chunk_scan(chunk_f, z)(
                eflat, cidx, hn2, disp.dest, disp.src_tok, disp.g_sorted,
                W0=W_spec)
        y = moe_lib.moe_combine(outs.reshape(cfg.n_experts, disp.cap, d),
                                disp)
        h3 = h2 + shared_y + y.reshape(B, S, d).astype(h2.dtype)
        return h3, new_cache, disp.aux_loss, sec_out

    def _moe_inference_scan(self, moe_f):
        """Layer scan for the serving MoE stack: routing-ahead speculative
        chunk-0 gather when the chunk ring can be seeded from it (nc >= 2,
        prefetched), plain scan otherwise.  ``moe_f(W, W_spec, h, x,
        *bargs)`` always takes the speculative buffer (None when off)."""
        z = self.zcfg
        if z.effective_prefetch(self.cfg.expert_chunks) >= 1:
            return zero_scan_inference(moe_f, z, spec=_spec_chunk0)
        return zero_scan_inference(
            lambda W, h, x, *b: moe_f(W, None, h, x, *b), z)

    # ------------------------------------------------------------------ train

    def loss_fn(self, params: Dict[str, Array], batch: Dict[str, Array],
                rs: RunSpec, dp_world: int) -> Tuple[Array, Dict[str, Array]]:
        """Local loss (sum-NLL / global token count).  psum-able."""
        cfg, z = self.cfg, self.zcfg
        if cfg.embed_inputs:
            h = batch["embeds"].astype(z.compute_dtype)
        else:
            toks = batch["tokens"]
            emb_f = lambda W, t: self.embed_spec.unpack(W)["emb"][t] \
                .astype(z.compute_dtype)
            h = zero_apply(emb_f, z)(params["embed"], toks)
        B, S_loc = h.shape[0], h.shape[1]
        cos, sin = self._rope_tables(batch, rs, S_loc)
        global_tokens = float(B * S_loc * dp_world)

        def period_fn(W, h, cos, sin, spec=self.period_spec, kinds=self.period):
            p = spec.unpack(W.astype(z.compute_dtype))
            aux = jnp.float32(0)
            for i, kind in enumerate(kinds):
                h, _, a = apply_block(cfg, kind, _sub(p, f"{i}."), h, rs,
                                      {"rope": (cos, sin)}, None)
                aux = aux + a
            return h, aux

        if self.is_moe:
            # the same ring-prefetched layer scan as the dense stack:
            # layer i+k's SHARED-param gather rides under layer i's
            # routing + expert compute, and the expert-chunk stack flows
            # through xs into each layer's own zero_chunk_scan pipeline.
            # Two ring-only knobs (core/schedule.py): spec pre-gathers
            # layer i+k's chunk-0 expert weights (routing-ahead
            # dispatch), and with hpZ the chunk secondary shards thread
            # through the outer residuals so the nested remat replays
            # chunk gathers on the fast tier (f_fwd/f_bwd).
            hpz_remat = z.hpz and z.distributed
            # the speculative gather only pays when the chunk ring can be
            # seeded from it (nc >= 2, prefetched); with a single chunk
            # the sync chunk path would re-gather and the speculation
            # would be pure wasted wire bytes
            spec = _spec_chunk0 \
                if z.effective_prefetch(cfg.expert_chunks) >= 1 else None

            def moe_f(W, h, eflat, cos, sin):
                h2, _, aux, _ = self._moe_layer(rs, True, W, eflat, h,
                                                cos, sin, None, None)
                return h2, aux

            def moe_f_fwd(W, W_spec, h, eflat, cos, sin):
                h2, _, aux, sec = self._moe_layer(
                    rs, True, W, eflat, h, cos, sin, None, None,
                    W_spec=W_spec, collect_sec=hpz_remat)
                return h2, aux, sec

            def moe_f_bwd(W, h, eflat, sec, cos, sin, W0=None):
                h2, _, aux, _ = self._moe_layer(
                    rs, True, W, eflat, h, cos, sin, None, None, sec=sec,
                    W_spec=W0)
                return h2, aux

            ap = zero_apply_scan(
                moe_f, z, f_fwd=moe_f_fwd,
                f_bwd=moe_f_bwd if hpz_remat else None,
                spec=spec,
                bwd_spec=_bwd_spec_chunk0
                if (hpz_remat and spec is not None) else None)
            h, auxs = ap(params["blocks"], h, params["experts"], cos, sin)
        else:
            # prefetched (z.prefetch>=1) or synchronous (0) block scan —
            # see core/schedule.py
            ap = zero_apply_scan(
                lambda W, h, x, cos, sin: period_fn(W, h, cos, sin), z)
            h, auxs = ap(params["blocks"], h, None, cos, sin)
        aux = jnp.sum(auxs)
        if self.rem_spec:
            ap_rem = zero_apply(
                partial(period_fn, spec=self.rem_spec,
                        kinds=self.period[: self.rem]), z)
            h, aux_r = ap_rem(params["rem"], h, cos, sin)
            aux = aux + aux_r

        def norm_fn(W, h):
            p = self.head_spec.unpack(W.astype(z.compute_dtype))
            return nn.rms_norm(h, p["fnorm"])

        hn = zero_apply(norm_fn, z)(params["head"], h)
        nll_sum = self._streaming_xent(
            lambda f: zero_apply(f, z), params["unemb"],
            hn.reshape(-1, cfg.d_model), batch["targets"].reshape(-1))
        loss = nll_sum / global_tokens
        metrics = {"nll_sum": nll_sum, "tokens": jnp.float32(B * S_loc)}
        if self.n_moe_layers:
            aux_mean = aux / (self.n_moe_layers * dp_world)
            loss = loss + cfg.aux_loss_weight * aux_mean
            metrics["moe_aux"] = aux
        return loss, metrics

    # -------------------------------------------------------------- head

    def _streaming_xent(self, zw, unemb, hn2, targets) -> Array:
        """Sum-NLL with the (V, d) unembedding gathered one vocab chunk at
        a time; log-sum-exp streams across chunks (flash-style, exact).

        Full (T, V) logits never exist: each chunk's zero_apply computes
        (per-token max, rel-sum-exp, gold-logit contribution) — (T,)-sized
        outputs — and the scan combines them with the running-max rule.
        """
        z = self.zcfg
        Vc = self.vchunk
        T = hn2.shape[0]

        def chunk_f(Wc, hn2, targets, c):
            p = self.unemb_spec.unpack(Wc.astype(z.compute_dtype))
            logits = jnp.einsum("td,vd->tv", hn2, p["unemb"],
                                preferred_element_type=jnp.float32)
            m_c = jnp.max(logits, axis=1)
            s_c = jnp.sum(jnp.exp(logits - m_c[:, None]), axis=1)
            idx = targets - c * Vc
            in_r = (idx >= 0) & (idx < Vc)
            g = jnp.take_along_axis(
                logits, jnp.clip(idx, 0, Vc - 1)[:, None], axis=1)[:, 0]
            return m_c, s_c, jnp.where(in_r, g, 0.0)

        ap = zw(chunk_f)

        def body(carry, xs):
            m, l, gold = carry
            Wc, c = xs
            m_c, s_c, g_c = ap(Wc, hn2, targets, c)
            m_new = jnp.maximum(m, m_c)
            l = l * jnp.exp(m - m_new) + s_c * jnp.exp(m_c - m_new)
            return (m_new, l, gold + g_c), ()

        init = (jnp.full((T,), -1e30, jnp.float32),
                jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.float32))
        (m, l, gold), _ = lax.scan(
            body, init, (unemb, jnp.arange(self.unemb_chunks,
                                           dtype=jnp.int32)))
        return jnp.sum(m + jnp.log(l) - gold)

    def _head_logits(self, zi, params, h_last) -> Array:
        """Serving head: (B, S, V) logits assembled from vocab chunks."""
        z = self.zcfg
        cfg = self.cfg

        def norm_fn(W, hl):
            p = self.head_spec.unpack(W.astype(z.compute_dtype))
            return nn.rms_norm(hl, p["fnorm"])

        hn = zi(norm_fn)(params["head"], h_last)

        Vc, d = self.vchunk, cfg.d_model
        if qwz_gemm_eligible(z, Vc, d):
            # fused head: gather the qwZ payload WITHOUT dequantizing and
            # let the dequant-GEMM kernel apply the scales per weight
            # tile in VMEM — the bf16 (Vc, d) chunk never materializes.  The unemb
            # entry sits at flat offset 0 of its spec, so payload rows are
            # a plain reshape; the two eligible scale layouts are per-row
            # groups (d % block == 0) or one-block-covers-whole-rows
            # (block % d == 0).
            blk = z.qwz_block

            def ap(Wc, hn):
                pq, sq = fwd_gather_quant(Wc, z)
                pr = pq[: Vc * d].reshape(Vc, d)
                if d % blk == 0:
                    sr = sq[: Vc * (d // blk)].reshape(Vc, d // blk)
                else:
                    sr = jnp.repeat(sq[: Vc // (blk // d)], blk // d)[:, None]
                out2 = kops.dequant_matmul(
                    hn.reshape(-1, d), pr, sr,
                    compute_dtype=z.compute_dtype)
                return out2.reshape(hn.shape[0], hn.shape[1], Vc)
        else:
            def chunk_f(Wc, hn):
                p = self.unemb_spec.unpack(Wc.astype(z.compute_dtype))
                return jnp.einsum("bsd,vd->bsv", hn, p["unemb"],
                                  preferred_element_type=jnp.float32)

            ap = zi(chunk_f)

        def body(carry, Wc):
            return carry, ap(Wc, hn)

        _, chunks = lax.scan(body, (), params["unemb"])  # (nv, B, S, Vc)
        B, S = hn.shape[0], hn.shape[1]
        return jnp.moveaxis(chunks, 0, 2).reshape(B, S, cfg.vocab)

    # ------------------------------------------------------------ prefill

    def prefill_fn(self, params, batch, rs: RunSpec,
                   last_pos: Optional[Array] = None) -> Tuple[Array, Any]:
        """Forward over a prompt; returns (last-token logits, caches).

        ``last_pos`` (B,) selects per-sequence logits positions — the last
        REAL token of each (possibly right-padded) prompt.  Default: the
        final sequence position, the unpadded behaviour.
        """
        cfg, z = self.cfg, self.zcfg
        zi = lambda f: zero_apply_inference(f, z)
        if cfg.embed_inputs:
            h = batch["embeds"].astype(z.compute_dtype)
        else:
            h = zi(lambda W, t: self.embed_spec.unpack(W)["emb"][t]
                   .astype(z.compute_dtype))(params["embed"], batch["tokens"])
        B, S_loc = h.shape[0], h.shape[1]
        pos = {"rope": self._rope_tables(batch, rs, S_loc)}

        def period_fn(W, h, kinds=self.period, spec=self.period_spec):
            p = spec.unpack(W.astype(z.compute_dtype))
            caches = []
            for i, kind in enumerate(kinds):
                h, c, _ = apply_block(cfg, kind, _sub(p, f"{i}."), h, rs,
                                      pos, None)
                caches.append(c)
            return h, tuple(caches)

        if self.is_moe:
            cos, sin = pos["rope"]

            def moe_f(W, W_spec, h, eflat, cos, sin):
                h2, c, _, _ = self._moe_layer(rs, False, W, eflat, h,
                                              cos, sin, None, None,
                                              W_spec=W_spec)
                return h2, (c,)

            ap = self._moe_inference_scan(moe_f)
            h, caches = ap(params["blocks"], h, params["experts"], cos, sin)
        else:
            ap = zero_scan_inference(
                lambda W, h, x: period_fn(W, h), z)
            h, caches = ap(params["blocks"], h, None)
        rem_caches = None
        if self.rem_spec:
            h, rem_caches = zi(partial(period_fn, kinds=self.period[:self.rem],
                                       spec=self.rem_spec))(params["rem"], h)

        from repro.models.transformer import _last_shard_value, \
            select_positions
        if last_pos is None:
            h_last = _last_shard_value(h[:, -1:, :], rs.seq_axes)
        else:
            h_last = select_positions(h, last_pos, rs.seq_axes)

        logits = self._head_logits(zi, params, h_last)
        return logits, {"blocks": caches, "rem": rem_caches}

    # ------------------------------------------------------------- decode

    def decode_fn(self, params, caches, batch, cache_pos: Array,
                  rs: RunSpec) -> Tuple[Array, Any]:
        """One decode step.  batch: tokens (B,1) or embeds (B,1,d).

        ``cache_pos`` is PER-SEQUENCE — a (B,) int32 vector (a scalar is
        broadcast): every batch row may sit at a different position, which
        is what lets the continuous-batching engine decode requests
        admitted at different steps in one batch.
        """
        cfg, z = self.cfg, self.zcfg
        zi = lambda f: zero_apply_inference(f, z)
        if cfg.embed_inputs:
            h = batch["embeds"].astype(z.compute_dtype)
        else:
            h = zi(lambda W, t: self.embed_spec.unpack(W)["emb"][t]
                   .astype(z.compute_dtype))(params["embed"], batch["tokens"])
        cache_pos = attn_lib.per_seq_pos(cache_pos, h.shape[0])
        pos = {"rope": self._rope_tables(batch, rs, 1, cache_pos=cache_pos),
               "cache_pos": cache_pos}

        def period_fn(W, h, cache, kinds=self.period, spec=self.period_spec):
            p = spec.unpack(W.astype(z.compute_dtype))
            new = []
            for i, kind in enumerate(kinds):
                h, c, _ = apply_block(cfg, kind, _sub(p, f"{i}."), h, rs,
                                      pos, cache[i])
                new.append(c)
            return h, tuple(new)

        if self.is_moe:
            cos, sin = pos["rope"]

            def moe_f(W, W_spec, h, x, cos, sin, cache_pos):
                eflat, cache = x
                h2, c, _, _ = self._moe_layer(rs, False, W, eflat, h,
                                              cos, sin, cache_pos,
                                              cache[0], W_spec=W_spec)
                return h2, (c,)

            ap = self._moe_inference_scan(moe_f)
            h, new_caches = ap(
                params["blocks"], h,
                (params["experts"], caches["blocks"]), cos, sin,
                pos["cache_pos"])
        else:
            ap = zero_scan_inference(
                lambda W, h, cache: period_fn(W, h, cache), z)
            h, new_caches = ap(params["blocks"], h, caches["blocks"])
        new_rem = None
        if self.rem_spec:
            h, new_rem = zi(partial(period_fn, kinds=self.period[:self.rem],
                                    spec=self.rem_spec))(
                params["rem"], h, caches["rem"])

        logits = self._head_logits(zi, params, h)
        return logits, {"blocks": new_caches, "rem": new_rem}

    # -------------------------------------------------------------- paged

    def paged_fn(self, params, caches, batch, page_table: Array,
                 start_pos: Array, rs: RunSpec) -> Tuple[Array, Any]:
        """One paged-serving step: (B, T) tokens against a page arena.

        ``caches`` hold a PAGE ARENA — (n_pages, page_size, K, hd) per
        layer, shared by every slot — instead of per-slot slabs;
        ``page_table`` (B, Pm) maps each row's logical pages to physical
        ones (-1 = unmapped: the row writes nothing and attends to
        nothing).  One step shape serves all three paged workloads:
        T=1 batched decode, T=gamma+1 speculative verify, and B=1
        T=chunk chunked prefill.  Row r's token j sits at position
        ``start_pos[r] + j``; logits come back for every position,
        (B, T, V).  Paged mode is attn-only (no window/ssd/rec/moe).
        """
        cfg, z = self.cfg, self.zcfg
        assert not self.is_moe and set(self.period) == {"attn"}, \
            "paged serving supports dense attn-only stacks"
        assert not cfg.mrope, "paged serving does not support mrope"
        zi = lambda f: zero_apply_inference(f, z)
        h = zi(lambda W, t: self.embed_spec.unpack(W)["emb"][t]
               .astype(z.compute_dtype))(params["embed"], batch["tokens"])
        B, T = h.shape[0], h.shape[1]
        start_pos = attn_lib.per_seq_pos(start_pos, B)
        tpos = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)  # (B, T)
        cos, sin = nn.rope_table(tpos, cfg.d_head, cfg.rope_theta)
        pos = {"rope": (lax.stop_gradient(cos), lax.stop_gradient(sin)),
               "cache_pos": start_pos, "positions": tpos,
               "page_table": page_table}

        def period_fn(W, h, cache, kinds=self.period, spec=self.period_spec):
            p = spec.unpack(W.astype(z.compute_dtype))
            new = []
            for i, kind in enumerate(kinds):
                h, c, _ = apply_block(cfg, kind, _sub(p, f"{i}."), h, rs,
                                      pos, cache[i])
                new.append(c)
            return h, tuple(new)

        ap = zero_scan_inference(
            lambda W, h, cache: period_fn(W, h, cache), z)
        h, new_caches = ap(params["blocks"], h, caches["blocks"])
        new_rem = None
        if self.rem_spec:
            h, new_rem = zi(partial(period_fn, kinds=self.period[:self.rem],
                                    spec=self.rem_spec))(
                params["rem"], h, caches["rem"])

        logits = self._head_logits(zi, params, h)
        return logits, {"blocks": new_caches, "rem": new_rem}

    def paged_cache_shapes(self, n_pages: int, page_size: int,
                           dtype=jnp.bfloat16):
        """GLOBAL page-arena shapes matching paged_fn's cache layout.

        Same per-layer layout as :meth:`cache_shapes` with (batch, kv_len)
        reinterpreted as (n_pages, page_size): the arena's page dim is
        unsharded, the within-page token dim shards over kv_axes.
        """
        assert set(self.period) == {"attn"}, "paged caches are attn-only"
        return self.cache_shapes(n_pages, page_size, dtype)

    def init_paged_caches(self, n_pages: int, page_size: int,
                          dtype=jnp.bfloat16):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.paged_cache_shapes(n_pages, page_size, dtype))

    # ------------------------------------------------------------- caches

    def cache_shapes(self, batch: int, kv_len: int, dtype=jnp.bfloat16):
        """GLOBAL cache shapes pytree matching decode_fn's layout."""
        per = [init_cache_shapes(self.cfg, k, batch, kv_len, dtype)
               for k in self.period]
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.n_periods,) + s.shape,
                                           s.dtype), tuple(per))
        rem = None
        if self.rem_spec:
            rem = tuple(init_cache_shapes(self.cfg, k, batch, kv_len, dtype)
                        for k in self.period[: self.rem])
        return {"blocks": stacked, "rem": rem}

    def init_caches(self, batch: int, kv_len: int, dtype=jnp.bfloat16):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch, kv_len, dtype))


def _xent_chunked(h2d: Array, unemb: Array, targets: Array,
                  chunk: int = 1024) -> Array:
    """Sum-NLL with logits materialized one token-chunk at a time (fp32 LSE),
    rematerialized in backward — keeps the (T, V) logits out of memory."""
    T, d = h2d.shape
    if T <= chunk:
        nll, _ = nn.softmax_xent((h2d @ unemb)[None], targets[None])
        return nll
    n = T // chunk
    rem = T - n * chunk

    @jax.checkpoint
    def chunk_nll(hc, tc):
        nll, _ = nn.softmax_xent((hc @ unemb)[None], tc[None])
        return nll

    def body(acc, xs):
        hc, tc = xs
        return acc + chunk_nll(hc, tc), ()

    acc, _ = lax.scan(body, jnp.float32(0),
                      (h2d[: n * chunk].reshape(n, chunk, d),
                       targets[: n * chunk].reshape(n, chunk)))
    if rem:
        acc = acc + chunk_nll(h2d[n * chunk:], targets[n * chunk:])
    return acc
