"""Plain float32 reference of the training step, layer by layer.

It follows the repository's dense block as it stands: RMSNorm with a
``1 + offset`` scale, RoPE on rotated halves, causal GQA attention, a gated
MLP (fused gate|up, SiLU or tanh-GELU), residuals, a final RMSNorm and an
untied unembedding, with the loss summed over tokens and divided by the
global token count.  The optimizer is AdamW with global-norm clipping and
decoupled weight decay, as the traffic file states it.  Every matmul runs
at ``Precision.HIGHEST``.  Nothing here imports the program: the weights
come from :mod:`bench.weights`, the rows from :mod:`bench.traffic`.

Memory: parameters, both Adam moments and one layer's working set live on
one device.  Each step runs the forward once, keeping only the layer
inputs, then the backward twice with each layer recomputed: the first
backward sums the gradients' squares (for the clipping norm), the second
applies the update layer by layer as its gradient appears, so no whole
gradient is ever held.

``precision="fp8"`` is a reading that ``bench/control.py`` prints beside
the control, not held to fail (PERF.md says why): every matmul of the
forward and the backward takes float8-e4m3 operands, each scaled per
tensor to its largest magnitude, and accumulates in float32.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
NORM_EPS = 1e-6         # the blocks' RMSNorm epsilon
HEAD_TOKENS = 2048      # tokens per unembedding chunk
ATTN_QUERIES = 1024     # queries per attention chunk


def _f8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _einsum_f8(spec, a, b):
    @jax.custom_vjp
    def f(a, b):
        return _einsum(spec, _f8(a), _f8(b))

    def fwd(a, b):
        qa, qb = _f8(a), _f8(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(partial(_einsum, spec), *res)
        return vjp(_f8(g))

    f.defvjp(fwd, bwd)
    return f(a, b)


def rms_norm(x, offset):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + offset)


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def rope_tables(seq: int, head_dim: int, theta: float):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def lr_at(opt: Dict, count: int) -> float:
    """Learning rate of the ``count``-th update (1-based): linear warm-up
    to ``lr`` over ``warmup`` updates, then cosine to ``floor * lr`` at
    ``total``."""
    peak, warm = float(opt["lr"]), max(int(opt["warmup"]), 1)
    total, floor = int(opt["total"]), float(opt.get("floor", 0.1))
    if count < warm:
        return peak * count / warm
    prog = min(max((count - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * prog)))


@dataclasses.dataclass
class Readings:
    """What the checks compare: the loss of each step, each leaf's sum of
    squares of the first gradient as the optimizer takes it (clipped),
    and of each leaf's change after the last step."""
    losses: List[float]
    grad_sumsq: Dict[str, float]
    change_sumsq: Dict[str, float]


class Reference:
    def __init__(self, cfg: Dict, traffic: Dict, precision: str = "fp32"):
        if precision not in ("fp32", "fp8"):
            raise ValueError(precision)
        self.cfg, self.opt = cfg, traffic["optimizer"]
        self.dot = _einsum if precision == "fp32" else _einsum_f8
        self.leaf_shapes = dict(weights.leaves(cfg))
        self.layer_names = [n for n, _ in weights.layer_leaves(cfg)]
        self._block_fwd = jax.jit(self._block)
        self._block_vjp = jax.jit(self._block_vjp_impl)
        self._head = jax.jit(self._head_impl)
        self._adam = jax.jit(self._adam_impl, donate_argnums=(0, 1, 2))
        self._leaf = jax.jit(weights.leaf_value, static_argnums=(4,))

    # ------------------------------------------------------------- model

    def _attention(self, q, k, v):
        B, S, H, hd = q.shape
        rep = H // k.shape[2]
        kr, vr = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        C = min(ATTN_QUERIES, S)
        kpos = jnp.arange(S)

        @jax.checkpoint
        def chunk(args):
            qc, q0 = args
            logits = self.dot("bqhd,bkhd->bhqk", qc, kr) * hd ** -0.5
            mask = (q0 + jnp.arange(C))[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            return self.dot("bhqk,bkhd->bqhd", p, vr)

        qs = q.reshape(B, S // C, C, H, hd).transpose(1, 0, 2, 3, 4)
        out = jax.lax.map(chunk, (qs, jnp.arange(S // C) * C))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)

    def _block(self, W, h, cos, sin):
        cfg, dot = self.cfg, self.dot
        B, S, d = h.shape
        H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
        x = rms_norm(h, W["ln1"])
        q = dot("bsd,de->bse", x, W["wq"]).reshape(B, S, H, hd)
        k = dot("bsd,de->bse", x, W["wk"]).reshape(B, S, K, hd)
        v = dot("bsd,de->bse", x, W["wv"]).reshape(B, S, K, hd)
        o = self._attention(rope(q, cos, sin), rope(k, cos, sin), v)
        h = h + dot("bse,ed->bsd", o.reshape(B, S, H * hd), W["wo"])
        x = rms_norm(h, W["ln2"])
        g, u = jnp.split(dot("bsd,df->bsf", x, W["wgu"]), 2, axis=-1)
        if cfg["act"] == "silu":
            a = jax.nn.silu(g)
        elif cfg["act"] == "gelu":
            a = jax.nn.gelu(g, approximate=True)
        else:
            raise ValueError(cfg["act"])
        return h + dot("bsf,fd->bsd", a * u, W["wdn"])

    def _block_vjp_impl(self, W, h, cos, sin, g):
        _, vjp = jax.vjp(lambda W_, h_: self._block(W_, h_, cos, sin), W, h)
        return vjp(g)

    def _head_impl(self, fnorm, unemb, h, targets, inv_tokens):
        """Summed NLL of a chunk of tokens and its gradients (scaled to
        the global mean) w.r.t. the final norm, unembedding and input."""
        def nll(fn, un, x):
            logits = self.dot("td,vd->tv", rms_norm(x, fn), un)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
            return jnp.sum(lse - gold)
        loss, vjp = jax.vjp(nll, fnorm, unemb, h)
        return (loss,) + vjp(inv_tokens)

    def _adam_impl(self, w, m, v, g, scale, lr, c1, c2):
        o = self.opt
        g = g * scale
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + o["eps"]) \
            + o["weight_decay"] * w
        return w - lr * step, m, v

    # ------------------------------------------------------------- driver

    def draw(self, kd, name: str) -> jax.Array:
        """A leaf's initial value, drawn again from the seed."""
        shape = self.leaf_shapes[name]
        lid, nid, _ = weights.leaf_ids(name)
        return self._leaf(kd, np.uint32(lid), np.uint32(nid),
                          np.float32(weights.leaf_std(name, shape)), shape)

    def _layer(self, P, l):
        return {n: P[f"layer{l}.{n}"] for n in self.layer_names}

    def run(self, seed: int, batches: List[Dict[str, np.ndarray]]
            ) -> Readings:
        """Follow ``len(batches)`` steps from the seed's weights."""
        cfg = self.cfg
        kd = weights.key_data(seed)
        P = {name: self.draw(kd, name) for name in self.leaf_shapes}
        M = {k: jnp.zeros_like(v) for k, v in P.items()}
        Vm = {k: jnp.zeros_like(v) for k, v in P.items()}
        cos, sin = rope_tables(batches[0]["tokens"].shape[1],
                               cfg["head_dim"], cfg["rope_theta"])
        losses, first = [], None
        L = cfg["n_layers"]
        o = self.opt
        for t, batch in enumerate(batches, start=1):
            tokens = jnp.asarray(batch["tokens"])
            targets = jnp.asarray(batch["targets"]).reshape(-1)
            B, S = tokens.shape
            T = B * S
            hs = [P["emb"][tokens]]
            for l in range(L):
                hs.append(self._block_fwd(self._layer(P, l), hs[-1], cos, sin))
            hl = hs[-1].reshape(T, -1)
            loss, d_fn, d_un, g_parts = 0.0, 0.0, 0.0, []
            for a in range(0, T, HEAD_TOKENS):
                lc, fc, uc, gc = self._head(P["fnorm"], P["unemb"],
                                            hl[a:a + HEAD_TOKENS],
                                            targets[a:a + HEAD_TOKENS],
                                            np.float32(1.0 / T))
                loss, d_fn, d_un = loss + float(lc), d_fn + fc, d_un + uc
                g_parts.append(gc)
            g_top = jnp.concatenate(g_parts).reshape(hs[-1].shape)
            losses.append(loss / T)
            grads = {"fnorm": d_fn, "unemb": d_un}
            sumsq = {k: float(jnp.sum(v * v)) for k, v in grads.items()}
            g = g_top
            for l in reversed(range(L)):            # backward 1: norms
                dW, g = self._block_vjp(self._layer(P, l), hs[l], cos, sin, g)
                for n, x in dW.items():
                    sumsq[f"layer{l}.{n}"] = float(jnp.sum(x * x))
                del dW
            grads["emb"] = jnp.zeros_like(P["emb"]).at[
                tokens.reshape(-1)].add(g.reshape(T, -1))
            sumsq["emb"] = float(jnp.sum(grads["emb"] ** 2))
            gnorm = float(np.sqrt(sum(sumsq.values())))
            clip = o["grad_clip"]
            scale = clip / (gnorm + 1e-12) if gnorm > clip else 1.0
            if first is None:
                first = {k: v * scale * scale for k, v in sumsq.items()}
            args = (np.float32(scale), np.float32(lr_at(o, t)),
                    np.float32(1 - o["b1"] ** t), np.float32(1 - o["b2"] ** t))

            def update(name, grad):
                P[name], M[name], Vm[name] = self._adam(
                    P[name], M[name], Vm[name], grad, *args)

            g = g_top
            for l in reversed(range(L)):            # backward 2: update
                dW, g = self._block_vjp(self._layer(P, l), hs[l], cos, sin, g)
                for n, x in dW.items():
                    update(f"layer{l}.{n}", x)
                del dW
            for name, grad in grads.items():
                update(name, grad)
            del hs, grads, g, g_top
        change = {name: float(jnp.sum((P[name] - self.draw(kd, name)) ** 2))
                  for name in self.leaf_shapes}
        return Readings(losses=losses, grad_sumsq=first, change_sumsq=change)
