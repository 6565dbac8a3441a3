"""Plain float32 reference of the training step, layer by layer.

The layers are the configuration's own (``bench/models/<model>.py``,
found by the configuration's ``"model"`` key): each layer's float32
forward pass, with the term it adds to the loss.  Around them: the
embedding lookup, a final RMSNorm and an untied unembedding, with the loss
summed over tokens and divided by the global token count, plus each
layer's term.  The optimizer is AdamW with global-norm clipping and
decoupled weight decay, as the traffic file states it.  Every matmul runs
at ``Precision.HIGHEST``.  Nothing here imports the program: the weights
come from :mod:`bench.weights`, the rows from :mod:`bench.tokens`.

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
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import models, weights
from bench.models.dense import rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
HEAD_TOKENS = 2048      # tokens per unembedding chunk


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


def rope_tables(seq: int, head_dim: int, theta: float):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def data_shards(traffic: Dict) -> Tuple[int, int]:
    """(row groups, sequence pieces): how a step's batch is spread over
    the chips in ZeRO's data parallelism.  The rows go over as many of the
    mesh's axes, in order, as they divide into; the sequence over the
    rest.  Each chip computes its share of the loss on its own tokens."""
    rows, pieces, left = 1, 1, int(traffic["global_batch"])
    for n in (int(x) for x in traffic["mesh"]):
        if pieces == 1 and left % n == 0 and left >= n:
            rows, left = rows * n, left // n
        else:
            pieces *= n
    return rows, pieces


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
        self.model = models.load(cfg)
        self.shards = data_shards(traffic)
        leaves = self.model.leaves(cfg)
        self.leaf_shapes = {n: s for n, s, _ in leaves}
        self.leaf_std = {n: std for n, _, std in leaves}
        L = cfg["n_layers"]
        self.layer_names = [[n for n, _, _ in self.model.layer_leaves(cfg, l)]
                            for l in range(L)]
        # one compile per kind of layer, from its first layer
        first: Dict[str, int] = {}
        for l in range(L):
            first.setdefault(self.model.layer_kind(cfg, l), l)
        fwd = {k: jax.jit(partial(self._block, l)) for k, l in first.items()}
        vjp = {k: jax.jit(partial(self._block_vjp_impl, l))
               for k, l in first.items()}
        kinds = [self.model.layer_kind(cfg, l) for l in range(L)]
        self._block_fwd = [fwd[k] for k in kinds]
        self._block_vjp = [vjp[k] for k in kinds]
        self._head = jax.jit(self._head_impl)
        self._adam = jax.jit(self._adam_impl, donate_argnums=(0, 1, 2))
        self._leaf = jax.jit(weights.leaf_value, static_argnums=(4,))

    # ------------------------------------------------------------- model

    def _block(self, l, W, h, cos, sin):
        """Layer ``l``'s output and its term of the loss."""
        return self.model.block(self.cfg, l, self.dot, W, h, cos, sin,
                                self.shards)

    def _block_vjp_impl(self, l, W, h, cos, sin, g):
        _, vjp = jax.vjp(lambda W_, h_: self._block(l, W_, h_, cos, sin),
                         W, h)
        return vjp((g, jnp.ones((), jnp.float32)))

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
                          np.float32(self.leaf_std[name]), shape)

    def _layer(self, P, l):
        return {n: P[f"layer{l}.{n}"] for n in self.layer_names[l]}

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
            hs, aux = [P["emb"][tokens]], []
            for l in range(L):
                h, a = self._block_fwd[l](self._layer(P, l), hs[-1], cos, sin)
                hs.append(h)
                aux.append(a)
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
            losses.append(loss / T + float(sum(aux)))
            grads = {"fnorm": d_fn, "unemb": d_un}
            sumsq = {k: float(jnp.sum(v * v)) for k, v in grads.items()}
            g = g_top
            for l in reversed(range(L)):            # backward 1: norms
                dW, g = self._block_vjp[l](self._layer(P, l), hs[l], cos,
                                           sin, g)
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
                dW, g = self._block_vjp[l](self._layer(P, l), hs[l], cos,
                                           sin, g)
                for n, x in dW.items():
                    update(f"layer{l}.{n}", x)
                del dW
            for name, grad in grads.items():
                update(name, grad)
            del hs, grads, g, g_top
        change = {name: float(jnp.sum((P[name] - self.draw(kd, name)) ** 2))
                  for name in self.leaf_shapes}
        return Readings(losses=losses, grad_sumsq=first, change_sumsq=change)
