"""Sharded AdamW: the ZeRO-3 partitioned optimizer.

Every optimizer tensor lives on the *primary* parameter shard only — the
"K·M/P" row of the paper's Fig. 4 memory analysis.  Gradients arrive as
fp32 primary shards (already summed over the world by qgZ / reduce-scatter);
global-norm clipping needs one scalar psum because every device owns a
disjoint shard.

Memory layout choices (per-parameter bytes on each device's shard):
  * There is no separate bf16 parameter copy: the fp32 master IS the
    parameter buffer, and the ZeRO++ forward gather quantizes (qwZ) or
    casts (baseline) straight from it.  Saves 2 bytes/param vs the usual
    master+param split.
  * ``moments_dtype`` controls m/v storage.  fp32 (default, 4+4 B) for
    small models; bf16 (2+2 B) for the ≥70B configs where fp32 moments
    alone would not fit v5e's 16 GB HBM.  Update math is always fp32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs.trace import annotate

Array = jax.Array
PyTree = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: Union[float, Callable[[Array], Array]] = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moments_dtype: jnp.dtype = jnp.float32   # fp32 | bf16 (large models)


def init_opt_state(params: PyTree,
                   cfg: AdamWConfig = AdamWConfig()) -> Dict[str, PyTree]:
    """params: fp32 master buffers (these ARE the trained parameters)."""
    zeros = lambda t: jax.tree.map(
        lambda p: jnp.zeros(p.shape, cfg.moments_dtype), t)
    return {"m": zeros(params), "v": zeros(params),
            "count": jnp.zeros((), jnp.int32)}


def global_grad_norm(grads: PyTree, dp_axes: Tuple[str, ...]) -> Array:
    local = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads))
    if dp_axes:
        local = lax.psum(local, dp_axes)  # shards are disjoint -> psum = global
    return jnp.sqrt(local)


def apply_update(
    grads: PyTree,
    params: PyTree,
    opt: Dict[str, PyTree],
    cfg: AdamWConfig,
    dp_axes: Tuple[str, ...] = (),
) -> Tuple[PyTree, Dict[str, PyTree], Dict[str, Array]]:
    """One AdamW step on the primary shards.

    Returns (new_params (fp32), new_opt, stats).
    """
    # optim.* scopes name the update and its clip in the profiler trace's
    # op names (bench/scopes.py); they change no instruction.
    with annotate("optim.adamw"):
        count = opt["count"] + 1
        lr = cfg.lr(count) if callable(cfg.lr) else jnp.float32(cfg.lr)

        with annotate("optim.clip"):
            gnorm = global_grad_norm(grads, dp_axes)
            scale = jnp.where(gnorm > cfg.grad_clip,
                              cfg.grad_clip / (gnorm + 1e-12), 1.0) \
                if cfg.grad_clip else jnp.float32(1.0)

        c1 = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        c2 = 1.0 - cfg.b2 ** count.astype(jnp.float32)

        def upd(g, m, v, w):
            g = g.astype(jnp.float32) * scale
            m32 = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g
            v32 = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * g * g
            mhat = m32 / c1
            vhat = v32 / c2
            step = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * w
            w = w - lr * step
            return (m32.astype(cfg.moments_dtype),
                    v32.astype(cfg.moments_dtype), w)

        flat_g, tdef = jax.tree.flatten(grads)
        flat_m = tdef.flatten_up_to(opt["m"])
        flat_v = tdef.flatten_up_to(opt["v"])
        flat_w = tdef.flatten_up_to(params)
        out = [upd(g, m, v, w) for g, m, v, w in zip(flat_g, flat_m, flat_v,
                                                     flat_w)]
    new_m = tdef.unflatten([o[0] for o in out])
    new_v = tdef.unflatten([o[1] for o in out])
    new_w = tdef.unflatten([o[2] for o in out])
    new_opt = {"m": new_m, "v": new_v, "count": count}
    return new_w, new_opt, {"grad_norm": gnorm, "lr": lr}
