"""shard_map train-step builder: the distributed runtime around the Model.

One ``shard_map`` over the full mesh wraps loss + backward + optimizer.
All ZeRO++ collectives (qwZ gathers, hpZ secondary gathers, qgZ all-to-all
reduce-scatter) happen *inside*, per layer group, via the engine; the only
things sharded at the jit boundary are the flat parameter/optimizer buffers
(over every mesh axis) and the batch (batch dims over the slow axes,
sequence over the fast ``model`` axis = sequence parallelism).

Also provides gradient accumulation (microbatching) — at very small
per-device batch the paper's regime — and metric reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model import Model
from repro.models.transformer import RunSpec
from repro.optim.adamw import AdamWConfig, apply_update
# State specs/init/shapes are owned by the ZeroState subsystem
# (train/state.py); re-exported here for existing callers.
from repro.train.state import (ZeroState, opt_specs,  # noqa: F401
                               param_specs, state_shapes)

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# batch partition specs (model-state specs live in train/state.py)
# ---------------------------------------------------------------------------

def batch_specs(model: Model, axes: Tuple[str, ...],
                batch_axes: Tuple[str, ...], seq_axes: Tuple[str, ...],
                ) -> Dict[str, P]:
    """Specs for a train batch dict (tokens/targets/embeds/positions)."""
    b = tuple(batch_axes) or None
    s = tuple(seq_axes) or None
    cfg = model.cfg
    out = {"targets": P(b, s)}
    if cfg.embed_inputs:
        out["embeds"] = P(b, s, None)
    else:
        out["tokens"] = P(b, s)
    if cfg.mrope:
        out["positions"] = P(None, b, s)
    return out


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainStep:
    """A built (but not yet lowered) distributed train step."""
    fn: Callable                       # jitted (params, opt, batch) -> ...
    mesh: Any
    in_specs: Tuple[Any, ...]
    out_specs: Tuple[Any, ...]
    run_spec: RunSpec
    world: int


def choose_batch_seq_axes(global_batch: int, mesh
                          ) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """Greedy activation layout: shard batch over as many (slowest-first)
    axes as it divides into; remaining axes carry the sequence dim.

    Pure-DP (batch over every axis, no sequence sharding — the paper's own
    ZeRO layout, zero attention-KV gathers) whenever global_batch covers the
    world; sequence parallelism only absorbs the axes batch can't fill.
    """
    batch_axes, rem = [], global_batch
    for ax in mesh.axis_names:
        n = mesh.shape[ax]
        if rem % n == 0 and rem >= n:
            batch_axes.append(ax)
            rem //= n
        else:
            break
    seq_axes = tuple(a for a in mesh.axis_names if a not in batch_axes)
    return tuple(batch_axes), seq_axes


def build_train_step(
    model: Model,
    mesh,
    opt_cfg: AdamWConfig,
    accum: int = 1,
    donate: bool = True,
    global_batch: Optional[int] = None,
    seq_shard: str = "auto",     # auto | force (always seq-shard on model)
    attn_impl: str = "xla",      # xla | pallas (flash kernel, §Perf)
) -> TrainStep:
    """Build the jitted ZeRO++ train step for ``mesh``.

    Batch layout: every leaf has GLOBAL shape; with ``accum > 1`` a leading
    microbatch axis (accum, B, S, ...) is scanned with gradient summation.
    """
    z = model.zcfg
    axes = tuple(mesh.axis_names)
    assert tuple(z.dp_axes) == axes, (z.dp_axes, axes)
    if seq_shard == "auto" and global_batch is not None:
        batch_axes, seq_axes = choose_batch_seq_axes(global_batch, mesh)
    else:
        batch_axes = tuple(a for a in axes if a != z.intra_axis)
        seq_axes = (z.intra_axis,)
    world = int(np.prod(list(mesh.shape.values())))
    rs = RunSpec(mode="train", seq_axes=seq_axes, attn_impl=attn_impl)

    p_specs = param_specs(model, axes)
    o_specs = opt_specs(model, axes)
    b_specs = batch_specs(model, axes, batch_axes, seq_axes)
    if accum > 1:
        b_specs = {k: P(None, *v) for k, v in b_specs.items()}

    m_specs = {"loss": P(), "nll": P(), "tokens": P(), "grad_norm": P(),
               "lr": P()}
    if model.n_moe_layers:
        m_specs["moe_aux"] = P()

    def local_step(params, opt, batch):
        def loss_of(p, b):
            return model.loss_fn(p, b, rs, world)

        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
        else:
            def micro(carry, mb):
                (l, mts), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, mb)
                loss_a, grads_a, m_a = carry
                grads_a = jax.tree.map(jnp.add, grads_a, g)
                m_a = jax.tree.map(jnp.add, m_a, mts)
                return (loss_a + l, grads_a, m_a), ()

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zero_m = {"nll_sum": jnp.float32(0), "tokens": jnp.float32(0)}
            if model.n_moe_layers:
                zero_m["moe_aux"] = jnp.float32(0)
            (loss, grads, metrics), _ = lax.scan(
                micro, (jnp.float32(0), zero_g, zero_m), batch)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)

        new_params, new_opt, stats = apply_update(
            grads, params, opt, opt_cfg, dp_axes=z.dp_axes)

        gl = lax.psum(loss, z.dp_axes)
        nll = lax.psum(metrics["nll_sum"], z.dp_axes)
        toks = lax.psum(metrics["tokens"], z.dp_axes)
        out_m = {"loss": gl, "nll": nll / toks, "tokens": toks,
                 "grad_norm": stats["grad_norm"], "lr": stats["lr"]}
        if model.n_moe_layers:
            out_m["moe_aux"] = lax.psum(metrics["moe_aux"], z.dp_axes) \
                / (model.n_moe_layers * world * max(accum, 1))
        return new_params, new_opt, out_m

    sm = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, m_specs),
        check_vma=False,
    )
    fn = jax.jit(sm, donate_argnums=(0, 1) if donate else ())
    return TrainStep(fn=fn, mesh=mesh,
                     in_specs=(p_specs, o_specs, b_specs),
                     out_specs=(p_specs, o_specs, m_specs),
                     run_spec=rs, world=world)


# ---------------------------------------------------------------------------
# state construction / placement
# ---------------------------------------------------------------------------

def init_state(model: Model, mesh, opt_cfg: AdamWConfig, key,
               ) -> Tuple[PyTree, PyTree]:
    """Initialize (params fp32, opt) sharded over the mesh.

    Thin wrapper over :meth:`repro.train.state.ZeroState.init` for callers
    that want bare pytrees rather than the state object.
    """
    st = ZeroState(model, mesh, opt_cfg).init(key)
    return st.params, st.opt


def place_batch(batch: Dict[str, np.ndarray], mesh, b_specs) -> Dict:
    """Device_put a host batch dict with the trainer's shardings."""
    return {k: jax.device_put(v, NamedSharding(mesh, b_specs[k]))
            for k, v in batch.items()}
