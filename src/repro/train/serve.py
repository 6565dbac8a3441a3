"""Serving-path builders: prefill and decode steps under shard_map.

The serving layout keeps parameters ZeRO-sharded (flat buffers over every
mesh axis) and gathers per layer group exactly like training's forward —
with qwZ the gather moves INT8.  KV caches shard their batch dim over the
slow axes and their sequence dim over ``kv_axes``; decode uses the exact
2-pass split-KV softmax so any kv sharding works.

Shape policy (see configs.base.SHAPES):
  * prefill_32k  — batch over ('pod','data'), prompt sequence over 'model'
                   (kv cache inherits the same layout).
  * decode_32k   — batch over ('pod','data'), cache sequence over 'model'.
  * long_500k    — global_batch=1: batch unsharded, cache sequence over
                   EVERY mesh axis (the only way 0.5M tokens of KV fit).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model import Model
from repro.models.transformer import RunSpec
# specs come from the state subsystem, not the trainer: serving must not
# depend on the training stack (see DESIGN.md §4)
from repro.train.state import load_serving_params, param_specs  # noqa: F401

Array = jax.Array


def _opt(axes) -> Optional[Tuple[str, ...]]:
    t = tuple(axes)
    return t or None


def cache_specs(model: Model, batch_axes, kv_axes) -> Dict[str, Any]:
    """PartitionSpec tree matching ``model.cache_shapes`` exactly."""
    b = _opt(batch_axes)
    kv = _opt(kv_axes)

    def for_kind(kind: str, stacked: bool):
        L = (None,) if stacked else ()
        if kind in ("attn", "local", "moe"):
            s = P(*L, b, kv, None, None)
            return {"k": s, "v": s}
        if kind == "ssd":
            return {"h": P(*L, b, None, None, None),
                    "conv": P(*L, b, None, None)}
        if kind == "rec":
            return {"h": P(*L, b, None), "conv": P(*L, b, None, None)}
        raise ValueError(kind)

    blocks = tuple(for_kind(k, True) for k in model.period)
    rem = tuple(for_kind(k, False) for k in model.period[: model.rem]) \
        if model.rem_spec else None
    return {"blocks": blocks, "rem": rem}


def serve_batch_specs(model: Model, batch_axes, seq_axes) -> Dict[str, P]:
    b = _opt(batch_axes)
    s = _opt(seq_axes)
    cfg = model.cfg
    out = {}
    if cfg.embed_inputs:
        out["embeds"] = P(b, s, None)
    else:
        out["tokens"] = P(b, s)
    if cfg.mrope:
        out["positions"] = P(None, b, s)
    return out


@dataclasses.dataclass(frozen=True)
class ServeStep:
    fn: Callable
    mesh: Any
    in_specs: Tuple[Any, ...]
    out_specs: Tuple[Any, ...]
    run_spec: RunSpec


def build_prefill_step(model: Model, mesh,
                       batch_axes: Tuple[str, ...],
                       seq_axes: Tuple[str, ...],
                       with_last_pos: bool = False,
                       prefetch: Optional[int] = None) -> ServeStep:
    """Prompt ingestion: (params, batch) -> (last-token logits, caches).

    The prefill KV cache inherits the activation layout, so kv_axes ==
    seq_axes by construction.  With ``with_last_pos`` the step takes an
    extra (B,) int32 argument selecting each sequence's logits position —
    the last REAL token of a right-padded prompt (continuous-batching
    engine, prompt-length buckets).  ``prefetch`` overrides the model's
    ring depth for this step (see build_decode_step).
    """
    if prefetch is not None:
        model = model.with_prefetch(prefetch)
    rs = RunSpec(mode="prefill", seq_axes=tuple(seq_axes),
                 kv_axes=tuple(seq_axes))
    p_specs = param_specs(model, tuple(mesh.axis_names))
    b_specs = serve_batch_specs(model, batch_axes, seq_axes)
    c_specs = cache_specs(model, batch_axes, seq_axes)
    logit_spec = P(_opt(batch_axes), None, None)

    if with_last_pos:
        def stepf(params, batch, last_pos):
            return model.prefill_fn(params, batch, rs, last_pos=last_pos)
        in_specs = (p_specs, b_specs, P(_opt(batch_axes)))
    else:
        def stepf(params, batch):
            return model.prefill_fn(params, batch, rs)
        in_specs = (p_specs, b_specs)

    sm = jax.shard_map(stepf, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=(logit_spec, c_specs),
                       check_vma=False)
    return ServeStep(fn=jax.jit(sm), mesh=mesh,
                     in_specs=in_specs,
                     out_specs=(logit_spec, c_specs), run_spec=rs)


def build_decode_step(model: Model, mesh,
                      batch_axes: Tuple[str, ...],
                      kv_axes: Tuple[str, ...],
                      donate: bool = True,
                      prefetch: Optional[int] = None) -> ServeStep:
    """One-token decode: (params, caches, batch, cache_pos) ->
    (logits, new caches).

    ``cache_pos`` is a PER-SEQUENCE (B,) int32 vector, batch-sharded like
    the activations: each row of the batch decodes at its own position, so
    one compiled step serves any mix of in-flight requests (the
    continuous-batching contract, DESIGN.md §5).

    ``prefetch`` overrides the model's ring depth for THIS step: decode
    batches are small enough that one layer's compute rarely covers a
    weight gather on a slow interconnect, so gathering k>1 layers ahead
    pays exactly here (core/schedule.py; depth still clamps to
    n_layers-1).
    """
    if prefetch is not None:
        model = model.with_prefetch(prefetch)
    rs = RunSpec(mode="decode", kv_axes=tuple(kv_axes))
    p_specs = param_specs(model, tuple(mesh.axis_names))
    b_specs = serve_batch_specs(model, batch_axes, ())
    c_specs = cache_specs(model, batch_axes, kv_axes)
    logit_spec = P(_opt(batch_axes), None, None)
    pos_spec = P(_opt(batch_axes))

    def stepf(params, caches, batch, cache_pos):
        return model.decode_fn(params, caches, batch, cache_pos, rs)

    sm = jax.shard_map(stepf, mesh=mesh,
                       in_specs=(p_specs, c_specs, b_specs, pos_spec),
                       out_specs=(logit_spec, c_specs),
                       check_vma=False)
    fn = jax.jit(sm, donate_argnums=(1,) if donate else ())
    return ServeStep(fn=fn, mesh=mesh,
                     in_specs=(p_specs, c_specs, b_specs, pos_spec),
                     out_specs=(logit_spec, c_specs), run_spec=rs)


def paged_cache_specs(model: Model, kv_axes) -> Dict[str, Any]:
    """PartitionSpec tree matching ``model.paged_cache_shapes``.

    The arena's page dim is UNSHARDED (any slot's table may point at any
    physical page); the within-page token dim shards over ``kv_axes`` —
    the same split-KV ownership decode_attend uses, at page granularity.
    With extra mesh axes (e.g. 'data') the arena is replicated across
    them: every shard runs the identical paged step on identical inputs,
    so the replicas stay bit-equal without any cross-axis traffic.
    """
    kv = _opt(kv_axes)

    def for_kind(kind: str, stacked: bool):
        if kind != "attn":
            raise ValueError(f"paged caches are attn-only, got {kind!r}")
        L = (None,) if stacked else ()
        s = P(*L, None, kv, None, None)
        return {"k": s, "v": s}

    blocks = tuple(for_kind(k, True) for k in model.period)
    rem = tuple(for_kind(k, False) for k in model.period[: model.rem]) \
        if model.rem_spec else None
    return {"blocks": blocks, "rem": rem}


def build_paged_step(model: Model, mesh,
                     kv_axes: Tuple[str, ...],
                     donate: bool = True,
                     prefetch: Optional[int] = None) -> ServeStep:
    """Paged multi-token step: (params, arena, batch, page_table,
    start_pos) -> ((B, T, V) logits, new arena).

    ONE builder covers every paged workload — the engine calls it with
    T=1 (batched decode), T=gamma+1 (speculative verify) and B=1/T=chunk
    (chunked prefill); each (B, T) shape compiles once.  The slot->page
    indirection is resolved INSIDE the jitted step (gather + scatter by
    physical page id, models/attention.py paged_*), so the host only
    uploads the small int32 table.  Batch stays unsharded: the arena is
    one global pool whose pages any row may reference, which is
    incompatible with slicing pages per batch shard.
    """
    if prefetch is not None:
        model = model.with_prefetch(prefetch)
    rs = RunSpec(mode="paged", kv_axes=tuple(kv_axes))
    p_specs = param_specs(model, tuple(mesh.axis_names))
    b_specs = serve_batch_specs(model, (), ())
    c_specs = paged_cache_specs(model, kv_axes)
    logit_spec = P(None, None, None)
    table_spec = P(None, None)
    pos_spec = P(None)

    def stepf(params, caches, batch, table, start_pos):
        return model.paged_fn(params, caches, batch, table, start_pos, rs)

    in_specs = (p_specs, c_specs, b_specs, table_spec, pos_spec)
    sm = jax.shard_map(stepf, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=(logit_spec, c_specs),
                       check_vma=False)
    fn = jax.jit(sm, donate_argnums=(1,) if donate else ())
    return ServeStep(fn=fn, mesh=mesh, in_specs=in_specs,
                     out_specs=(logit_spec, c_specs), run_spec=rs)


def pad_prefill_caches(model: Model, caches, kv_len: int):
    """Grow prefill KV caches (length = prompt) to decode capacity.

    Full-attention caches use slot == position, so zero-padding the
    sequence dim to ``kv_len`` is exact (padded slots are masked out by the
    position-validity test in decode_attend).  Ring buffers (local window)
    and recurrent states are already capacity-sized.
    """
    import jax.numpy as jnp

    def grow(kind, cache, stacked):
        if kind not in ("attn", "moe") or cache is None:
            return cache
        axis = 2 if stacked else 1
        out = {}
        for key in ("k", "v"):
            arr = cache[key]
            pad = kv_len - arr.shape[axis]
            if pad > 0:
                widths = [(0, 0)] * arr.ndim
                widths[axis] = (0, pad)
                arr = jnp.pad(arr, widths)
            out[key] = arr
        return out

    blocks = tuple(grow(k, c, True)
                   for k, c in zip(model.period, caches["blocks"]))
    rem = caches.get("rem")
    if rem is not None:
        rem = tuple(grow(k, c, False)
                    for k, c in zip(model.period[: model.rem], rem))
    return {"blocks": blocks, "rem": rem}


def serve_shape_policy(shape_name: str, mesh_axes: Tuple[str, ...]
                       ) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """(batch_axes, kv_axes) for a named inference shape.

    Validates both inputs instead of silently falling through to the
    default layout: the shape must be a known *serving* shape from
    ``configs.base.SHAPES`` and the mesh must carry the fast ``model``
    axis the KV layout is keyed on.
    """
    from repro.configs.base import SHAPES

    serving = {n for n, s in SHAPES.items() if s.kind in ("prefill",
                                                          "decode")}
    if shape_name not in SHAPES:
        raise ValueError(
            f"unknown inference shape {shape_name!r}; known serving shapes: "
            f"{sorted(serving)}")
    if shape_name not in serving:
        raise ValueError(
            f"shape {shape_name!r} is a {SHAPES[shape_name].kind} shape, "
            f"not a serving one; expected one of {sorted(serving)}")
    axes = tuple(mesh_axes)
    if len(set(axes)) != len(axes):
        raise ValueError(f"duplicate mesh axis names: {axes}")
    if "model" not in axes:
        raise ValueError(
            f"serving layouts shard the KV cache over the fast 'model' "
            f"axis (DESIGN.md §2), absent from mesh axes {axes}")
    fast = ("model",)
    slow = tuple(a for a in axes if a != "model")
    if shape_name == "long_500k":
        return (), axes                  # B=1: shard the cache everywhere
    return slow, fast
