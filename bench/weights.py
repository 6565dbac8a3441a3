"""Weights made by the benchmark from ``--seed``, for the program and the
reference alike.

Every tensor of the model (a *leaf*: ``emb``, ``layer<l>.<name>``,
``fnorm``, ``unemb``; the configuration's module, ``bench/models``, lists
them with their shapes and init std) is drawn from its own key, folded
from the seed, the layer and the leaf's name, so the reference can draw
any leaf again by itself.

:class:`Packer` lays the leaves out in the program's flat ZeRO buffers
(one jitted call per buffer group, straight into the mesh sharding) and
reads per-leaf sums of squares back out of them.  It reads the layout from
the program's model object, and where each entry of it comes from from the
configuration's module; the reference never sees that layout.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import models
from bench.tokens import SEED_MASK

Shape = Tuple[int, ...]

# the model object's spec of each buffer group it may have
GROUP_SPECS = {"embed": "embed_spec", "blocks": "period_spec",
               "experts": "expert_spec", "rem": "rem_spec",
               "head": "head_spec", "unemb": "unemb_spec"}


def key_data(seed: int) -> np.ndarray:
    """The seed as two uint32 words: every 64-bit seed is its own key."""
    s = int(seed) & SEED_MASK
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def leaf_ids(name: str) -> Tuple[int, int, str]:
    """(layer id, name id, base name) of a leaf name."""
    if name.startswith("layer"):
        layer, base = name[len("layer"):].split(".", 1)
        lid = int(layer) + 1
    else:
        lid, base = 0, name
    return lid, zlib.crc32(base.encode()) & 0x7FFFFFFF, base


def leaf_value(kd, lid, nid, std, shape: Shape) -> jax.Array:
    """A leaf's initial fp32 value.  ``kd``, ``lid``, ``nid`` and ``std``
    may be traced, so one compile serves every leaf of a shape."""
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32),
                                   impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, lid), nid)
    return jax.random.normal(key, shape, jnp.float32) * std


# ---------------------------------------------------------------------------
# the program's flat layout (harness side)
# ---------------------------------------------------------------------------

class Packer:
    """Leaves <-> the program's flat buffer groups, for one model.

    A group is a stack of rows (one per layer period, per vocabulary chunk
    of the unembedding, per layer and expert chunk), counted flat, or a
    single row; each row packs its entries in the program's order, padded
    with zeros to the row length.  An entry holds a leaf, or a slice of it
    along the leaf's first axis."""

    def __init__(self, model, cfg: Dict):
        if model.embed_spec is None:
            raise ValueError("Packer lays out token-input models")
        self.cfg, self.model = cfg, model
        self.mod = models.load(cfg)
        self.shapes = model.param_shapes()
        self.specs = {g: getattr(model, GROUP_SPECS[g]) for g in self.shapes}
        leaves = self.mod.leaves(cfg)
        self.leaf_shapes = {n: s for n, s, _ in leaves}
        self.leaf_std = {n: std for n, _, std in leaves}

    def rows(self, group: str) -> int:
        return math.prod(self.shapes[group][:-1])

    def leaf_name(self, group: str, entry: str, q: int) -> str:
        """The leaf an entry of row ``q`` (a Python int) holds part of."""
        base, layer, _ = self.mod.source(self.cfg, self.model, group, entry,
                                         q)
        return base if layer is None else f"layer{layer}.{base}"

    def row(self, kd, group: str, q) -> jax.Array:
        """Row ``q`` (may be traced) of the group's flat buffer, fp32."""
        parts = []
        for entry, shape in self.specs[group].entries:
            # every row of a group holds the same leaves of its layers:
            # row 0 names the shape and std
            name = self.leaf_name(group, entry, 0)
            full_shape = self.leaf_shapes[name]
            base, layer, start = self.mod.source(self.cfg, self.model, group,
                                                 entry, q)
            lid = 0 if layer is None else layer + 1
            _, nid, _ = leaf_ids(name)
            x = leaf_value(kd, lid, nid, self.leaf_std[name], full_shape)
            if x.shape != tuple(shape):
                x = jax.lax.dynamic_slice_in_dim(x, start, shape[0])
            assert x.shape == tuple(shape), (group, entry, x.shape, shape)
            parts.append(x.reshape(-1))
        flat = jnp.concatenate(parts)
        return jnp.pad(flat, (0, self.shapes[group][-1] - flat.shape[0]))

    def build(self, kd, group: str) -> jax.Array:
        """The group's global flat buffer (trace inside a jit); the rows
        of a stack come from one traced row body."""
        shape = self.shapes[group]
        if len(shape) == 1:
            return self.row(kd, group, 0)
        rows = jax.lax.map(lambda q: self.row(kd, group, q),
                           jnp.arange(self.rows(group), dtype=jnp.int32))
        return rows.reshape(shape)

    def row_sumsq(self, x: jax.Array, group: str) -> jax.Array:
        """(entries,) sums of squares of each entry of one flat row, by
        masks over the flat dim (so a sharded row reduces in place)."""
        spec = self.specs[group]
        pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        sq = x.astype(jnp.float32) ** 2
        return jnp.stack([
            jnp.sum(jnp.where((pos >= o) & (pos < o + k), sq, 0.0))
            for o, k in (spec.offsets[n] for n, _ in spec.entries)])

    def to_leaves(self, group: str, sums: np.ndarray) -> Dict[str, float]:
        """Fold a group's (rows, entries) sums into per-leaf sums."""
        names = [n for n, _ in self.specs[group].entries]
        out: Dict[str, float] = {}
        for q in range(sums.shape[0]):
            for j, n in enumerate(names):
                lname = self.leaf_name(group, n, q)
                out[lname] = out.get(lname, 0.0) + float(sums[q, j])
        return out
