"""Weights made by the benchmark from ``--seed``, for the program and the
reference alike.

Every tensor of the model (a *leaf*: ``emb``, ``layer<l>.<name>``,
``fnorm``, ``unemb``) is drawn from its own key, folded from the seed, the
layer and the leaf's name, so the reference can draw any leaf again by
itself.  Norm offsets start at zero (the blocks scale by ``1 + offset``);
the embedding is N(0, 0.02^2); every projection is N(0, 1/fan_in), the
unembedding (stored vocab-major, ``(V, d)``) N(0, 1/d).

:class:`Packer` lays the leaves out in the program's flat ZeRO buffers
(one jitted call per buffer group, straight into the mesh sharding) and
reads per-leaf sums of squares back out of them.  It reads the layout from
the program's model object; the reference never sees that layout.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.tokens import SEED_MASK

Shape = Tuple[int, ...]


def key_data(seed: int) -> np.ndarray:
    """The seed as two uint32 words: every 64-bit seed is its own key."""
    s = int(seed) & SEED_MASK
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def layer_leaves(cfg: Dict) -> List[Tuple[str, Shape]]:
    """One dense block's leaves, in the order the program's block reads
    them (RMSNorm, GQA attention, RMSNorm, gated MLP with fused gate|up)."""
    d, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff = cfg["head_dim"], cfg["d_ff"]
    return [("ln1", (d,)), ("wq", (d, H * hd)), ("wk", (d, K * hd)),
            ("wv", (d, K * hd)), ("wo", (H * hd, d)), ("ln2", (d,)),
            ("wgu", (d, 2 * ff)), ("wdn", (ff, d))]


def leaves(cfg: Dict) -> List[Tuple[str, Shape]]:
    """Every leaf of the model, named as the reference and the checks
    name them."""
    d, V = cfg["d_model"], cfg["vocab"]
    out = [("emb", (V, d))]
    for l in range(cfg["n_layers"]):
        out += [(f"layer{l}.{n}", s) for n, s in layer_leaves(cfg)]
    return out + [("fnorm", (d,)), ("unemb", (V, d))]


def leaf_ids(name: str) -> Tuple[int, int, float]:
    """(layer id, name id, base name) of a leaf name."""
    if name.startswith("layer"):
        layer, base = name[len("layer"):].split(".", 1)
        lid = int(layer) + 1
    else:
        lid, base = 0, name
    return lid, zlib.crc32(base.encode()) & 0x7FFFFFFF, base


def leaf_std(name: str, shape: Shape) -> float:
    base = leaf_ids(name)[2]
    if len(shape) == 1:
        return 0.0
    if base == "emb":
        return 0.02
    if base == "unemb":
        return float(shape[1]) ** -0.5
    return float(shape[0]) ** -0.5


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

    A group is a stack of rows (one per layer period, or per vocabulary
    chunk of the unembedding) or a single row; each row packs its entries
    in the program's order, padded with zeros to the row length."""

    def __init__(self, model, cfg: Dict):
        if model.rem_spec is not None or model.is_moe or \
                model.embed_spec is None:
            raise ValueError("Packer lays out dense token-input stacks")
        self.cfg = cfg
        self.shapes = model.param_shapes()
        self.period = len(model.period)
        self.vc = model.vchunk
        self.specs = {"embed": model.embed_spec, "blocks": model.period_spec,
                      "head": model.head_spec, "unemb": model.unemb_spec}
        assert set(self.specs) == set(self.shapes), self.shapes
        self.leaf_shapes = dict(leaves(cfg))

    def rows(self, group: str) -> int:
        shape = self.shapes[group]
        return 1 if len(shape) == 1 else shape[0]

    def leaf_name(self, group: str, entry: str, r: int) -> str:
        """The leaf an entry of row ``r`` (a Python int) holds part of."""
        if group == "blocks":
            i, base = entry.split(".", 1)
            return f"layer{r * self.period + int(i)}.{base}"
        return "unemb" if group == "unemb" else entry

    def row(self, kd, group: str, r) -> jax.Array:
        """Row ``r`` (may be traced) of the group's flat buffer, fp32."""
        parts = []
        for entry, shape in self.specs[group].entries:
            if group == "blocks":
                i, base = entry.split(".", 1)
                name = f"layer0.{base}"
                lid = r * self.period + int(i) + 1
            else:
                name = self.leaf_name(group, entry, 0)
                lid = 0
            full_shape = self.leaf_shapes[name]
            _, nid, _ = leaf_ids(name)
            x = leaf_value(kd, lid, nid, leaf_std(name, full_shape),
                           full_shape)
            if group == "unemb":
                x = jax.lax.dynamic_slice_in_dim(x, r * self.vc, self.vc)
            assert x.shape == tuple(shape), (group, entry, x.shape, shape)
            parts.append(x.reshape(-1))
        flat = jnp.concatenate(parts)
        return jnp.pad(flat, (0, self.shapes[group][-1] - flat.shape[0]))

    def build(self, kd, group: str) -> jax.Array:
        """The group's global flat buffer (trace inside a jit); the rows
        of a stack come from one traced row body."""
        if len(self.shapes[group]) == 1:
            return self.row(kd, group, 0)
        return jax.lax.map(lambda r: self.row(kd, group, r),
                           jnp.arange(self.rows(group), dtype=jnp.int32))

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
        for r in range(sums.shape[0]):
            for j, n in enumerate(names):
                lname = self.leaf_name(group, n, r)
                out[lname] = out.get(lname, 0.0) + float(sums[r, j])
        return out
