"""The DeepSeekMoE layer (arXiv:2401.06066, Sec. 3), from its equations.

    u = h + Attention(RMSNorm(h))          as in the dense block
    x = RMSNorm(u)
    s = Softmax(x W_router)                over the router's E experts
    g_e = s_e if e is among the top_k of s, else 0
    out = u + sum_i FFN_i^shared(x) + sum_e g_e FFN_e(x)

with every FFN gated, ``(SiLU(x W_gate) * x W_up) W_down`` of width
``moe_ff``, and every routed pair computed: no capacity and no dropped
token.  The leading ``first_k_dense_replace`` layers are dense blocks.
The balance term is the paper's expert-level one,
``E * sum_e mean_t(s_e) * (count_e / (top_k T))`` over the T tokens
routed together, added to the loss at ``aux_loss_weight`` averaged over
the MoE layers.

Where the program departs from the paper, the configuration states it,
each key listed under its ``departures``; without a key the paper holds:

  norm_topk_prob    the gates are renormalised to sum to 1 over the top_k
  capacity_factor   each expert takes at most
                    ``max(1, floor(T top_k capacity_factor / E))`` pairs of
                    the T tokens routed together, in token order; the rest
                    are dropped
  aux_loss_weight   the balance term's weight (no default: state it)
  routing_scope     ``"chip"``: the tokens routed together, for the
                    balance term and the capacity, are each chip's share
                    of the batch, not the whole batch (``"batch"``)

A layer holds experts ``0 .. n_experts - 1`` of the ``router_experts``
(default ``n_experts``) its router scores: one chip's share of a layer
whose experts are spread over chips.  It computes only their part; the
router, the shared experts and attention stay whole.  ``block``'s
``held`` computes the part of any share.

Leaves of an MoE layer, after the dense block's attention: ``ln2``,
``router`` (d, router_experts), ``sgu`` (d, 2 n_shared moe_ff) and
``sdn`` (n_shared moe_ff, d), the shared experts fused (gate | up) as
one FFN n_shared times as wide, and ``egu`` (n_experts, d, 2 moe_ff),
``edn`` (n_experts, moe_ff, d), expert-major.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.models import dense
from bench.models.dense import attention_flops_per_token  # noqa: F401
from bench.models.dense import rms_norm, with_std

DEPARTURES = ("norm_topk_prob", "capacity_factor", "aux_loss_weight",
              "routing_scope")


def first_dense(cfg: Dict) -> int:
    return int(cfg.get("first_k_dense_replace", 0))


def router_experts(cfg: Dict) -> int:
    return int(cfg.get("router_experts", cfg["n_experts"]))


def layer_kind(cfg: Dict, l: int) -> str:
    return "dense" if l < first_dense(cfg) else "moe"


def layer_leaves(cfg: Dict, l: int):
    if layer_kind(cfg, l) == "dense":
        return dense.layer_leaves(cfg, l)
    d, f, ns = cfg["d_model"], cfg["moe_ff"], cfg["n_shared"]
    out = [("ln2", (d,)), ("router", (d, router_experts(cfg)))]
    if ns:
        out += [("sgu", (d, 2 * ns * f)), ("sdn", (ns * f, d))]
    E = cfg["n_experts"]
    out += [("egu", (E, d, 2 * f)), ("edn", (E, f, d))]
    return dense.attention_leaves(cfg) + with_std(out)


def leaves(cfg: Dict):
    return dense.leaves(cfg, layer_leaves)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _gated(dot, x, wgu, wdn):
    g, u = jnp.split(dot("...d,df->...f", x, wgu), 2, axis=-1)
    return dot("...f,fd->...d", jax.nn.silu(g) * u, wdn)


def route(cfg: Dict, dot, router, x):
    """Router scores of token groups ``x`` (G, T, d): the gate of each
    expert for each token, (G, T, E), zero off the top_k and for dropped
    pairs, and the group's balance term, (G,)."""
    E, k = router.shape[-1], cfg["top_k"]
    G, T, _ = x.shape
    s = jax.nn.softmax(dot("gtd,de->gte", x, router), axis=-1)
    top, idx = jax.lax.top_k(s, k)
    if cfg.get("norm_topk_prob", False):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    pick = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (G, T, k, E)
    count = jnp.sum(pick, axis=(1, 2))                    # (G, E)
    balance = E * jnp.sum(jnp.mean(s, axis=1) * count / (k * T), axis=-1)
    cf = cfg.get("capacity_factor")
    if cf is not None:
        cap = int(max(1, (T * k * cf) // E))
        pairs = pick.reshape(G, T * k, E)                 # token-major
        ahead = jnp.cumsum(pairs, axis=1) - pairs
        kept = jnp.sum(ahead * pairs, axis=-1) < cap
        pick = pick * kept.reshape(G, T, k, 1)
    return jnp.einsum("gtke,gtk->gte", pick, top), balance


def block(cfg: Dict, l: int, dot, W, h, cos, sin, shards=(1, 1),
          held: Sequence[int] = None):
    """Layer ``l``; an MoE layer computes the part of the experts ``held``
    (ids among the layer's own, default all of them), with ``W["egu"]``
    and ``W["edn"]`` holding just those, in that order."""
    if layer_kind(cfg, l) == "dense":
        return dense.block(cfg, l, dot, W, h, cos, sin, shards)
    u = dense.attend(cfg, dot, W, h, cos, sin)
    x = rms_norm(u, W["ln2"])
    B, S, d = x.shape
    rb, sb = shards
    scope = cfg.get("routing_scope", "batch")
    if scope == "batch":
        rb = sb = 1
    elif scope != "chip":
        raise ValueError(f"routing_scope {scope!r}")
    # token groups routed together: (rows, pieces of the sequence) each
    xg = x.reshape(rb, B // rb, sb, S // sb, d).transpose(0, 2, 1, 3, 4) \
        .reshape(rb * sb, -1, d)
    gate, balance = route(cfg, dot, W["router"], xg)
    ids = jnp.arange(cfg["n_experts"]) if held is None \
        else jnp.asarray(held, jnp.int32)
    gate = gate[..., ids]                                 # (G, T, held)
    a = dot("gtd,edf->gtef", xg, W["egu"])
    g, v = jnp.split(a, 2, axis=-1)
    y = dot("gtef,efd->gtd", jax.nn.silu(g) * v * gate[..., None], W["edn"])
    if cfg["n_shared"]:
        y = y + _gated(dot, xg, W["sgu"], W["sdn"])
    y = y.reshape(rb, sb, B // rb, S // sb, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, d)
    n_moe = cfg["n_layers"] - first_dense(cfg)
    aux = cfg["aux_loss_weight"] * jnp.mean(balance) / n_moe
    return u + y, aux


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------

def matmul_params(cfg: Dict):
    """Per MoE layer: attention, the router, the shared experts and the
    top_k routed experts a token meets here, ``top_k * n_experts /
    router_experts`` of them (all top_k where the layer holds every
    expert), which has to come out whole."""
    d, f = cfg["d_model"], cfg["moe_ff"]
    routed = 3 * d * f * cfg["top_k"] * cfg["n_experts"]
    E = router_experts(cfg)
    if routed % E:
        raise ValueError(f"top_k x n_experts x 3 d moe_ff = {routed} "
                         f"routed parameters over {E} router experts is no "
                         f"whole number")
    routed //= E
    moe = dense.attention_params(cfg) + d * E + 3 * d * f * cfg["n_shared"] \
        + routed
    k = first_dense(cfg)
    dense_layer = dense.attention_params(cfg) + 3 * d * cfg.get("d_ff", 0)
    return k * dense_layer + (cfg["n_layers"] - k) * moe \
        + cfg["vocab"] * d


# ---------------------------------------------------------------------------
# the program's buffer groups (harness side)
# ---------------------------------------------------------------------------

def source(cfg: Dict, model, group: str, entry: str, q):
    """Row ``q`` of the expert stack (MoE layers x expert chunks, flat)
    holds one chunk of one layer's experts, ``n_experts / chunks`` of
    them; the other groups are laid out as for the dense model, one MoE
    layer a period."""
    if first_dense(cfg):
        raise ValueError("the program's layout of leading dense layers is "
                         "not known to bench/models/moe.py")
    if group == "experts":
        chunks = model.cfg.expert_chunks
        per = cfg["n_experts"] // chunks
        return entry, q // chunks, (q % chunks) * per
    return dense.source(cfg, model, group, entry, q)


def quant_sites(shapes: Dict[str, tuple], prefetch: int) -> Dict:
    """The dense groups as for the dense model.  The expert stack (n
    layers of nc chunks), as ``core/schedule.py``'s rings issue it under
    hpZ: where both the layer ring (depth k) and the chunk ring (depth
    kc) run, the layer ring also gathers each layer's chunk 0 ahead (n +
    k), which seeds the chunk ring, so each layer's forward gathers nc +
    kc - 1 more; the backward's recompute gathers on the hpZ tier, which
    quantizes nothing, and each layer's chunk ring reduces nc + kc times.
    Without the layer ring every chunk is gathered twice (the recompute
    re-runs the forward's gather) and reduced once."""
    sites = dense.quant_sites(shapes, prefetch)
    n, nc = shapes["experts"][0], shapes["experts"][1]
    k, kc = dense.ring(prefetch, n), dense.ring(prefetch, nc)
    ahead = int(k >= 1 and kc >= 1)
    gathers = ahead * (n + k) + n * (nc + kc - ahead)
    if k >= 1:
        reduces = n * (nc + kc)
    else:
        gathers, reduces = gathers + n * nc, n * nc
    sites["experts"] = (gathers, reduces)
    return sites
