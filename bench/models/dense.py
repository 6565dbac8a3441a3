"""The dense GQA block, as the repository's dense models run it.

RMSNorm with a ``1 + offset`` scale, RoPE on rotated halves, causal GQA
attention, a gated MLP (fused gate|up, SiLU or tanh-GELU) and residuals;
every layer alike.  Leaves: the embedding ``emb`` (V, d), N(0, 0.02^2);
``layer<l>.<name>`` per layer; the final norm ``fnorm``; the unembedding
``unemb``, stored vocab-major (V, d), N(0, 1/d).  Norm offsets start at
zero, every projection is N(0, 1/fan_in).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6         # the blocks' RMSNorm epsilon
ATTN_QUERIES = 1024     # queries per attention chunk

Leaf = Tuple[str, Tuple[int, ...], float]


def init_std(base: str, shape) -> float:
    if len(shape) == 1:
        return 0.0
    if base == "emb":
        return 0.02
    if base == "unemb":
        return float(shape[1]) ** -0.5
    return float(shape[-2]) ** -0.5


def with_std(entries) -> List[Leaf]:
    return [(n, s, init_std(n, s)) for n, s in entries]


def attention_leaves(cfg: Dict) -> List[Leaf]:
    d, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["head_dim"]
    return with_std([("ln1", (d,)), ("wq", (d, H * hd)), ("wk", (d, K * hd)),
                     ("wv", (d, K * hd)), ("wo", (H * hd, d))])


def layer_leaves(cfg: Dict, l: int) -> List[Leaf]:
    """One dense block's leaves, in the order the program's block reads
    them (RMSNorm, GQA attention, RMSNorm, gated MLP with fused gate|up)."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    return attention_leaves(cfg) + with_std(
        [("ln2", (d,)), ("wgu", (d, 2 * ff)), ("wdn", (ff, d))])


def layer_kind(cfg: Dict, l: int) -> str:
    return "dense"


def leaves(cfg: Dict, layer_leaves=layer_leaves) -> List[Leaf]:
    """Every leaf of the model, named as the reference and the checks
    name them."""
    d, V = cfg["d_model"], cfg["vocab"]
    out = with_std([("emb", (V, d))])
    for l in range(cfg["n_layers"]):
        out += [(f"layer{l}.{n}", s, std)
                for n, s, std in layer_leaves(cfg, l)]
    return out + with_std([("fnorm", (d,)), ("unemb", (V, d))])


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def rms_norm(x, offset):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + offset)


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def causal_attention(dot, q, k, v):
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    kr, vr = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    C = min(ATTN_QUERIES, S)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def chunk(args):
        qc, q0 = args
        logits = dot("bqhd,bkhd->bhqk", qc, kr) * hd ** -0.5
        mask = (q0 + jnp.arange(C))[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return dot("bhqk,bkhd->bqhd", p, vr)

    qs = q.reshape(B, S // C, C, H, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(chunk, (qs, jnp.arange(S // C) * C))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def attend(cfg: Dict, dot, W, h, cos, sin):
    """``h`` plus the block's attention (its first residual branch)."""
    B, S, d = h.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    x = rms_norm(h, W["ln1"])
    q = dot("bsd,de->bse", x, W["wq"]).reshape(B, S, H, hd)
    k = dot("bsd,de->bse", x, W["wk"]).reshape(B, S, K, hd)
    v = dot("bsd,de->bse", x, W["wv"]).reshape(B, S, K, hd)
    o = causal_attention(dot, rope(q, cos, sin), rope(k, cos, sin), v)
    return h + dot("bse,ed->bsd", o.reshape(B, S, H * hd), W["wo"])


def act(cfg: Dict, g):
    if cfg["act"] == "silu":
        return jax.nn.silu(g)
    if cfg["act"] == "gelu":
        return jax.nn.gelu(g, approximate=True)
    raise ValueError(cfg["act"])


def block(cfg: Dict, l: int, dot, W, h, cos, sin, shards=(1, 1)):
    """Every operation of the block is per token, so ``shards`` changes
    nothing; it adds nothing to the loss."""
    h = attend(cfg, dot, W, h, cos, sin)
    x = rms_norm(h, W["ln2"])
    g, u = jnp.split(dot("bsd,df->bsf", x, W["wgu"]), 2, axis=-1)
    return h + dot("bsf,fd->bsd", act(cfg, g) * u, W["wdn"]), \
        jnp.float32(0.0)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------

def attention_params(cfg: Dict) -> int:
    d, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["head_dim"]
    return d * H * hd + 2 * d * K * hd + H * hd * d


def matmul_params(cfg: Dict) -> int:
    """Every projection and the unembedding; the embedding is a lookup."""
    per_layer = attention_params(cfg) + 3 * cfg["d_model"] * cfg["d_ff"]
    return cfg["n_layers"] * per_layer + cfg["vocab"] * cfg["d_model"]


def attention_flops_per_token(cfg: Dict, seq: int) -> int:
    """Causal attention's score and value products: 2 x seq x (heads x
    head_dim) a token a layer forward, half of it masked, times 3 for the
    backward."""
    return cfg["n_layers"] * 3 * 2 * seq * cfg["n_heads"] * cfg["head_dim"]


# ---------------------------------------------------------------------------
# the program's buffer groups (harness side)
# ---------------------------------------------------------------------------

def source(cfg: Dict, model, group: str, entry: str, q):
    """A stacked period row holds one layer per period position (entries
    ``<i>.<name>``), the remainder group the layers after the last whole
    period, each unembedding row one vocabulary chunk."""
    if group in ("blocks", "rem"):
        i, base = entry.split(".", 1)
        first = q * len(model.period) if group == "blocks" \
            else model.n_periods * len(model.period)
        return base, first + int(i), 0
    if group == "unemb":
        return "unemb", None, q * model.vchunk
    return entry, None, 0


def ring(prefetch: int, n: int) -> int:
    """Ring depth of an ``n``-step scan: the program's prefetch, at most
    n - 1; a single step is synchronous."""
    return min(prefetch, n - 1) if n >= 2 else 0


def quant_sites(shapes: Dict[str, tuple], prefetch: int) -> Dict:
    """The layer stack's prefetch ring gathers and reduces ``layers +
    ring depth`` times; the embedding, final norm and remainder layers
    once; the unembedding once per vocabulary chunk."""
    rows = shapes["blocks"][0]
    sites = {"embed": 1, "head": 1, "unemb": shapes["unemb"][0],
             "blocks": rows + ring(prefetch, rows)}
    if "rem" in shapes:
        sites["rem"] = 1
    return {g: (n, n) for g, n in sites.items()}
