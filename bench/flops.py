"""Operations and bytes the algorithm needs, from a configuration's shapes.

``flops_per_token``: the model FLOPs of one training token, forward and
backward, with no recompute: 6 x the matmul parameters (every projection
and the unembedding; the embedding is a lookup) plus causal attention,
whose score and value products cost 2 x seq x (heads x head_dim) a token
a layer forward, half of it masked, times 3 for the backward.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(cfg: Dict) -> int:
    d, H, K = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    return cfg["n_layers"] * per_layer + V * d


def attention_flops_per_token(cfg: Dict, seq: int) -> int:
    return cfg["n_layers"] * 3 * 2 * seq * cfg["n_heads"] * cfg["head_dim"]


def flops_per_token(cfg: Dict, seq: int) -> int:
    return 6 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)


# ---------------------------------------------------------------------------
# the ZeRO++ quantize kernels
# ---------------------------------------------------------------------------

# their operations in the trace: Pallas calls under the qwZ gather and
# qgZ reduce scopes
QUANT_KERNELS = (r'^%[^ =]*(qwz_gather|qgz_reduce)[^ =]* = .*'
                 r'custom_call_target="tpu_custom_call"',)
SCALE_BYTES = 4          # one fp32 scale per quantization block


def quant_kernel_calls(shapes: Dict[str, tuple], world: int, intra: int,
                       prefetch: int, weight_bits: int = 8,
                       grad_bits: int = 4, block: int = 256) -> Dict:
    """Calls per step of the five quantize kernels on each device, and the
    least bytes they move, from the flat buffer shapes and the world.

    Every weight gather (qwZ) quantizes the device's fp32 shard and
    dequantizes the gathered payload to bf16; every gradient reduce (qgZ,
    two hops over an ``intra`` x ``world / intra`` mesh) quantizes the
    bf16 gradient reordered, dequantizes-reduces-requantizes the intra
    hop's contributions, and dequantizes-reduces the inter hop's into the
    fp32 shard.  The layer stack's prefetch ring gathers and reduces
    ``layers + ring depth`` times; the embedding and final norm once; the
    unembedding once per vocabulary chunk.
    """
    rows = shapes["blocks"][0]
    ring = min(prefetch, rows - 1) if rows >= 2 else 0
    sites = {"embed": 1, "head": 1, "unemb": shapes["unemb"][0],
             "blocks": rows + ring}
    wq, gq = weight_bits / 8, grad_bits / 8

    def scales(n):
        return SCALE_BYTES * n / block

    calls, nbytes = 0, 0.0
    for group, count in sites.items():
        n = shapes[group][-1]
        shard, hop = n / world, n / intra
        gather = (4 * shard + wq * shard + scales(shard)      # quantize
                  + wq * n + scales(n) + 2 * n)               # dequantize
        reduce = (2 * n + gq * n + scales(n)                  # reorder-quant
                  + gq * n + scales(n) + gq * hop + scales(hop)
                  + gq * hop + scales(hop) + 4 * shard)       # last hop
        calls += 5 * count
        nbytes += count * (gather + reduce)
    return {"calls_per_step": calls, "bytes_per_step": nbytes}
