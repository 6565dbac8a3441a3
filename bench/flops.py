"""Operations and bytes the algorithm needs, from a configuration's shapes.

``flops_per_token``: the model FLOPs of one training token, forward and
backward, with no recompute: 6 x the matmul parameters a token meets
plus attention's score and value products, as the configuration's module
counts them (``bench/models/<model>.py``).
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import models


def matmul_params(cfg: Dict):
    return models.load(cfg).matmul_params(cfg)


def attention_flops_per_token(cfg: Dict, seq: int) -> int:
    return models.load(cfg).attention_flops_per_token(cfg, seq)


def flops_per_token(cfg: Dict, seq: int):
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
                       grad_bits: int = 4, block: int = 256,
                       cfg: Optional[Dict] = None) -> Dict:
    """Calls per step of the five quantize kernels on each device, and the
    least bytes they move, from the flat buffer shapes and the world.

    Every weight gather (qwZ) quantizes the device's fp32 shard and
    dequantizes the gathered payload to bf16; every gradient reduce (qgZ,
    two hops over an ``intra`` x ``world / intra`` mesh) quantizes the
    bf16 gradient reordered, dequantizes-reduces-requantizes the intra
    hop's contributions, and dequantizes-reduces the inter hop's into the
    fp32 shard.  How many gathers and reduces each group issues a step is
    the configuration's module's count (``quant_sites``; the dense model's
    without ``cfg``).
    """
    sites = models.load(cfg or {}).quant_sites(shapes, prefetch)
    wq, gq = weight_bits / 8, grad_bits / 8

    def scales(n):
        return SCALE_BYTES * n / block

    calls, nbytes = 0, 0.0
    for group, (gathers, reduces) in sites.items():
        n = shapes[group][-1]
        shard, hop = n / world, n / intra
        gather = (4 * shard + wq * shard + scales(shard)      # quantize
                  + wq * n + scales(n) + 2 * n)               # dequantize
        reduce = (2 * n + gq * n + scales(n)                  # reorder-quant
                  + gq * n + scales(n) + gq * hop + scales(hop)
                  + gq * hop + scales(hop) + 4 * shard)       # last hop
        calls += 2 * gathers + 3 * reduces
        nbytes += gathers * gather + reduces * reduce
    return {"calls_per_step": calls, "bytes_per_step": nbytes}
