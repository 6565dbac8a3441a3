"""Model FLOP/s utilization of the whole step, in % of the chips' bf16
peak: the traced window's tokens/s x model FLOPs per token (no recompute,
``bench/flops.py``) / (chips x peak)."""


def read(ctx):
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return 100.0 * ctx["tokens_per_s"] * ctx["flops_per_token"] / peak
