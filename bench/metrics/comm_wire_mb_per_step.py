"""Bytes each device puts on the wire per step, in MB (1e6 bytes), summed
over the ZeRO++ collective labels: the exact count of
``repro.launch.jaxpr_analysis.analyze_jaxpr`` over the step's jaxpr."""


def read(ctx):
    total = sum(ctx["wire_by_label"].values())
    return total / 1e6 if total else None
