"""Share of the device's busy time spent in the ZeRO++ quantize kernels,
in %, the mean over the cell's chips."""


def read(ctx):
    s = ctx["summary"]
    if not s.mean("kernel_ns"):
        return None
    return 100.0 * s.mean("kernel_ns") / s.mean("busy_ns")
