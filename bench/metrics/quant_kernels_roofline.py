"""Roofline share of the ZeRO++ quantize kernels, in %: the least time
the bytes they must move take at the chip's HBM bandwidth (they are
elementwise or reductions, so bandwidth bounds them), over their summed
device time, the mean over the cell's chips.  Reads nothing where no
kernel ran; fails the run where the kernel events in the trace are not
the calls ``bench/flops.py`` counts for the traced steps, since the bytes
would then not be the kernels' bytes."""


def read(ctx):
    s, calls = ctx["summary"], ctx["kernel_calls"]
    if not s.mean("kernel_ns"):
        return None
    want = calls["calls_per_step"] * ctx["steps"]
    got = {dev: d.kernel_count for dev, d in s.devices.items()}
    if any(n != want for n in got.values()):
        raise RuntimeError(
            f"quant_kernels_roofline: the trace holds {got} kernel calls, "
            f"bench/flops.py counts {calls['calls_per_step']} a step x "
            f"{ctx['steps']} steps = {want}")
    least_s = calls["bytes_per_step"] * ctx["steps"] / \
        ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (s.mean("kernel_ns") / 1e9)
