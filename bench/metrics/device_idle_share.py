"""Share of the traced window in which no operation runs on the device,
in %, the mean over the cell's chips."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s.mean("busy_ns") / s.window_ns)
