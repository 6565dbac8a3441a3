"""Share of the traced window in which a collective runs on the device
and no other operation does, in %, the mean over the cell's chips."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * s.mean("collective_exposed_ns") / s.window_ns
