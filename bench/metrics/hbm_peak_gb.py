"""Peak device memory in use, GB (1e9 bytes), on the fullest chip:
``memory_stats()["peak_bytes_in_use"]`` read after the window."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
