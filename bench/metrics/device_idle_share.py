"""Device idle time over one traced repetition: 1 - busy / window, in
percent, from the profiler trace (`bench/trace.py`)."""


def read(ctx):
    if ctx.device is None or not ctx.device["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
