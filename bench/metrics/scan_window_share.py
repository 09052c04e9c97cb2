"""Share of the traced window inside the epoch loop's K-epoch scan
windows: the program's `loop.scan` spans, one per `_run_scan_window` call
(`repro.core.vectorized_cluster.run_for`), each holding its window's
pending pops, sampling, dispatch, pull, delivery, logs and bookkeeping."""


def read(ctx):
    return ctx.program_share("loop.scan")
