"""Share of the traced window in the epoch loop's own host work: the
program's `loop.pending`, `loop.absorb` and `loop.faults` spans
(`repro.core.vectorized_cluster.run_for`)."""


def read(ctx):
    return ctx.program_share("loop.pending", "loop.absorb", "loop.faults")
