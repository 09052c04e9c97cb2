"""Share of the traced window in the program's `fused.pull` spans: the
host waiting for and copying a fused epoch program's outputs."""


def read(ctx):
    return ctx.program_share("fused.pull")
