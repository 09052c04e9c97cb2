"""Share of the traced window inside `WorkloadDriver.inject_open_loop`."""


def read(ctx):
    return ctx.share("inject")
