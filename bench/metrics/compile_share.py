"""Share of the traced window in JAX tracing, lowering and backend compile
or persistent-cache load (JAX's own compile spans)."""


def read(ctx):
    return ctx.share("compile")
