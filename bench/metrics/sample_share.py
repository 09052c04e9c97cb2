"""Share of the traced window inside `SampleStage.run` (network sampling)."""


def read(ctx):
    return ctx.share("sample")
