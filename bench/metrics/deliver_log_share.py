"""Share of the traced window inside `DeliverStage.run` and `LogStage.run`."""


def read(ctx):
    return ctx.share("deliver", "log")
