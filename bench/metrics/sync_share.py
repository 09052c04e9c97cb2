"""Share of the traced window inside `DomEngine.advance_sync`; recorded only
where a modeled clock-sync daemon runs."""


def read(ctx):
    return ctx.share("sync")
