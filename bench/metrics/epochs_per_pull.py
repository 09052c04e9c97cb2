"""Epochs with work per device-to-host pull over the traced window: the
benchmark's count of epochs that ran work (`work_epochs`, scanned or
stepped) over the program's `fused.pull` spans, one per dispatch of the
fused epoch step or of a K-epoch scan window.  Epochs with nothing to
dispatch count in neither.  None without a program record."""


def read(ctx):
    pulls = len(ctx.program_spans.get("fused.pull", []))
    return ctx.counters.get("work_epochs", 0) / pulls if pulls else None
