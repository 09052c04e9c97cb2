"""Epochs dispatched inside `DomEngine.run_epoch_window` scans, in percent
of all epochs that ran work in the traced window (an exact count)."""


def read(ctx):
    work = ctx.counters.get("work_epochs", 0)
    if not work:
        return None
    return 100.0 * ctx.counters.get("scan_epochs", 0) / work
