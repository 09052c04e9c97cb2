"""Epoch programs traced per window repetition: the program's
`programs_traced.step` and `programs_traced.scan` counters (one count per
trace) over the window's repetitions.  0 where the program recorded and
traced nothing; None where it recorded nothing."""


def read(ctx):
    if not ctx.program_counters or not ctx.reps:
        return None
    traced = (ctx.program_counters.get("programs_traced.step", 0)
              + ctx.program_counters.get("programs_traced.scan", 0))
    return traced / ctx.reps
