"""Programs dispatched to the device a statement of the window:
`exec.dispatch.programs`, counted where each is called (the plan's
executable in Prepared.dispatch; a flag reduction a sentinel column, the
pack and any eager gather in ops/batch.py).
Left out where the program has no such counter."""

import span_reduce

COUNTERS = ["exec.dispatch.programs"]


def read(ctx):
    return span_reduce.per_statement(ctx, COUNTERS)
