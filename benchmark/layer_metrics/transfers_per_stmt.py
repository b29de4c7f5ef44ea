"""Transfers across the host/device boundary a statement of the window:
`exec.transfer.h2d.calls` (host values handed over with a dispatch: the
scalars of Prepared.dispatch, gather indices) plus
`exec.transfer.d2h.calls` (result pulls in ops/batch.pull_arrays).
Left out where the program has no such counter."""

import span_reduce

COUNTERS = ["exec.transfer.h2d.calls", "exec.transfer.d2h.calls"]


def read(ctx):
    return span_reduce.per_statement(ctx, COUNTERS)
