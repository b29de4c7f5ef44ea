"""Rows probed a statement of the window: counter `exec.join.probe_rows`,
per dispatched statement the rows each of its joins' probes is traced
over (after any compaction), summed over its joins. Left out where the
program has no such counter."""

import span_reduce

COUNTERS = ["exec.join.probe_rows"]


def read(ctx):
    return span_reduce.per_statement(ctx, COUNTERS)
