"""Rows the grouping sets above the finest are traced over, a statement
of the window: counter `exec.agg.rollup.rows` (the finest set's group
slots, a set a dispatch; never the fact rows, which would read 2^22 a
set). Left out where the program has no such counter."""

import span_reduce


def read(ctx):
    return span_reduce.per_statement(ctx, ["exec.agg.rollup.rows"])
