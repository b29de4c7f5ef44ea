"""Rows the Windows sort, a statement of the window: counter
`exec.window.rows` (a Window's input batch a dispatch, a prefix of a
hash Aggregate's slots where one was given). Left out where the program
has no such counter."""

import span_reduce


def read(ctx):
    return span_reduce.per_statement(ctx, ["exec.window.rows"])
