"""Composite-key joins past the packed direct table that set-up's plans
traced in a form with no data-dependent loop: counters
`exec.join.strategy.bounded` + `.sorted` (one tally a traced join; Q80's
three sales-to-returns joins and Q5's web return to its sale: 4). Left
out where the program has no such counters."""

import nested_classes

COUNTERS = ["exec.join.strategy.bounded", "exec.join.strategy.sorted"]


def read(ctx):
    return nested_classes.setup_count(ctx, COUNTERS, "exec.join.strategy.")
