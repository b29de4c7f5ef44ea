"""Joins that set-up's plans traced on the while-loop hash table:
counter `exec.join.strategy.hash` (must be 0). Left out where the
program has no such counter."""

import nested_classes

COUNTERS = ["exec.join.strategy.hash"]


def read(ctx):
    return nested_classes.setup_count(ctx, COUNTERS, "exec.join.strategy.")
