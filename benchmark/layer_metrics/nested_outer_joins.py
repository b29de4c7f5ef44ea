"""Left outer joins that set-up's plans traced: counter
`exec.join.kind.left`, one tally a traced hash join (Q13's own, and
the grouped sub-selects Q17's scalar subquery and Q21's inequality
tests join back). Left out where the program has no such counter."""

import nested_classes

COUNTERS = ["exec.join.kind.left"]


def read(ctx):
    return nested_classes.setup_count(ctx, COUNTERS, "exec.join.kind.")
