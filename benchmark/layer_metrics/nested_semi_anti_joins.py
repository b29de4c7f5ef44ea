"""Semi- and anti-joins that set-up's plans traced: counters
`exec.join.kind.semi` + `exec.join.kind.anti`, one tally a traced hash
join. What EXISTS and NOT EXISTS really became: 0 where they unnest
into grouped LEFT JOINs instead (Q21's two tests, whose correlation
holds an inequality, still do). Left out where the program has no such
counter."""

import nested_classes

COUNTERS = ["exec.join.kind.semi", "exec.join.kind.anti"]


def read(ctx):
    return nested_classes.setup_count(ctx, COUNTERS, "exec.join.kind.")
