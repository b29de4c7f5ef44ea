"""Grouping sets that set-up's plans traced: counter
`exec.agg.grouping_sets`, a tally a set of a traced grouping-set
Aggregate (ROLLUP of k keys is k + 1 sets: Q27 3, Q36 3, Q67 9). A
statement traced again in set-up counts again. Left out where the
program has no such counter."""

import ds_classes


def read(ctx):
    return ds_classes.setup_count(ctx, "exec.agg.grouping_sets")
