"""Plain GROUP BYs past the dense bound that set-up's plans grouped by
the sorted layout: counter `exec.agg.sorted.group_by`, a tally a traced
Aggregate whose keys pack and whose batch holds SORTED_GROUP_MIN_ROWS
rows or more (Q89's, one; Q67's grouping sets are counted apart, by
`ds_rollup_network_sets`). A statement traced again in set-up counts
again. Left out where the program has no such counter."""

import ds_classes


def read(ctx):
    return ds_classes.setup_count(ctx, "exec.agg.sorted.group_by")
