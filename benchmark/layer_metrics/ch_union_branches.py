"""UNION ALL branches that set-up's plans traced into device programs:
counter `exec.setop.union_all.branches` (Q5 nine, Q77 three, Q80 three:
15). Left out where the program has no such counter."""

import ds_classes


def read(ctx):
    return ds_classes.setup_count(ctx, "exec.setop.union_all.branches")
