"""Grouping sets whose groups set-up's plans packed by the displacement
network: counter `exec.agg.rollup.network`, a tally a set of a traced
grouping-set Aggregate of the sorted layout (Q67's nine; Q27's and
Q36's sets are dense levels, Q89 has none). A statement traced again
in set-up counts again. Left out where the program has no such
counter."""

import ds_classes


def read(ctx):
    return ds_classes.setup_count(ctx, "exec.agg.rollup.network")
