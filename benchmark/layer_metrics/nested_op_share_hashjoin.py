"""The share of device busy time whose innermost plan-operator scope is a
`hashjoin.<n>` (build, probe, expand) in `tpch_sf1_full.nested`: the
reader of `op_share_hashjoin`, whose list of cells this cell may not be
put on, under a name of its own."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_share_hashjoin")
