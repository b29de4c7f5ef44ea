"""The share of device busy time whose innermost plan-operator scope is a
`hashjoin.<n>` (build, probe, expand) in `ssb_sf1.flight`: the reader of
`op_share_hashjoin`, whose list of cells this cell is not on, under a
name of its own."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_share_hashjoin")
