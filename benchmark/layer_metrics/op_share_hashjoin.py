"""The share of device busy time whose innermost plan-operator scope is a
`hashjoin.<n>` (build, probe, expand)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_share_hashjoin")
