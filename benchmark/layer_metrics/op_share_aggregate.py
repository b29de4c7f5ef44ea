"""The share of device busy time whose innermost plan-operator scope is an
`aggregate.<n>`: a share that falls is an operator that got cheaper
beside the rest."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_share_aggregate")
