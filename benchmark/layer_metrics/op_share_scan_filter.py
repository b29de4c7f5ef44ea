"""The share of device busy time whose innermost plan-operator scope is a
`scan.<n>` or a `filter.<n>`."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_share_scan_filter")
