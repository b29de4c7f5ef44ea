"""The share of the mix slice's device idle time in which no statement span
was open in the server: the device idled because no work had arrived,
not because the host path was slow.

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "idle_no_stmt_share")
