"""What no layer metric of the span tree holds: the served root's and the
statement span's own time, bookkeeping between the layers
(`span_reduce.layer_ms`'s `other_ms`, printed since PR 24 and a metric
since PR 38).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "other_ms")
