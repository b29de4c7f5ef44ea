"""Parse and prepare on the host: the `parse` span (Engine._parse_cached)
plus the self time of `plan` (Engine._prepare_select_inner less the
`compile` and `upload` beneath it).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "parse_plan_ms")
