"""From device arrays to rows: the `materialize` span
(ScanPlane._materialize) less its `pull`s, which is the flag dispatches
before the pull and the `decode` span after it.

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "decode_ms")
