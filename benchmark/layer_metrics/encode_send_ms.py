"""Rows to the socket: the `encode` span (pgwire._send_result) plus `send`
(the flush that carries ReadyForQuery).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "encode_send_ms")
