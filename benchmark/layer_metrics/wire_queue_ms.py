"""The wait of a statement's frame for a worker: the `wire.queue` span,
from the reactor's stamp of the frame's arrival (pgfront._enqueue) to
the worker's pick-up (pgwire._Conn.process).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "wire_queue_ms")
