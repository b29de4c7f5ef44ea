"""Host time to hand the program to the device: the `dispatch` span
(Prepared.run: refresh, read timestamp, scalars, the call returning)
less the `queue` beneath it (the mesh dispatcher's queue).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "dispatch_host_ms")
