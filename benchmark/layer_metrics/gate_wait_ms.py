"""Waiting to be let in: the `admission` span (the wait in
AdmissionController.acquire, absent on its fast path) plus `gate` (the
wait for the statement lock in Engine._dispatch_locked).

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "gate_wait_ms")
