"""Blocked on the device and the transfer: the `pull` spans
(ops/batch.pull_arrays), which hold the device program's run time when
one session has the chip.

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "pull_wait_ms")
