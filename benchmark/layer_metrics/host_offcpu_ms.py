"""Time a statement's thread was off the processor outside the five
named waits (`pull`, `queue`, `gate`, `admission`, `wire.queue`): self
wall less self CPU (less CPU another thread spent for the span) summed
over every other span of the served root, in the slice of the cell's own
mix with the collector on and no profiler. With one session it is the
OS's; with several it is the wait for the interpreter lock.

A class's mean, mean over classes (host_reduce.py)."""

import host_reduce


def read(ctx):
    return host_reduce.metric(ctx, "host_offcpu_ms")
