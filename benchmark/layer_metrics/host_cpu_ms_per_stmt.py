"""CPU the server's Python threads spent a statement of the window: the
four `process.threads.cpu.seconds.*` counters (reactor, workers, mesh
dispatchers, the rest; each thread's own CPU clock, read at snapshot
time) over the statements completed, in ms. With one interpreter this
is what bounds `stmts_per_s` where the host is the bottleneck. Left out
on a program without the counters."""

import host_reduce


def read(ctx):
    cpu = host_reduce.window_cpu(ctx)
    n = ctx["client"]["completed"]
    return 1000.0 * cpu["threads"] / n if cpu and n else None
