"""CPU of the process that none of its Python threads spent, a statement
of the window: `process.cpu.seconds` less the four
`process.threads.cpu.seconds.*`, in ms: the runtime's native threads
(XLA's and the TPU client's pools, transfers). It does not hold the
interpreter lock, so it costs a core and not `stmts_per_s`, unless the
cores run out. Left out on a program without the counters."""

import host_reduce


def read(ctx):
    cpu = host_reduce.window_cpu(ctx)
    n = ctx["client"]["completed"]
    return 1000.0 * cpu["runtime"] / n if cpu and n else None
