"""Time the collector's generation-2 passes stopped the interpreter in
the window, all of them together, in ms (`process.gc.pause.seconds.gen2`,
a `gc.callbacks` stopwatch; how many there were is on the `#
host_process` line). A full pass walks every container the process
holds, so it is one statement's latency, not every statement's. Left
out on a program without the counters."""

import host_reduce


def read(ctx):
    cpu = host_reduce.window_cpu(ctx)
    return 1000.0 * cpu["gc_gen2"][1] if cpu else None
