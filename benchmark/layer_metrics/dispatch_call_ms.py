"""CPU of the executable's call returning: stage `call` of the `dispatch`
span (Prepared.dispatch), whichever thread ran it: the statement's own
on one chip, the `mesh-dispatch-*` thread's on a mesh
(queued_collective_call credits the stage with it).

A class's mean (a CPU clock may tick: host_reduce.py), mean over
classes, mix slice."""

import host_reduce


def read(ctx):
    return host_reduce.stage_ms(ctx, "call", "cpu")
