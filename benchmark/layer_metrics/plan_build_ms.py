"""CPU of the planner on a statement whose compiled plan is cached:
stage `build` of the `plan` span (Engine._prepare_select_planned: the
seal check and Engine._plan, before the placement verdict).

A class's mean (a CPU clock may tick: host_reduce.py), mean over
classes, mix slice."""

import host_reduce


def read(ctx):
    return host_reduce.stage_ms(ctx, "build", "cpu")
