"""The `materialize` span's own time before its first pull: stage `flags`
(ScanPlaneMixin._materialize: the sentinel programs dispatched, the
column lists), wall.

Median a class, mean over classes, mix slice (host_reduce.py)."""

import host_reduce


def read(ctx):
    return host_reduce.stage_ms(ctx, "flags", "self_wall")
