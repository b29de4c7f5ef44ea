"""Prepares of the run, set-up and window, whose placement verdict was
not `resident`: counters `sql.exec.placement.{stream,spill,distributed}`,
one a prepare (the planner runs on every statement, plan-cache hit or
not). Must be 0 in a one-chip cell whose tables fit the chip: a
statement paged through HBM or spilled is another program.
Left out where the program has no such counters."""

PREFIX = "sql.exec.placement."


def read(ctx):
    phases = [ctx["counters"]["setup"], ctx["counters"]["window"]]
    if not any(PREFIX + "resident" in d for d in phases):
        return None
    return float(sum(d.get(PREFIX + verdict, 0) for d in phases
                     for verdict in ("stream", "spill", "distributed")))
