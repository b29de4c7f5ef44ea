"""Temp tables materialized for a CTE, a derived table or a set
operation inside the window: counter `exec.cte.temps`, counted at every
execution (must be 0: every WITH and UNION ALL planned in place). Left
out where the program has no such counter."""


def read(ctx):
    d = ctx["counters"]["window"]
    return float(d["exec.cte.temps"]) if "exec.cte.temps" in d else None
