"""Subquery results that reached a dispatched program as constants of
its plan, over set-up and the window: counter `exec.subquery.inlined`.
Each is another compiled program for another load of the tables (a cold
compile in every run, whatever the compile cache holds); 0 where every
subquery's result is an argument of the program (`exec.subquery.args`)
or a join. Left out where the program has no such counter."""

COUNTER = "exec.subquery.inlined"


def read(ctx):
    phases = [ctx["counters"]["setup"], ctx["counters"]["window"]]
    if not any(COUNTER in d for d in phases):
        return None
    return float(sum(d.get(COUNTER, 0) for d in phases))
