"""CPU the observability plane costs a statement with everything off:
stages `setup` (Engine._execute_stmt_inner up to the statement's span:
settings, fingerprint, diagnostics check, profile sink, admission) and
`account` (from the span's close: counters, sqlstats, devstats, the
tenant rollup), marked on the served root.

A class's mean (a CPU clock may tick: host_reduce.py), mean over
classes, mix slice."""

import host_reduce


def read(ctx):
    parts = [host_reduce.stage_ms(ctx, s, "cpu")
             for s in ("setup", "account")]
    return None if None in parts else sum(parts)
