"""Host milliseconds a subquery's own execution takes inside the window:
the mean of the `subquery` span (Prepared.subquery_params: the
subquery's prepared statement dispatched, pulled and decoded at the
outer statement's read timestamp, a child of the statement's root
beside `plan` and `dispatch`), which the program also observes into the
histogram `exec.subquery.seconds` with the span's own two stamps. Q22's
average is the cell's one. Left out where the program has no such
histogram, or no subquery ran in the window."""

HISTOGRAM = "exec.subquery.seconds"


def read(ctx):
    window = ctx["counters"]["window"]
    n = window.get(HISTOGRAM + ".count")
    if not n:
        return None
    return 1000.0 * window.get(HISTOGRAM + ".sum", 0.0) / n
