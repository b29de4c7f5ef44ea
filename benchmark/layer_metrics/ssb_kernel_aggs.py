"""Aggregates that set-up compiled onto the large-G Pallas kernel (a dense
group domain of at most 2^19 slots: Q2.1-Q2.3, Q3.1, Q4.1, Q4.2):
counter `exec.agg.strategy.kernel`, one tally a traced Aggregate. Left
out where the program has no such counter."""

import ssb_flights


def read(ctx):
    return ssb_flights.strategy_count(ctx, "kernel")
