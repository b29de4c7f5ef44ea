"""Aggregates that set-up compiled onto the while-loop hash table (a group
domain over the planner's dense bound: Q3.2-Q3.4, Q4.3): counter
`exec.agg.strategy.hash`, one tally a traced Aggregate. Left out where
the program has no such counter."""

import ssb_flights


def read(ctx):
    return ssb_flights.strategy_count(ctx, "hash")
