"""What the readers of `tpch_sf1_full.nested`'s per-layer metrics share:
the cell's statement classes (traffic/nested.json) by the mechanism each
forces, the two means the per-kind readers take over them
(layer_metrics/nested_device_ms_*.py, nested_lat_*_ms.py), and set-up's
tallies of what the plans chose (nested_semi_anti_joins.py,
nested_outer_joins.py). A class the slice did not hold is left out of
its mean; a program without a counter, or a run without a trace, prints
nothing for the metric."""

from __future__ import annotations

import statistics

# a class stands where its distinctive mechanism is: Q4 is a semi-join
# (EXISTS), Q21 an EXISTS and a NOT EXISTS with an inequality besides
# the key, Q22 an anti-join (NOT EXISTS) under a scalar subquery; Q13
# is the left outer join under two aggregates; Q17 the correlated
# scalar subquery, unnested into a grouped join
KINDS = {
    "semi_anti": ("q4", "q21", "q22"),
    "outer": ("q13",),
    "unnested": ("q17",),
}


def _mean(values: list):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def mean_device_ms(ctx: dict, kind: str):
    """Mean of the classes' median device time in the one-session
    slice, over the classes of the kind that the slice held; None
    where it held none (or there is no trace)."""
    per_class = (ctx.get("trace") or {}).get("per_class") or {}
    return _mean([(per_class.get(c) or {}).get("device_ms")
                  for c in KINDS[kind]])


def mean_client_ms(ctx: dict, kind: str):
    """Mean of the classes' median client latency in the window, over
    the classes of the kind that the cell runs."""
    medians = ctx["client"]["class_median_ms"]
    return _mean([medians.get(c) for c in KINDS[kind]])


def setup_count(ctx: dict, counters: list, family: str):
    """Sum of set-up's deltas of `counters`. The counters of one family
    (a name prefix) are registered side by side, so a program that
    counts any of the family counts these, if only as 0; None on a
    program with none of the family."""
    d = ctx["counters"]["setup"]
    if not any(k.startswith(family) for k in d):
        return None
    return float(sum(d.get(c, 0) for c in counters))
