"""What the readers of `ssb_sf1.flight`'s per-layer metrics share: the
four flights of the Star Schema Benchmark as lists of the statement
classes of traffic/flight.json, the two means the per-flight readers
(layer_metrics/ssb_device_ms_f*.py, ssb_lat_f*_ms.py) take over them,
and the count of set-up's aggregates by strategy (ssb_kernel_aggs.py,
ssb_hash_aggs.py)."""

from __future__ import annotations

import statistics

FLIGHTS = {
    "f1": ("q1_1", "q1_2", "q1_3"),
    "f2": ("q2_1", "q2_2", "q2_3"),
    "f3": ("q3_1", "q3_2", "q3_3", "q3_4"),
    "f4": ("q4_1", "q4_2", "q4_3"),
}


def _mean(values: list):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def mean_device_ms(ctx: dict, flight: str):
    """Mean of the classes' median device time in the one-session
    slice, over the classes the slice held; None where it held none
    of the flight (or there is no trace)."""
    per_class = (ctx.get("trace") or {}).get("per_class") or {}
    return _mean([(per_class.get(c) or {}).get("device_ms")
                  for c in FLIGHTS[flight]])


def mean_client_ms(ctx: dict, flight: str):
    """Mean of the classes' median client latency in the window."""
    medians = ctx["client"]["class_median_ms"]
    return _mean([medians.get(c) for c in FLIGHTS[flight]])


STRATEGY = "exec.agg.strategy."


def strategy_count(ctx: dict, kind: str):
    """Aggregates that set-up compiled onto one strategy: counter
    `exec.agg.strategy.<kind>`, one tally a traced Aggregate. The
    strategies are tallied side by side, so a program that counts any
    of them counts this one, if only as 0; None on a program with no
    such counter."""
    d = ctx["counters"]["setup"]
    if not any(k.startswith(STRATEGY) for k in d):
        return None
    return float(d.get(STRATEGY + kind, 0))
