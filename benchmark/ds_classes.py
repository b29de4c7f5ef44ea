"""What the readers of `tpcds_sf1.rollup`'s per-layer metrics share: the
cell's statement classes (traffic/rollup.json) and the means the
readers take over them (layer_metrics/ds_*.py). A class the slice did
not hold is left out of its mean; a program without a counter, or a
run without a trace, prints nothing for the metric."""

from __future__ import annotations

import statistics

# Q67 is the rollup of eight keys ranked over all nine sets, where the
# new mechanisms take most of the device; in the other three the star
# joins beneath the grouping do
STAR = ("q27", "q36", "q89")


def device_ms(ctx: dict, classes) -> float | None:
    """Mean of the classes' median device time in the one-session
    slice (`trace/per_class/<class>/device_ms`), over the classes the
    slice held; None where it held none (or there is no trace)."""
    per_class = (ctx.get("trace") or {}).get("per_class") or {}
    values = [(per_class.get(c) or {}).get("device_ms") for c in classes]
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def setup_count(ctx: dict, counter: str) -> float | None:
    """A counter's delta over set-up; None on a program without it."""
    d = ctx["counters"]["setup"]
    return float(d[counter]) if counter in d else None
