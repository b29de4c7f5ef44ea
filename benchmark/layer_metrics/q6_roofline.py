"""Q6's share of its roofline: the bytes its four columns hold at their
stored widths over the chip's peak HBM bandwidth, over Q6's device time
per statement in the one-session slice of the trace. Q6 is bound by
bytes (one multiply and four compares a row)."""

import peaks


def read(ctx):
    trace = ctx["trace"]
    if not trace or "q6" not in trace["per_class"]:
        return None
    seconds = trace["per_class"]["q6"]["device_ms"] / 1e3
    if seconds <= 0:
        return None
    columns, _ = ctx["data"]["lineitem"]
    share = peaks.roofline_share(peaks.q6_bytes(columns), 0.0, seconds,
                                 ctx["device"]["kind"])
    return 100.0 * share["share"]
