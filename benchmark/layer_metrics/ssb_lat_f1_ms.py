"""Client latency of SSB flight 1 (Q1.1-Q1.3: one join to the date
dimension and an ungrouped sum): the mean over the flight's classes of
each class's median in the window (`client/class_median_ms`)."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_client_ms(ctx, "f1")
