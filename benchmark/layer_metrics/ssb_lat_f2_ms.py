"""Client latency of SSB flight 2 (Q2.1-Q2.3: three joins, grouped by year
and brand, 8,008 dense groups): the mean over the flight's classes of
each class's median in the window (`client/class_median_ms`)."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_client_ms(ctx, "f2")
