"""Client latency of SSB flight 4 (Q4.1-Q4.3: four joins, profit grouped by
208, 5,408 and 2,010,008 (over the dense bound) groups): the mean over
the flight's classes of each class's median in the window
(`client/class_median_ms`)."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_client_ms(ctx, "f4")
