"""Client latency of SSB flight 3 (Q3.1-Q3.4: three joins, grouped by
customer and supplier nation (5,408 dense groups) or city (504,008, over
the dense bound) and year): the mean over the flight's classes of each
class's median in the window (`client/class_median_ms`)."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_client_ms(ctx, "f3")
