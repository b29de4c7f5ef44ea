"""Device milliseconds a statement of SSB flight 2 (Q2.1-Q2.3: three joins,
grouped by year and brand, 8,008 dense groups): the mean over the
flight's classes of each class's median in the one-session trace slice
(`trace/per_class/<class>/device_ms`). Over the classes the slice held:
a round longer than the slice leaves some out."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_device_ms(ctx, "f2")
