"""Device milliseconds a statement of SSB flight 1 (Q1.1-Q1.3: one join to
the date dimension and an ungrouped sum): the mean over the flight's
classes of each class's median in the one-session trace slice
(`trace/per_class/<class>/device_ms`). Over the classes the slice held:
a round longer than the slice leaves some out."""

import ssb_flights


def read(ctx):
    return ssb_flights.mean_device_ms(ctx, "f1")
