"""Device milliseconds a statement of the outer-join class (Q13: customer
LEFT JOIN orders, an aggregate of an aggregate): the mean over the
kind's classes of each class's median in the one-session trace slice
(`trace/per_class/<class>/device_ms`), over the classes the slice held."""

import nested_classes


def read(ctx):
    return nested_classes.mean_device_ms(ctx, "outer")
