"""Client latency of the outer-join class (Q13): the mean over the kind's
classes of each class's median in the window (`client/class_median_ms`)."""

import nested_classes


def read(ctx):
    return nested_classes.mean_client_ms(ctx, "outer")
