"""Device milliseconds a statement of the unnested correlated scalar
subquery (Q17: a 200,000-group sub-aggregate joined back): the mean over
the kind's classes of each class's median in the one-session trace slice
(`trace/per_class/<class>/device_ms`), over the classes the slice held."""

import nested_classes


def read(ctx):
    return nested_classes.mean_device_ms(ctx, "unnested")
