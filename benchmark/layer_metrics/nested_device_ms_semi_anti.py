"""Device milliseconds a statement of the semi- and anti-join classes (Q4's
EXISTS, Q21's EXISTS and NOT EXISTS with an inequality, Q22's NOT
EXISTS): the mean over the kind's classes of each class's median in the
one-session trace slice (`trace/per_class/<class>/device_ms`), over the
classes the slice held."""

import nested_classes


def read(ctx):
    return nested_classes.mean_device_ms(ctx, "semi_anti")
