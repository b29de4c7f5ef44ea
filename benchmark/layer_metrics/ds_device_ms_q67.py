"""Device milliseconds a TPC-DS Q67 (the ROLLUP of eight keys, nine
grouping sets from one sort, and the rank over all of them): the
class's median in the one-session trace slice
(`trace/per_class/q67/device_ms`); nothing where the slice held no
Q67."""

import ds_classes


def read(ctx):
    return ds_classes.device_ms(ctx, ("q67",))
