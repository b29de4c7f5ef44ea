"""Device milliseconds a TPC-DS Q5 of `tpcds_sf1_channels.reports`:
the class's median in the one-session trace slice
(`trace/per_class/q5/device_ms`); nothing where the slice held no
Q5."""

import ds_classes


def read(ctx):
    return ds_classes.device_ms(ctx, ("q5",))
