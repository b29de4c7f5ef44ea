"""Device milliseconds a TPC-DS Q77 of `tpcds_sf1_channels.reports`:
the class's median in the one-session trace slice
(`trace/per_class/q77/device_ms`); nothing where the slice held no
Q77."""

import ds_classes


def read(ctx):
    return ds_classes.device_ms(ctx, ("q77",))
