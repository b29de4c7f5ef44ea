"""Device milliseconds a TPC-DS Q80 of `tpcds_sf1_channels.reports`:
the class's median in the one-session trace slice
(`trace/per_class/q80/device_ms`); nothing where the slice held no
Q80."""

import ds_classes


def read(ctx):
    return ds_classes.device_ms(ctx, ("q80",))
