"""Device milliseconds a statement of TPC-DS Q27, Q36 and Q89, whose
time the star joins beneath the grouping take (a ROLLUP over a dense
domain, a window over a thousand groups): the mean over the three
classes of each one's median in the one-session trace slice, over the
classes the slice held."""

import ds_classes


def read(ctx):
    return ds_classes.device_ms(ctx, ds_classes.STAR)
