"""The share of the mix slice's device idle time in which a `plan` span
was the innermost one open in the server (`engine`: the statement's own
span and every span without a fixed name), over the classes; of the
sixteen rows `idle_by_span` prints, so a lower bound
(span_reduce.py, host_reduce.idle_share)."""

import host_reduce


def read(ctx):
    return host_reduce.idle_share(ctx, "plan")
