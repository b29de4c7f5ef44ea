"""How far one interpreter under the cell's sessions stretches the host
path: a statement's span time outside `pull`, median in the mix slice
over median in the one-session slice, mean over classes. A span cannot
see a wait for the interpreter lock; this is the nearest reading.

Mean over the statement classes of each class's median in the
one-session slice unless said otherwise (span_reduce.py)."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "host_stretch_x")
