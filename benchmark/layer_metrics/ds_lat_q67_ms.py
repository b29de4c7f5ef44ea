"""Client latency of TPC-DS Q67: the class's median in the window
(`client/class_median_ms/q67`)."""


def read(ctx):
    return ctx["client"]["class_median_ms"].get("q67")
