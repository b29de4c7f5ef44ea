"""The share of device busy time (self time of the ops of the one-session
slice) whose op carries a scope of the program: a plan operator
(`<kind>.<ordinal>`, exec/compile.compile_plan) or `harness` (the result
path's flag and pack programs). What is left is ops the compiler made
with no source op behind them."""

import span_reduce


def read(ctx):
    return span_reduce.metric(ctx, "op_attributed_share")
