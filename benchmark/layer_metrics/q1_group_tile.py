"""Lanes of the group tile the large-G kernel took in the builds of
set-up: counter `exec.pallas.kernel.group_tile` (summed over builds)
over `exec.pallas.kernel.builds.large`. In these cells only Q1 reaches
the kernel, so it is the tile Q1's twelve dense groups were given: 128
since the tile is sized by the plan's group count (the tile parameter,
512, before): the one-hot and the contraction are that many lanes wide.
Left out where the program has no such counter."""

SUM, BUILDS = "exec.pallas.kernel.group_tile", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
