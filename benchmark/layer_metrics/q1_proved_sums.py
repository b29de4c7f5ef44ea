"""Exact sums and avgs of the large-G kernel's builds of set-up whose
argument carried a value-range proof: counter
`exec.pallas.kernel.proved_sums` (summed over builds) over
`exec.pallas.kernel.builds.large`. In these cells it is Q1's: 7 when
the plan proves all four sums and three avgs (the words, limb rows and
overflow sentinel of each are then sized by the argument's bits), 0
when the mechanism did not engage. Left out where the program has no
such counter."""

SUM, BUILDS = "exec.pallas.kernel.proved_sums", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
