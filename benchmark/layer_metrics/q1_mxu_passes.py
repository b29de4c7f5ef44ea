"""bf16 MXU passes of the large-G kernel's contraction of its exact
rows (limbs, counts, liveness) in the builds of set-up: counter
`exec.pallas.kernel.mxu_passes` (summed over builds) over
`exec.pallas.kernel.builds.large`. In these cells it is Q1's: 1 since
every such row is exact in bf16 (limbs of at most 8 bits); an f32
contraction at `Precision.HIGHEST` is 6.
Left out where the program has no such counter."""

SUM, BUILDS = "exec.pallas.kernel.mxu_passes", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
