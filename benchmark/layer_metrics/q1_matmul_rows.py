"""Rows of the large-G kernel's matmul operand (limb, count and shadow
rows, built in VMEM) in the builds of set-up: counter
`exec.pallas.kernel.matmul_rows` (summed over builds) over
`exec.pallas.kernel.builds.large`. In this cell it is Q1's: 66 at limb
width 6 (51 at SF1's width 8), past the 64 the matmul padded to there.
Left out where the program has no such counter."""

SUM, BUILDS = "exec.pallas.kernel.matmul_rows", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
