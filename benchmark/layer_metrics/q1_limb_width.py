"""Limb width of the exact int64 sums in the large-G kernel's builds of
set-up: counter `exec.pallas.kernel.limb_bits` (summed over builds) over
`exec.pallas.kernel.builds.large`. In this cell only Q1 reaches the
kernel, so it is the width Q1's group-rows bound gave: 6 at 2^26 rows
with the exact bound, 5 without it (8 at SF1's 2^23). Narrower limbs
are more matmul rows for the same sums.
Left out where the program has no such counter."""

SUM, BUILDS = "exec.pallas.kernel.limb_bits", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
