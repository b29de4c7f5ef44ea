"""[1, n] 32-bit arrays the large-G kernel is handed in the builds of
set-up: counter `exec.pallas.kernel.operand_words` (summed over builds)
over `exec.pallas.kernel.builds.large`. In these cells it is Q1's: the
group ids, one packed mask word, and one word for each argument the
plan proved under 2^31 or two for one it did not: 8 with the value-range
proofs (four one-word arguments and `charge` in two), 12 on a program
that proves none of the five (by `exec.pallas.kernel.operand_bytes`).
Left out where the program has no such counter."""

SUM, BUILDS = "exec.pallas.kernel.operand_words", "exec.pallas.kernel.builds.large"


def read(ctx):
    d = ctx["counters"]["setup"]
    if SUM not in d or not d.get(BUILDS):
        return None
    return d[SUM] / d[BUILDS]
