"""The comparison that decides `correct`.

A reference (statements/<name>.py) returns rows of exact values and
says, column by column, what each value is; a wire reply is text. The
guarantees a configuration states are held here:

  text, int   equal as written
  date        the reference's day number as ISO text
  dec<s>      an exact DECIMAL: the reference's integer at scale s. The
              server sends a DECIMAL as the shortest text of the float8
              nearest to it (float(int) / 10**s), so the wire value must
              be that very float: digit for digit as far as the wire
              carries digits
  avg<s>      AVG over DECIMAL, (integer sum at scale s, count): 1e-12
              relative (the engine's two aggregation arms round in
              different orders, PERF.md findings)
  ratio       (numerator, denominator) of exact integers: 1e-12 relative
"""

from __future__ import annotations

import math
from fractions import Fraction

from generators.tpch import iso

REL = 1e-12


def _same(kind: str, got: str | None, want) -> bool:
    if got is None:
        return False
    if kind == "text":
        return got == want
    if kind == "int":
        return int(got) == want
    if kind == "date":
        return got == iso(want)
    if kind.startswith("dec"):
        return float(got) == float(want) / 10 ** int(kind[3:])
    if kind.startswith("avg"):
        total, count = want
        exact = Fraction(total, count * 10 ** int(kind[3:]))
        return math.isclose(float(got), float(exact), rel_tol=REL)
    if kind == "ratio":
        num, den = want
        return math.isclose(float(got), float(Fraction(num, den)),
                            rel_tol=REL)
    raise ValueError(f"unknown column kind {kind!r}")


def compare(kinds: list, got_rows: list, want_rows: list) -> str | None:
    """None when the reply holds the reference's rows in order, else
    the first difference as text."""
    if len(got_rows) != len(want_rows):
        return f"{len(got_rows)} rows, reference has {len(want_rows)}"
    for i, (g, w) in enumerate(zip(got_rows, want_rows)):
        if len(g) != len(kinds) or len(w) != len(kinds):
            return f"row {i}: width {len(g)}, reference {len(w)}"
        for j, kind in enumerate(kinds):
            if not _same(kind, g[j], w[j]):
                return (f"row {i} col {j} ({kind}): got {g[j]!r}, "
                        f"reference {w[j]!r}")
    return None
