"""Value-range proofs for bound integer expressions.

Interval arithmetic over a bound expression tree, in Python integers
(unbounded, so the proof itself cannot wrap): what an exact SUM / AVG
needs to know about its argument before it picks how many 32-bit
words and i32 limbs the argument travels as (exec/compile.py
large_layout, ops/agg.py _group_sum_i64_limbs) and whether its int64
sum can wrap at all. The leaves are stored columns, whose
all-versions [lo, hi] the store keeps per generation
(ColumnStore.key_int_range, from the chunks' zone maps), and
constants; the nodes are `+`, `-`, `*` and unary minus (the decimal
rescale is a `*` by the constant 10^k, sql/binder.py). Everything
else proves nothing, and the aggregate behaves as it did without a
proof.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from .bound import BBin, BCol, BConst, BExpr, BUnary
from .types import Family

Interval = tuple[int, int]

_INT_FAMILIES = (Family.INT, Family.DECIMAL)


def _int_bits(e: BExpr) -> int:
    """Width of the signed integer the compiled expression holds e in
    (32 or 64), or 0 outside INT / DECIMAL."""
    ty = getattr(e, "type", None)
    if ty is None or ty.family not in _INT_FAMILIES:
        return 0
    return 8 * ty.np_dtype.itemsize


def _fits(iv: Interval, bits: int) -> bool:
    return -(1 << (bits - 1)) <= iv[0] and iv[1] < 1 << (bits - 1)


def expr_int_range(e: BExpr, col_range: Callable[[str], Optional[Interval]]
                   ) -> Optional[Interval]:
    """[lo, hi] of integer expression e over every row whose inputs
    are valid, or None where nothing is proven. col_range(name) gives a
    batch column's stored [lo, hi], or None. A node is proven only if
    its interval fits the integer the compiled expression computes it
    in (exec/expr.py: the wider of the operands', then the node's
    own), so no intermediate wraps and the traced value IS the
    mathematical one."""
    bits = _int_bits(e)
    if not bits:
        return None
    if isinstance(e, BConst):
        if not isinstance(e.value, (int, np.integer)) \
                or isinstance(e.value, (bool, np.bool_)):
            return None
        iv = (int(e.value), int(e.value))
    elif isinstance(e, BCol):
        iv = col_range(e.name)
    elif isinstance(e, BUnary) and e.op == "-":
        x = expr_int_range(e.operand, col_range)
        iv = None if x is None else (-x[1], -x[0])
    elif isinstance(e, BBin) and e.op in ("+", "-", "*"):
        a = expr_int_range(e.left, col_range)
        b = expr_int_range(e.right, col_range)
        if a is None or b is None:
            return None
        if e.op == "+":
            iv = (a[0] + b[0], a[1] + b[1])
        elif e.op == "-":
            iv = (a[0] - b[1], a[1] - b[0])
        else:
            corners = [x * y for x in a for y in b]
            iv = (min(corners), max(corners))
        # the arithmetic runs in the wider of the operands' integers
        bits = min(bits, max(_int_bits(e.left), _int_bits(e.right)))
    else:
        return None
    if iv is None or not _fits(iv, bits):
        return None
    return iv


def nonneg_bits(iv: Optional[Interval]) -> int:
    """Bit length that holds every value of a proven non-negative
    int64 interval (at least 1), or 0: nothing proven, or a value may
    be negative, whose two's-complement high bits are all set."""
    if iv is None or iv[0] < 0 or iv[1] >= 1 << 63:
        return 0
    return max(1, int(iv[1]).bit_length())
