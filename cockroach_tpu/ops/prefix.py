"""Prefix scans that XLA:TPU compiles in a second.

A one-dimensional `jnp.cumsum` or `lax.cummax` of 2^20 elements takes
XLA:TPU 15-47 s to compile (int32 / int64 sums, 23 s a running max;
`lax.associative_scan` 85 s), where the same scan over a
[n / 1024, 1024] view takes 0.5-1.4 s (compiled here for a described
v5e, PR 40). So a long array scans as rows of 1,024: each row on its
own, then the rows' totals, carried into every row. The result is the
one-dimensional scan's, element for element (integer sums wrap alike;
a float sum adds in another order). An array whose length is no
multiple of the row is padded with zeros to one and cut back after: a
1-D int64 cumsum of 313,600 elements (TPC-DS Q89's batch at SF1) took
146.7 s to compile as it was and 3.3 s as rows (for a described v5e).

`compress` packs the rows a mask keeps to the front, in their order,
with no scatter and no gather: every kept row moves left by the count
of dropped rows before it (one running sum), the moves taken a bit of
that displacement at a time (the `compress` network of Hacker's
Delight 7-4, `ops/pallas/compact.py` in blocks). Rows p < q kept sit,
after the moves of the bits below b, at p - (D_p mod 2^b) and
q - (D_q mod 2^b), where q - p > D_q - D_p >= (D_q mod 2^b) -
(D_p mod 2^b): never on one position, never out of order. So a step
is one shifted select an array, of any dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROW = 1024


def _rows(x):
    """x as rows of ROW, its tail padded with zeros; None where it is
    one row or less."""
    n = x.shape[0]
    if n <= ROW:
        return None
    if n % ROW:
        x = jnp.concatenate([x, jnp.zeros((-n % ROW,), x.dtype)])
    return x.reshape(-1, ROW)


def cumsum(x):
    """Inclusive running sum of a one-dimensional array."""
    y = _rows(x)
    if y is None:
        return jnp.cumsum(x)
    inner = jnp.cumsum(y, axis=1)
    last = inner[:, -1]
    out = inner + (jnp.cumsum(last) - last)[:, None]
    return out.reshape(-1)[:x.shape[0]]


def _cumextreme(x, pick, scan):
    y = _rows(x)
    if y is None:
        return scan(x)
    inner = scan(y, axis=1)
    carry = scan(inner[:, -1])
    # what the rows before each row reached (the first row: nothing)
    before = jnp.concatenate([inner[:1, :1].reshape(1), carry[:-1]])
    fixed = pick(inner, before[:, None])
    return jnp.concatenate([inner[:1], fixed[1:]]).reshape(-1)[:x.shape[0]]


def cummax(x):
    """Inclusive running maximum of a one-dimensional array."""
    return _cumextreme(x, jnp.maximum, jax.lax.cummax)


def cummin(x):
    """Inclusive running minimum of a one-dimensional array."""
    return _cumextreme(x, jnp.minimum, jax.lax.cummin)


def _shift_left(x, s: int):
    """y[i] = x[i + s], zeros past the end."""
    return jnp.concatenate([x[s:], jnp.zeros((s,), x.dtype)])


def compress(keep, arrays) -> list:
    """Each of `arrays` (one-dimensional, keep's length) with the rows
    `keep` marks at its front, in their order: row i of a result
    is the i-th kept row while i is under keep's count, anything after
    it. ceil(log2 n) steps of shifted selects."""
    n = keep.shape[0]
    # the displacement of a kept row; a dropped row's 0 never moves it
    d = jnp.where(keep, cumsum(jnp.logical_not(keep).astype(jnp.int32)), 0)
    out = list(arrays)
    for b in range(max(n - 1, 0).bit_length()):
        s = 1 << b
        coming = _shift_left(d, s)
        take = (coming & s) != 0
        d = jnp.where(take, coming, jnp.where((d & s) != 0, 0, d))
        out = [jnp.where(take, _shift_left(x, s), x) for x in out]
    return out
