"""Window function kernels: sort-once, scan-based, XLA-native.

The reference's vectorized window operators (pkg/sql/colexec/
colexecwindow: rank/row_number/lag/lead/aggregate windowers, each a
generated per-type operator over a sorted partition iterator) become
one formulation on TPU: lexsort rows by (partition keys, order keys),
compute every window value in the SORTED domain with cumulative
scans/segment ops — all O(n log n) sort + O(n) scans the XLA compiler
fuses — then scatter results back to the original row order. No
per-partition loop exists anywhere: a million tiny partitions cost the
same as one big one.

Default frames match PostgreSQL: aggregates without ORDER BY see the
whole partition; with ORDER BY they see RANGE UNBOUNDED PRECEDING ..
CURRENT ROW *including peers* (ties share a value), which is also what
last_value returns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import prefix, sortkey


def order_and_segments(part_keys: list, order_keys: list, sel,
                       mode: str = "off", peers_ordered: bool = True):
    """Sort the rows and describe partitions/peer groups.

    part_keys: list of (data, valid); order_keys: list of
    (data, valid, desc). Unselected rows sort to the end and form
    their own "partition" (excluded by callers via in_part).

    mode (sort_normalized): auto/on pack (partition keys, order keys)
    into 32-bit words and run one sort over them (ops/sortkey.py)
    instead of the 2K+1-operand lexsort whose XLA compile cost grows
    per operand. peers_ordered=False: the caller reads nothing of the
    order among a peer group (rank, dense_rank, an aggregate), so the
    row index is no key of that sort (a key less to compile).

    Returns (order, seg_start, peer_start, in_part) — all in the
    sorted domain except `order` which indexes original rows:
      order[i]     original index of sorted row i
      seg_start[i] sorted index of row i's partition start
      peer_start[i] sorted index of row i's ORDER BY peer-group start
      in_part[i]   sorted row i belongs to a real (selected) partition
    """
    n = sel.shape[0]
    order = None
    if mode in ("auto", "on"):
        specs = []
        for d, v in part_keys:
            # partitions group NULLs together, after live values
            # (the lexsort's logical_not(v) key)
            specs.append((d, v, False, False, None, None))
        for d, v, desc in order_keys:
            # NULLS LAST for asc, FIRST for desc (pg default)
            specs.append((d, v, desc, desc, None, None))
        fields = sortkey.encode_keys(specs)
        if fields is not None:
            lanes = sortkey.mask_dead(sortkey.pack_lanes(fields, n),
                                      sel)
            # one unstable sort with the row index as its last key: the
            # stable order, at a third of a stable argsort's compile
            order = sortkey.sort_perm_words(
                sortkey.words_of(lanes, fields), kind="window",
                stable=peers_ordered)
        else:
            sortkey.FALLBACKS.bump("window")
    if order is None:
        unsel = jnp.logical_not(sel).astype(jnp.int32)
        # jnp.lexsort: LAST key is primary. Build minor->major.
        keys = []
        for d, v, desc in reversed(order_keys):
            kd = _sortable(d, desc)
            keys.append(kd)
            # NULLS LAST for asc, FIRST for desc (pg default)
            keys.append(v.astype(jnp.int32) if desc
                        else jnp.logical_not(v).astype(jnp.int32))
        for d, v in reversed(part_keys):
            # partitions group NULLs together: validity is part of
            # the key
            keys.append(_sortable(d, False))
            keys.append(jnp.logical_not(v).astype(jnp.int32))
        keys.append(unsel)  # primary: selected rows first
        order = jnp.lexsort(tuple(keys))

    def sorted_eq(pairs):
        """Row i equals row i-1 on every (data, valid) pair."""
        eq = jnp.ones((n,), dtype=jnp.bool_)
        for d, v in pairs:
            ds, vs = d[order], v[order]
            same = jnp.logical_and(
                ds == jnp.roll(ds, 1),
                vs == jnp.roll(vs, 1))
            # two NULLs are the same partition/peer
            both_null = jnp.logical_and(jnp.logical_not(vs),
                                        jnp.logical_not(jnp.roll(vs, 1)))
            eq = jnp.logical_and(eq, jnp.logical_or(same, both_null))
        return eq

    sel_s = sel[order]
    same_part = sorted_eq([(d, v) for d, v in part_keys])
    same_part = jnp.logical_and(same_part, sel_s == jnp.roll(sel_s, 1))
    pb = jnp.logical_not(same_part).at[0].set(True)  # partition boundary
    same_peer = jnp.logical_and(
        same_part, sorted_eq([(d, v) for d, v, _ in order_keys]))
    ob = jnp.logical_not(same_peer).at[0].set(True)  # peer boundary

    idx = jnp.arange(n)
    seg_start = prefix.cummax(jnp.where(pb, idx, 0))
    peer_start = prefix.cummax(jnp.where(ob, idx, 0))
    return order, seg_start, peer_start, sel_s


def _sortable(d, desc: bool):
    if d.dtype.kind == "f":
        d = d.astype(jnp.float64)
        return -d if desc else d
    if not desc:
        return d
    # bitwise NOT reverses int order with no wraparound (negation
    # maps INT64_MIN to itself)
    return ~d.astype(jnp.int64)


def _peer_end(peer_start, n):
    """Sorted index of the LAST row of each row's peer group."""
    idx = jnp.arange(n)
    is_last = jnp.concatenate([peer_start[1:] != peer_start[:-1],
                               jnp.ones((1,), jnp.bool_)])
    marked = jnp.where(is_last, idx, n - 1)
    return prefix.cummin(marked[::-1])[::-1]


def scatter_back(order, vals, valid, n):
    out_d = jnp.zeros((n,), vals.dtype).at[order].set(vals)
    out_v = jnp.zeros((n,), jnp.bool_).at[order].set(valid)
    return out_d, out_v


def row_number(order, seg_start, sel_s):
    n = order.shape[0]
    rn = jnp.arange(n) - seg_start + 1
    return scatter_back(order, rn.astype(jnp.int64), sel_s, n)


def rank(order, seg_start, peer_start, sel_s):
    n = order.shape[0]
    r = peer_start - seg_start + 1
    return scatter_back(order, r.astype(jnp.int64), sel_s, n)


def dense_rank(order, seg_start, peer_start, sel_s):
    n = order.shape[0]
    idx = jnp.arange(n)
    ob = (peer_start == idx)
    c = prefix.cumsum(ob.astype(jnp.int64))
    dr = c - c[seg_start] + 1
    return scatter_back(order, dr, sel_s, n)


def lag_lead(order, seg_start, sel_s, data, valid, offset: int):
    """offset > 0 = lag, < 0 = lead; NULL outside the partition."""
    n = order.shape[0]
    idx = jnp.arange(n)
    src = idx - offset
    ds, vs = data[order], valid[order]
    seg_end = _seg_end(seg_start, n)
    ok = jnp.logical_and(src >= seg_start, src <= seg_end)
    src = jnp.clip(src, 0, n - 1)
    out = jnp.where(ok, ds[src], ds)
    outv = jnp.logical_and(ok, vs[src])
    return scatter_back(order, out, jnp.logical_and(outv, sel_s), n)


def ntile(order, seg_start, sel_s, buckets: int):
    """pg semantics: rows split sequentially into `buckets` groups as
    evenly as possible — the first (size % buckets) groups get one
    extra row; when size < buckets, row r lands in bucket r."""
    n = order.shape[0]
    idx = jnp.arange(n)
    rn = idx - seg_start  # 0-based row number within the partition
    size = _seg_end(seg_start, n) - seg_start + 1
    q = size // buckets          # small-bucket size
    rem = size % buckets         # groups with q+1 rows
    big_span = rem * (q + 1)     # rows covered by the big groups
    in_big = rn < big_span
    b_big = rn // (q + 1) + 1  # q >= 0, so the divisor is >= 1
    b_small = rem + (rn - big_span) // jnp.maximum(q, 1) + 1
    b = jnp.where(in_big, b_big, b_small)
    return scatter_back(order, b.astype(jnp.int64), sel_s, n)


def _seg_end(seg_start, n):
    idx = jnp.arange(n)
    is_last = jnp.concatenate([seg_start[1:] != seg_start[:-1],
                               jnp.ones((1,), jnp.bool_)])
    marked = jnp.where(is_last, idx, n - 1)
    return prefix.cummin(marked[::-1])[::-1]


def first_value(order, seg_start, sel_s, data, valid):
    n = order.shape[0]
    ds, vs = data[order], valid[order]
    return scatter_back(order, ds[seg_start],
                        jnp.logical_and(vs[seg_start], sel_s), n)


def last_value(order, seg_start, peer_start, sel_s, data, valid,
               framed: bool):
    """framed=True (ORDER BY present): value at the end of the peer
    group (pg's default-frame last_value); else partition end."""
    n = order.shape[0]
    ds, vs = data[order], valid[order]
    end = _peer_end(peer_start, n) if framed else _seg_end(seg_start, n)
    return scatter_back(order, ds[end],
                        jnp.logical_and(vs[end], sel_s), n)


def window_agg(func: str, order, seg_start, peer_start, sel_s,
               data, valid, framed: bool):
    """sum/count/min/max/avg over the window.

    framed=False: whole-partition value broadcast to every row.
    framed=True: running value up to the current row's peer-group end.
    """
    n = order.shape[0]
    if data is None:  # count(*)
        ds = jnp.ones((n,), jnp.int64)
        m = sel_s
    else:
        ds, vs = data[order], valid[order]
        m = jnp.logical_and(vs, sel_s)
    idx = jnp.arange(n)
    seg_end = _seg_end(seg_start, n)
    end = _peer_end(peer_start, n) if framed else seg_end

    def run_to(cum, base_at):
        # inclusive cumulative value at `end`, minus everything before
        # the partition start
        return cum[end] - jnp.where(seg_start > 0,
                                    cum[jnp.maximum(seg_start - 1, 0)], 0)

    if func in ("sum", "sum_int", "avg", "count", "count_rows"):
        if func in ("count", "count_rows"):
            x = m.astype(jnp.int64)
        else:
            x = jnp.where(m, ds, 0).astype(
                jnp.float64 if ds.dtype.kind == "f" else jnp.int64)
        cum = prefix.cumsum(x)
        total = run_to(cum, None)
        cnt = prefix.cumsum(m.astype(jnp.int64))
        cntw = cnt[end] - jnp.where(seg_start > 0,
                                    cnt[jnp.maximum(seg_start - 1, 0)], 0)
        if func == "avg":
            out = total.astype(jnp.float64) / jnp.maximum(cntw, 1)
            v = cntw > 0
        elif func in ("count", "count_rows"):
            out, v = cntw, jnp.ones((n,), jnp.bool_)
        else:
            out, v = total, cntw > 0
        return scatter_back(order, out, jnp.logical_and(v, sel_s), n)
    if func in ("min", "max"):
        if ds.dtype.kind == "f":
            ident = jnp.asarray(jnp.inf if func == "min" else -jnp.inf,
                                ds.dtype)
        else:
            info = jnp.iinfo(jnp.int64)
            ident = jnp.asarray(info.max if func == "min" else info.min,
                                ds.dtype)
        x = jnp.where(m, ds, ident)
        seg_id = prefix.cumsum((seg_start == idx).astype(jnp.int64))
        # per-partition running min/max (segment-reset associative scan)
        run = _segmented(x, seg_id, func)
        out = run[end]  # end = peer end (framed) or partition end
        cnt = prefix.cumsum(m.astype(jnp.int64))
        cntw = cnt[end] - jnp.where(seg_start > 0,
                                    cnt[jnp.maximum(seg_start - 1, 0)], 0)
        return scatter_back(order, out,
                            jnp.logical_and(cntw > 0, sel_s), n)
    raise ValueError(f"window aggregate {func} unsupported")


def _segmented(x, seg_id, func: str):
    """Segment-reset running min/max: associative scan over
    (segment id, value) pairs that forgets the accumulator whenever the
    segment changes."""
    pick = jnp.minimum if func == "min" else jnp.maximum

    def combine(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, pick(va, vb), vb)

    _, out = jax.lax.associative_scan(combine, (seg_id, x))
    return out
