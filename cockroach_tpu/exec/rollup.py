"""GROUP BY ROLLUP / GROUPING SETS: the levels of a grouping-set
Aggregate (plan.Aggregate.grouping_sets).

The finest set, every key, is aggregated over the child's rows into
partial states by the Aggregate's own strategy; every other set comes
from the groups of the finest through each aggregate's combine step
(sums of sums, counts of counts, extremes of extremes, AVG as a sum and
a count divided at the end), so a coarser set reads groups and not the
child's rows. Two layouts:

  dense   the finest set's slots are its mixed-radix domain (key 0 the
          most significant, a key's NULL its last code): a set's group
          id is the mixed-radix code of the keys it keeps, one scatter
          a state, for any subset of the keys; the sets' slots are
          concatenated
  sorted  past the dense bound (plan.Aggregate.sort_dims): the rows
          sort once by the keys' packed code; a set that is a prefix of
          the code's key order is a run of equal prefixes in that
          order, so the finest groups are runs of the sorted rows and a
          coarser set's groups runs of the finest groups. After the
          sort nothing scatters or gathers: an exact sum or count is a
          running sum over the sorted rows; an order-preserving
          displacement network (ops/prefix.py compress) packs the
          finest groups' last rows, with their running sums, to the
          front, and one network a coarser set packs the finest groups
          its groups end at; a group's state is then its running sum
          less that of the group before it in its set. Min, max, any
          and a float sum keep a segmented reduction, whose run values
          the networks carry. All the sets' groups are laid into one
          batch of plan.Aggregate.set_slots slots, level after level.
          A plain GROUP BY past the dense bound whose keys pack takes
          this layout too, as its one set, over a batch large enough
          (exec/compile.py SORTED_GROUP_MIN_ROWS)

Every set's groups carry a rolled-up key as NULL and `__grouping<j>` 1
where key j is rolled up (sql/binder.py bind_grouping reads it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import agg as aggops
from ..ops import prefix, sortkey
from ..sql import plan as P
from ..sql.bound import BoundAgg
from ..sql.types import FLOAT8, INT8, Family

# what the traced grouping-set Aggregates did (static shapes, one tally
# a trace): the engine's exec.agg.grouping_sets ("sets") and
# exec.agg.rollup.network / .segmented
SETS = sortkey._Tally()

_DEAD = np.int64(np.iinfo(np.int64).max)


def state_aggs(aggs: list) -> tuple:
    """(the partial aggregates the finest set computes, for each of
    `aggs` the indexes of its states among them): AVG is a sum and a
    count, everything else its own state."""
    state, where = [], []
    for a in aggs:
        if a.func != "avg":
            where.append((len(state),))
            state.append(a)
            continue
        fam = a.arg.type.family
        if fam == Family.INT:
            s = BoundAgg("sum_int", a.arg, INT8)
        elif fam == Family.DECIMAL:
            s = BoundAgg("sum", a.arg, a.arg.type)
        else:
            s = BoundAgg("sum", a.arg, FLOAT8)
        s.arg_bits, s.arg_nonneg = a.arg_bits, a.arg_nonneg
        where.append((len(state), len(state) + 1))
        state += [s, BoundAgg("count", a.arg, INT8)]
    return state, where


def _kind(a: BoundAgg) -> str:
    """How a state combines: `count` (added, never NULL), `add`, `min`,
    `max`, `any`."""
    if a.func in ("count", "count_rows"):
        return "count"
    if a.func in ("sum", "sum_int"):
        return "add"
    return a.func


def finalize(aggs: list, where: list, states: list) -> list:
    """Each aggregate's (data, valid) from the states of its groups."""
    out = []
    for a, idx in zip(aggs, where):
        if a.func != "avg":
            out.append(states[idx[0]])
            continue
        (s, _), (c, _) = states[idx[0]], states[idx[1]]
        scale = (10.0 ** a.arg.type.scale
                 if a.arg.type.family == Family.DECIMAL else 1.0)
        d = (s.astype(jnp.float64) / scale
             / jnp.maximum(c, 1).astype(jnp.float64))
        out.append((d, c > 0))
    return out


# -- dense -------------------------------------------------------------------

def _combine_scatter(kind, d, v, gid, live, num_groups):
    m = jnp.logical_and(live, v)
    if kind == "count":
        s = aggops.group_sum(d, gid, live, num_groups, acc_dtype=d.dtype)
        return s, jnp.ones((num_groups,), jnp.bool_)
    if kind == "add":
        s = aggops.group_sum(d, gid, m, num_groups, acc_dtype=d.dtype)
    elif kind == "min":
        s = aggops.group_min(d, gid, m, num_groups)
    elif kind == "max":
        s = aggops.group_max(d, gid, m, num_groups)
    else:
        s = aggops.group_any(d, gid, m, num_groups)
    return s, aggops.group_count(gid, m, num_groups) > 0


def dense_sets(sets: list, dims: list, los: list, key_names: list,
               states: list, state_aggs_: list, live) -> tuple:
    """The sets of a dense grouping-set Aggregate from its finest
    set's slots: (group columns {name: (data, valid)} with the
    `__grouping<j>` bits, the states, live, slots, the slots the
    coarser sets were traced over)."""
    k = len(dims)
    full = tuple(range(k))
    g = 1
    for dim in dims:
        g *= dim + 1
    slot = jnp.arange(g, dtype=jnp.int32)
    codes, stride = [None] * k, 1
    for j in range(k - 1, -1, -1):
        codes[j] = (slot // stride) % (dims[j] + 1)
        stride *= dims[j] + 1
    kinds = [_kind(a) for a in state_aggs_]
    parts, rows = [], 0
    for s in sets:
        kept = set(s)
        if tuple(s) == full:
            size, sub_codes, sub_states, sub_live = g, codes, states, live
        else:
            size, gid = 1, jnp.zeros((g,), jnp.int32)
            for j in s:
                gid = gid * (dims[j] + 1) + codes[j]
                size *= dims[j] + 1
            sub_states = [_combine_scatter(kd, d, v, gid, live, size)
                          for kd, (d, v) in zip(kinds, states)]
            sub_live = aggops.group_count(gid, live, size) > 0
            if not s:       # the grand total has a row, rows or none
                sub_live = jnp.ones((1,), jnp.bool_)
            sub_slot = jnp.arange(size, dtype=jnp.int32)
            sub_codes, stride = [None] * k, 1
            for j in reversed(s):
                sub_codes[j] = (sub_slot // stride) % (dims[j] + 1)
                stride *= dims[j] + 1
            rows += g
        cols = {}
        for j, name in enumerate(key_names):
            if j in kept:
                c = sub_codes[j]
                cols[name] = (c if los[j] == 0
                              else c.astype(jnp.int64) + los[j],
                              c < dims[j])
            else:
                cols[name] = (jnp.zeros((size,), jnp.int32),
                              jnp.zeros((size,), jnp.bool_))
            cols[f"__grouping{j}"] = (
                jnp.full((size,), 0 if j in kept else 1, jnp.int64),
                jnp.ones((size,), jnp.bool_))
        parts.append((cols, sub_states, sub_live, size))
    return _concat(parts, key_names, len(states)) + (rows,)


def _concat(parts, key_names, n_states):
    names = list(parts[0][0])
    cols = {}
    for name in names:
        ds = [p[0][name][0] for p in parts]
        dt = jnp.result_type(*[d.dtype for d in ds])
        cols[name] = (jnp.concatenate([d.astype(dt) for d in ds]),
                      jnp.concatenate([p[0][name][1] for p in parts]))
    states = []
    for i in range(n_states):
        ds = [p[1][i][0] for p in parts]
        dt = jnp.result_type(*[d.dtype for d in ds])
        states.append((jnp.concatenate([d.astype(dt) for d in ds]),
                       jnp.concatenate([p[1][i][1] for p in parts])))
    live = jnp.concatenate([p[2] for p in parts])
    return cols, states, live, sum(p[3] for p in parts)


# -- sorted ------------------------------------------------------------------

def _segment_reduce(kind, x, seg, n):
    """Each run's whole value of a state, at every row of the run (a
    run's rows share `seg`, 0..n-1): one segment reduction, a scatter
    (XLA:TPU compiles a segmented associative scan of 2^20 rows in 85
    s, a scatter in under one)."""
    op = {"add": jax.ops.segment_sum, "count": jax.ops.segment_sum,
          "min": jax.ops.segment_min, "max": jax.ops.segment_max,
          "any": jax.ops.segment_max}[kind]
    return op(x, seg, num_segments=n)[seg]


def _ident(kind, dtype):
    if kind in ("add", "count"):
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if kind == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if kind == "min" else info.min, dtype)


def _exact(kind, dtype) -> bool:
    """A state whose group value is a difference of running sums: an
    integer add or a count (a float sum would round otherwise)."""
    return kind in ("add", "count") and not jnp.issubdtype(dtype,
                                                           jnp.floating)


def _running(kinds, states, start) -> list:
    """What the networks carry of each state over rows in order (`start`
    marks a run's first row), read at a run's last row: an exact
    state's running sum in int64; any other state's whole value over
    its run (one segmented reduction); and but for a count, the running
    count of valid rows."""
    n = start.shape[0]
    seg = None
    out = []
    for kind, (d, v) in zip(kinds, states):
        if _exact(kind, d.dtype):
            x = prefix.cumsum(jnp.where(v, d, 0).astype(jnp.int64))
        else:
            if seg is None:
                seg = jnp.maximum(
                    prefix.cumsum(start.astype(jnp.int32)) - 1, 0)
            x = _segment_reduce(kind, jnp.where(v, d, _ident(kind, d.dtype)),
                                seg, n)
        out.append((x,) if kind == "count"
                   else (x, prefix.cumsum(v.astype(jnp.int32))))
    return out


def _since_previous(x):
    """x less the element before it (0 before the first)."""
    return x - jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])


def _groups(kinds, dtypes, words) -> list:
    """Each state's (data, valid) of packed groups, from its running
    words at the groups' ends: an exact state's sum and a state's count
    of valid rows are its running words less the previous group's."""
    out = []
    for kind, dt, w in zip(kinds, dtypes, words):
        d = (_since_previous(w[0]).astype(dt) if _exact(kind, dt)
             else w[0])
        out.append((d, jnp.ones(d.shape, jnp.bool_) if kind == "count"
                    else _since_previous(w[1]) > 0))
    return out


def _flat(words) -> list:
    return [x for w in words for x in w]


def _nest(flat, like) -> list:
    it = iter(flat)
    return [tuple(next(it) for _ in w) for w in like]


def sorted_sets(sets: list, sort_dims: list, key_names: list, keys: list,
                states: list, state_aggs_: list, sel, slots: int,
                tally: bool = True) -> tuple:
    """The sets of an Aggregate past the dense bound, from the child's
    rows: (group columns with the `__grouping<j>` bits, the states,
    live, slots, the rows the coarser sets were traced over, overflow:
    more groups than `slots`). A plain GROUP BY is the one set of every
    key, and `tally` False leaves it out of SETS.

    After the one sort, no scatter and no gather: each state's running
    sum (or its runs' values) over the sorted rows, read at the finest
    groups' last rows, which one network packs to the front; a coarser
    set's groups end where the prefix of the code they keep changes,
    and one network a set packs those ends; a group's state is then its
    running sum less that of the group before it in the same set."""
    k = len(sort_dims)
    order = P.grouping_key_order(sets, k)
    bits = [int(dim).bit_length() for dim, _ in sort_dims]
    shift = [0] * k
    acc = 0
    for j in reversed(order):
        shift[j] = acc
        acc += bits[j]
    total_bits = acc
    n = sel.shape[0]
    # the phases a profile tells apart (the caller's scope is the
    # kernel): the code and its sort, the running words, the networks
    with jax.named_scope("keys"):
        code = jnp.zeros((n,), jnp.int64)
        for j in order:
            d, v = keys[j]
            dim, lo = sort_dims[j]
            c = jnp.where(v, d.astype(jnp.int64) - lo, dim)
            code = (code << bits[j]) | c
        code = jnp.where(sel, code, _DEAD)
        words = [code.astype(jnp.uint32)]
        if total_bits > 32:
            words.insert(0, (code >> 32).astype(jnp.uint32))
        # grouping needs equal codes together, not an order among them
        perm = sortkey.sort_perm_words(words, stable=False)
        cs = code[perm]
        live = cs != _DEAD
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int64), cs[:-1]])
        nxt = jnp.concatenate([cs[1:], jnp.full((1,), -1, jnp.int64)])
        start = jnp.logical_and(live, cs != prev)
        end = jnp.logical_and(live, cs != nxt)
    kinds = [_kind(a) for a in state_aggs_]
    dtypes = [d.dtype for d, _ in states]
    if tally:
        SETS.bump("network", len(sets))
        SETS.bump("segmented", sum(not _exact(kd, dt)
                                   for kd, dt in zip(kinds, dtypes)))
    with jax.named_scope("operands"):
        run = _running(kinds, [(d[perm], jnp.logical_and(v[perm], live))
                               for d, v in states], start)
    # the finest groups, packed to the front of c0 slots in code order;
    # past them every word reads 0, so an empty grand total counts 0
    c0 = min(n, slots)
    ng0 = jnp.sum(end.astype(jnp.int32))
    slot = jnp.arange(c0, dtype=jnp.int32)
    live0 = slot < ng0
    packed = prefix.compress(end, [cs] + _flat(run))
    code0 = jnp.where(live0, packed[0][:c0], _DEAD)
    run0 = _nest([jnp.where(live0, x[:c0], jnp.zeros((), x.dtype))
                  for x in packed[1:]], run)
    fine = _groups(kinds, dtypes, run0)
    overflow = ng0 > c0
    # every set a run of equal prefixes of the finest groups' codes
    prev0 = jnp.concatenate([jnp.full((1,), -1, jnp.int64), code0[:-1]])
    nxt0 = jnp.concatenate([code0[1:], jnp.full((1,), -1, jnp.int64)])
    last0 = jnp.logical_not(jnp.concatenate(
        [live0[1:], jnp.zeros((1,), jnp.bool_)]))
    levels, rows = [], 0
    for s in sets:
        if len(s) == k:
            levels.append((jnp.minimum(ng0, c0), code0, fine))
            continue
        cut = total_bits - sum(bits[j] for j in order[:len(s)])
        lc, lp, ln = (code0 >> cut, prev0 >> cut, nxt0 >> cut)
        lstart = jnp.logical_and(live0, lc != lp)
        lend = jnp.logical_and(live0, jnp.logical_or(last0, lc != ln))
        if not s:           # the grand total has a row, rows or none
            none = jnp.logical_and(ng0 == 0, slot == 0)
            lstart = jnp.logical_or(lstart, none)
            lend = jnp.logical_or(lend, none)
        # an exact state's running words at a set's ends are the
        # finest groups' at theirs; any other state reduces the finest
        # groups' values over the set's runs
        lrun = []
        for kd, dt, w, (fd, fv) in zip(kinds, dtypes, run0, fine):
            if not _exact(kd, dt):
                seg = jnp.maximum(
                    prefix.cumsum(lstart.astype(jnp.int32)) - 1, 0)
                w = (_segment_reduce(kd, jnp.where(fv, fd, _ident(kd, dt)),
                                     seg, c0),) + w[1:]
            lrun.append(w)
        out = prefix.compress(lend, [code0] + _flat(lrun))
        levels.append((jnp.sum(lend.astype(jnp.int32)), out[0],
                       _groups(kinds, dtypes, _nest(out[1:], lrun))))
        rows += c0
    # the sets' groups, level after level, in one batch: each level's
    # packed c0 slots laid at its offset, over the previous one's tail
    width = slots + c0
    lay = [jnp.zeros((width,), jnp.int64)] + [
        jnp.zeros((width,), a.dtype) for st in levels[0][2] for a in st]
    off, starts = jnp.zeros((), jnp.int32), []
    for count, gcode, st in levels:
        starts.append(off)
        lay = [jax.lax.dynamic_update_slice(b, a, (off,))
               for b, a in zip(lay, [gcode] + _flat(st))]
        off = off + count
    overflow = jnp.logical_or(overflow, off > slots)
    out_slot = jnp.arange(slots, dtype=jnp.int32)
    out_live = out_slot < off
    gcode = lay[0][:slots]
    # the level of each slot: the last whose offset it has reached (an
    # empty level shares its offset with the next)
    lvl = jnp.zeros((slots,), jnp.int32)
    for o in starts[1:]:
        lvl = lvl + (out_slot >= o).astype(jnp.int32)
    cols = {}
    for j, name in enumerate(key_names):
        dim, lo = sort_dims[j]
        kept = jnp.zeros((slots,), jnp.bool_)
        for li, s in enumerate(sets):
            if j in s:
                kept = jnp.logical_or(kept, lvl == li)
        c = (gcode >> shift[j]) & ((1 << bits[j]) - 1)
        ok = jnp.logical_and(out_live, kept)
        cols[name] = ((c.astype(jnp.int32) if lo == 0 and dim < 2 ** 31
                       else c + lo),
                      jnp.logical_and(ok, c != dim))
        cols[f"__grouping{j}"] = (1 - kept.astype(jnp.int64),
                                  jnp.ones((slots,), jnp.bool_))
    out_states = [(d[:slots], jnp.logical_and(v[:slots], out_live))
                  for d, v in _nest(lay[1:], levels[0][2])]
    return cols, out_states, out_live, slots, rows, overflow
