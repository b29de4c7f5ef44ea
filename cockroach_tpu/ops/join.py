"""Hash join on device: build + probe + gather.

The reference's vectorized hash join (pkg/sql/colexec/colexecjoin/
hashjoiner.go:170) builds a hash table over the build (right) side and
probes with the left, emitting matched pairs. On TPU the
shape-friendly formulation keeps the probe side's static length: each
probe row gathers its (unique) matching build row's columns, and the
join verdict lands in the selection mask:

  INNER: sel &= matched
  LEFT : sel unchanged; build columns NULL where unmatched
  SEMI : sel &= matched, no build columns
  ANTI : sel &= ~matched

This is exact when build keys are unique (PK/FK joins — TPC-H Q14's
lineitem⋈part, all SSB dimension joins). Duplicate-key build sides
need row expansion (dynamic output size); the planner currently
rejects those (exec/compile.py) — the colexecjoin full cross-chain
emission is future work and will use a two-pass count+prefix-sum
materialization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.num import next_pow2 as _next_pow2
from . import hashtable, sortkey
from .batch import ColumnBatch

# Fibonacci-multiplicative mix for the host-side spill partitioner
# (same constant family as ops/hashtable's device hash; the two need
# NOT agree — partitioning only requires equal keys -> equal bucket)
_SPILL_MULT = np.uint64(0x9E3779B97F4A7C15)


def radix_partition_ids(cols, valids, nparts: int) -> np.ndarray:
    """Host-side partition id per row for the spill-partitioned hash
    join (exec/spill.py).

    ``cols``/``valids`` are the stored int-family key columns of ONE
    side; both join sides partition with this same function over their
    own key columns, so any probe/build pair that hash_join could
    match (equal key values on every column) lands in the same
    partition — the invariant that makes per-partition hash_join
    results combine exactly. NULL keys hash as 0: they never match
    anything on device (validity masks), so any bucket is correct.
    ``nparts`` must be a power of two; ids use the high bits of the
    mixed word (the multiplicative mix concentrates entropy there)."""
    h = np.zeros(len(cols[0]), dtype=np.uint64)
    for d, v in zip(cols, valids):
        # int64 view keeps negative keys deterministic across the
        # int32/int64 stored widths the two sides may disagree on
        k = d.astype(np.int64, copy=False).view(np.uint64)
        k = np.where(v, k, np.uint64(0))
        h = (h ^ k) * _SPILL_MULT
    if nparts <= 1:
        return np.zeros(len(h), dtype=np.int64)
    shift = np.uint64(64 - (nparts - 1).bit_length())
    return (h >> shift).astype(np.int64)


def summarize_build_keys(keys: np.ndarray, key_cap: int):
    """Semi-join filter summary of one build side's visible key set
    (exec/joinfilter.py): ``(lo, hi, sorted_unique_keys | None,
    bloom | None)``. Small key sets stay exact (never a false
    positive); above ``key_cap`` a blocked bloom stands in — still
    never false-NEGATIVE, which is the property join-induced skipping
    rests on: a page/chunk is only dropped when NO build key can
    match it."""
    from ..storage.chunkstats import BlockedBloom
    keys = np.unique(keys.astype(np.int64, copy=False))
    lo, hi = int(keys[0]), int(keys[-1])
    if len(keys) <= key_cap:
        return lo, hi, keys, None
    bl = BlockedBloom(len(keys))
    bl.add(keys)
    return lo, hi, None, bl


def _offset(keys, lo: int, span: int):
    """A key component as its offset from `lo`, int32 where the span
    allows (what every compare of the bounded and sorted forms reads),
    and whether it lies inside [lo, lo + span)."""
    inside = jnp.logical_and(keys >= lo, keys - lo < span)
    off = keys.astype(jnp.int64) - lo
    if span < (1 << 31):
        off = jnp.clip(off, 0, span).astype(jnp.int32)
    return off, inside


def bounded_table(bkeys: tuple, bmask, n: int, direct) -> tuple:
    """The bounded form's build: a direct table over one component of
    the key (`comp`, [base, base + size)), `k` candidate build rows a
    slot, where the store's statistics say no value of that component
    has more than k live rows (Engine._maybe_direct_join). A slot's
    candidates stand in ascending row order: placed by their distance
    from the slot's first row where every one is within k of it, else
    in k rounds, each a scatter-min of the rows no earlier round placed
    (the two are the branches of one lax.cond, not a loop). Returns
    (candidate rows [size, k], n where none; for each other component,
    its offsets at those rows [size, k])."""
    _, comp, base, size, k, los, spans = direct
    slot = jnp.clip(bkeys[comp] - base, 0, size - 1).astype(jnp.int32)
    slot = jnp.where(bmask, slot, size)               # dropped
    rows = jnp.arange(n, dtype=jnp.int32)
    # a table stored in the component's order (a returns table by its
    # ticket, partsupp by its part) puts a slot's rows within k of its
    # first: each row's place is its distance from that first, two
    # scatters in all; any other order takes the k rounds
    first = jnp.full((size,), n, dtype=jnp.int32).at[slot].min(
        rows, mode="drop")
    rank = rows - first[jnp.minimum(slot, size - 1)]
    clustered = jnp.all(jnp.logical_or(jnp.logical_not(bmask),
                                       rank < k))

    def by_rank():
        return jnp.full((size, k), n, dtype=jnp.int32).at[
            slot, jnp.clip(rank, 0, k - 1)].set(rows, mode="drop")

    def by_rounds():
        placed = jnp.logical_not(bmask)
        cands = []
        for _ in range(k):
            t = jnp.full((size,), n, dtype=jnp.int32).at[
                jnp.where(placed, size, slot)].min(rows, mode="drop")
            cands.append(t)
            placed = jnp.logical_or(
                placed, t[jnp.minimum(slot, size - 1)] == rows)
        return jnp.stack(cands, axis=1)

    cand = jax.lax.cond(clustered, by_rank, by_rounds)
    at = jnp.minimum(cand, n - 1)
    others = tuple(_offset(bkeys[c], los[c], spans[c])[0][at]
                   for c in range(len(bkeys)) if c != comp)
    return cand, others


def bounded_probe(table, pkeys: tuple, pmask, n: int, direct):
    """(matched, build row) of each probe row under bounded_table: its
    slot's k candidates gathered as one row, each other component
    compared at all k, the first candidate equal on every one."""
    _, comp, base, size, k, los, spans = direct
    cand, others = table
    pk = pkeys[comp]
    ok = jnp.logical_and(pmask, jnp.logical_and(pk >= base,
                                                pk - base < size))
    pidx = jnp.clip(pk - base, 0, size - 1).astype(jnp.int32)
    rows = cand[pidx]                                 # [n_p, k]
    eq = rows < n
    j = 0
    for c in range(len(pkeys)):
        if c == comp:
            continue
        off, inside = _offset(pkeys[c], los[c], spans[c])
        ok = jnp.logical_and(ok, inside)
        eq = jnp.logical_and(eq, others[j][pidx] == off[:, None])
        j += 1
    first = jnp.argmax(eq, axis=1)
    matched = jnp.logical_and(ok, jnp.any(eq, axis=1))
    row = jnp.take_along_axis(rows, first[:, None], axis=1)[:, 0]
    return matched, jnp.minimum(row, n - 1)


def _packed(keys: tuple, los, spans):
    """The key's components packed into one int64 (mixed radix, the
    first most significant), and whether every one is in its range."""
    code = jnp.zeros(keys[0].shape, dtype=jnp.int64)
    ok = jnp.ones(keys[0].shape, dtype=jnp.bool_)
    for kc, lo, span in zip(keys, los, spans):
        inside = jnp.logical_and(kc >= lo, kc - lo < span)
        code = code * span + jnp.clip(kc.astype(jnp.int64) - lo, 0,
                                      span - 1)
        ok = jnp.logical_and(ok, inside)
    return code, ok


def sorted_table(bkeys: tuple, bmask, n: int, direct) -> tuple:
    """The sorted form's build: the live rows' packed keys in ascending
    order (a dead row's key past every live one) and the rows they came
    from, equal keys in ascending row order."""
    _, los, spans = direct
    code, _ = _packed(bkeys, los, spans)
    code = jnp.where(bmask, code, jnp.iinfo(jnp.int64).max)
    order = jnp.argsort(code, stable=True).astype(jnp.int32)
    return code[order], order


def sorted_probe(table, pkeys: tuple, pmask, n: int, direct):
    """(matched, build row) of each probe row under sorted_table: a
    lower-bound binary search of ceil(log2(n + 1)) unrolled steps, each
    one gather, then one compare."""
    _, los, spans = direct
    keys, order = table
    pk, ok = _packed(pkeys, los, spans)
    lo = jnp.zeros(pk.shape, dtype=jnp.int32)
    hi = jnp.full(pk.shape, n, dtype=jnp.int32)
    for _ in range(max(n, 1).bit_length()):
        mid = (lo + hi) // 2
        right = keys[jnp.minimum(mid, n - 1)] < pk
        right = jnp.logical_and(right, mid < hi)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    at = jnp.minimum(lo, n - 1)
    matched = jnp.logical_and(jnp.logical_and(pmask, ok),
                              jnp.logical_and(lo < n, keys[at] == pk))
    return matched, order[at]


def join_strategy(direct, join_type: str = "inner") -> str:
    """The form a join's `direct` setting gives it: `direct` (a
    direct-address table on one key), `packed` (on a composite key
    packed into one), `bounded` (bounded_table), `sorted`
    (sorted_table), `hash` (the while-loop table), or `cross` (a
    cartesian product, no key)."""
    if join_type == "cross":
        return "cross"
    if direct is None:
        return "hash"
    if isinstance(direct[0], str):
        return direct[0]
    return "direct"


def hash_join(probe: ColumnBatch, build: ColumnBatch,
              probe_keys: list[str], build_keys: list[str],
              build_payload: list[str], join_type: str = "inner",
              suffix: str = "", expand: int = 1,
              direct=None, pack_payload=(),
              sort_normalized: str = "off") -> ColumnBatch:
    """Join `probe` against `build` and return the probe batch extended
    with `build_payload` columns gathered from matches.

    expand=1: unique build keys, one gather per payload column.
    expand=K>1: duplicate-keyed build sides — the engine measured the
    max key multiplicity host-side at prepare time (a STATIC bound, so
    XLA keeps static shapes), the output has probe.n * K rows, and
    copy j of probe row p follows the build side's per-key duplicate
    chain j hops (the two-pass count+materialize of the reference's
    hashjoiner.go:870, reshaped for the compiler: chains come from one
    lexsort, emission is K strided gathers).

    The phases a profile treats apart carry a jax.named_scope each
    (`build`: the key->row table and what is folded into it over the
    build domain; `probe`: every probe-width gather; `expand`), under
    the operator scope exec/compile.py opens."""
    if join_type == "cross":
        return _cross_join(probe, build, build_payload, suffix)
    bkeys = tuple(build.col(k) for k in build_keys)
    pkeys = tuple(probe.col(k) for k in probe_keys)
    bmask = build.sel
    # Build rows with NULL keys never match (SQL join semantics).
    for k in build_keys:
        bmask = jnp.logical_and(bmask, build.col_valid(k))
    pmask = probe.sel
    for k in probe_keys:
        pmask = jnp.logical_and(pmask, probe.col_valid(k))

    if direct is not None and direct[0] in ("bounded", "sorted"):
        # a composite key past the packed table's span: a fixed number
        # of compares (bounded) or of binary-search steps (sorted), no
        # data-dependent loop; what it finds is what the hash table's
        # probe finds, the lowest live build row of the key, so the
        # payload and expansion paths below take it as they are
        with jax.named_scope("build"):
            if direct[0] == "bounded":
                table = bounded_table(bkeys, bmask, build.n, direct)
            else:
                table = sorted_table(bkeys, bmask, build.n, direct)
        with jax.named_scope("probe"):
            matched, build_row = (bounded_probe if direct[0] == "bounded"
                                  else sorted_probe)(table, pkeys, pmask,
                                                     build.n, direct)
    elif direct is not None and direct[0] == "packed":
        # Composite-key direct addressing (q9's partsupp (partkey,
        # suppkey)): mixed-radix-pack the components into ONE synthetic
        # key, then reuse the single-key direct machinery unchanged.
        # The engine proved every component's value range; the packed
        # span product fits the slot cap.
        _, los, spans = direct
        bp, _ = _packed(bkeys, los, spans)
        pp, ok_p = _packed(pkeys, los, spans)
        size = 1
        for span in spans:
            size *= int(span)
        size += 1
        # an out-of-range component would alias a neighbouring slot
        # after packing: steer the whole packed key out of range so
        # the standard in_range check rejects the row
        pp = jnp.where(ok_p, pp, jnp.int64(size))
        bkeys, pkeys = (bp,), (pp,)
        direct = (0, size)

    if direct is not None and direct[0] in ("bounded", "sorted"):
        pass
    elif direct is not None and len(bkeys) == 1:
        # Direct addressing: TPU scatters/gathers inside the hash
        # table's while_loops are ~100x slower than straight-line ops,
        # and dimension join keys are almost always dense ints (pks,
        # dict codes). One scatter builds key->row; one gather probes.
        base, size = direct
        with jax.named_scope("build"):
            bidx = jnp.clip(bkeys[0] - base, 0,
                            size - 1).astype(jnp.int32)
            # a masked build row is sent past the table and dropped,
            # not into one shared slot: TPC-DS Q27's build of 1.92 M
            # customer_demographics rows, 69 in 70 masked, 68-70 ms ->
            # 48 on the v5e in a process where the scatter runs fast
            # (90.5 either way in one where it runs slow; PR 40)
            bslot = jnp.where(bmask, bidx, size)
            # .min keeps the FIRST (lowest-rowid) duplicate — the
            # same chain head _dup_chain produces
            table = jnp.full((size,), build.n, dtype=jnp.int32) \
                .at[bslot].min(jnp.arange(build.n, dtype=jnp.int32),
                               mode="drop")
        pk0 = pkeys[0]
        in_range = jnp.logical_and(pk0 >= base, pk0 - base < size - 1)
        pidx = jnp.clip(pk0 - base, 0, size - 1).astype(jnp.int32)
        if expand <= 1 and join_type in ("inner", "left", "semi",
                                         "anti") \
                and size <= 4 * probe.n:
            # Payload folding (round-3 VERDICT #5): re-shape the
            # tables so every probe-side gather is addressed by pidx
            # DIRECTLY instead of the two-hop chain (gather owner,
            # then gather payload at owner). The fold costs one
            # build-side gather per payload over the (small) dimension
            # domain; the probe side loses its serial dependency and
            # one random int32 read per row — the Q14/SSB star-join
            # gather ceiling BENCHMARKS.md round 2 measured.
            # Gated on size <= 4x probe width: the fold gathers at
            # TABLE width, so a sparse packed-composite table (q9's
            # partsupp at 61M slots over a 1M probe) would pay
            # table-width gathers per payload (~450ms each measured)
            # where the two-hop probe path pays probe-width (~8ms).
            with jax.named_scope("build"):
                owner_slot = jnp.minimum(table, build.n - 1)
                vtab = table < build.n           # slot -> live build?
            # Three-state packing: when a payload column is an int32
            # dict code (>= 0), fold the match bit AND the null bit
            # into the value table — the whole join then costs ONE
            # probe-side gather (-2 = no build row, -1 = NULL payload,
            # >= 0 = the code). Probe gathers are the star-join cost
            # on TPU (~44 ms per 8M rows measured on v5e); every table
            # here is built with size-length ops on the small build
            # domain.
            packable = [n_ for n_ in build_payload
                        if n_ in pack_payload
                        and build.col(n_).dtype in (jnp.int32,
                                                    jnp.bool_)]
            base_ok = jnp.logical_and(pmask, in_range)
            matched = None
            out = probe
            if packable and join_type in ("inner", "left"):
                first = packable[0]
                for name in build_payload:
                    if name in packable:
                        col = build.col(name)
                        is_bool = col.dtype == jnp.bool_
                        with jax.named_scope("build"):
                            code = (col.astype(jnp.int32)
                                    if is_bool else col)[owner_slot]
                            pval = build.col_valid(name)[owner_slot]
                            packed = jnp.where(
                                vtab, jnp.where(pval, code,
                                                jnp.int32(-1)),
                                jnp.int32(-2))
                        # barrier: XLA otherwise rematerializes the
                        # gather once per consumer fusion (observed:
                        # 2x probe-length gathers in the Q14 HLO)
                        with jax.named_scope("probe"):
                            t = jax.lax.optimization_barrier(
                                packed[pidx])
                        if name == first:
                            matched = jnp.logical_and(base_ok,
                                                      t >= -1)
                        data = (t == 1) if is_bool \
                            else jnp.maximum(t, 0)
                        valid = jnp.logical_and(t >= 0, base_ok)
                        out = out.with_column(name + suffix, data,
                                              valid)
                    else:
                        with jax.named_scope("build"):
                            ptab = build.col(name)[owner_slot]
                            pvtab = jnp.logical_and(
                                build.col_valid(name)[owner_slot],
                                vtab)
                        with jax.named_scope("probe"):
                            out = out.with_column(
                                name + suffix, ptab[pidx],
                                jnp.logical_and(pvtab[pidx], base_ok))
                return out.and_sel(matched) if join_type == "inner" \
                    else out
            with jax.named_scope("probe"):
                matched = jnp.logical_and(base_ok, vtab[pidx])
            if join_type == "semi":
                return probe.and_sel(matched)
            if join_type == "anti":
                return probe.and_sel(jnp.logical_not(matched))
            for name in build_payload:
                with jax.named_scope("build"):
                    ptab = build.col(name)[owner_slot]       # [size]
                    pvtab = jnp.logical_and(
                        build.col_valid(name)[owner_slot], vtab)
                with jax.named_scope("probe"):
                    data = ptab[pidx]
                    valid = jnp.logical_and(pvtab[pidx], matched)
                out = out.with_column(name + suffix, data, valid)
            return out.and_sel(matched) if join_type == "inner" \
                else out
        with jax.named_scope("probe"):
            owner = table[pidx]
            build_row = jnp.minimum(owner, build.n - 1)
        # No key-equality re-check needed: direct addressing is
        # collision-free by construction — every live build key maps
        # to its own slot inside [0, size-2] (the engine sized the
        # table from the all-versions key range), dead rows are
        # dropped, and in_range keeps probes off the sentinel slot
        # size-1. Saves one n_probe-wide int64 gather; the fuzzed
        # parity tests vs the hash path pin this reasoning.
        matched = jnp.logical_and(jnp.logical_and(pmask, in_range),
                                  owner < build.n)
    else:
        cap = _next_pow2(max(2 * build.n, 16))
        with jax.named_scope("build"):
            claim, _, _ = hashtable.build(bkeys, bmask, cap)  # cap>=2N
        with jax.named_scope("probe"):
            matched, build_row = hashtable.probe(claim, bkeys, pkeys,
                                                 pmask, cap, build.n)
    # A probe row can land on a build row that was masked out (dead build
    # rows never insert, so claim only holds live rows — no extra check).

    if join_type == "semi":
        return probe.and_sel(matched)
    if join_type == "anti":
        return probe.and_sel(jnp.logical_not(matched))
    if join_type not in ("inner", "left"):
        raise ValueError(f"unsupported join type {join_type!r}")

    if expand <= 1:
        out = probe
        with jax.named_scope("probe"):
            for name in build_payload:
                data = build.col(name)[build_row]
                valid = jnp.logical_and(
                    build.col_valid(name)[build_row], matched)
                out = out.with_column(name + suffix, data, valid)
        return out.and_sel(matched) if join_type == "inner" else out

    with jax.named_scope("expand"):
        return _expand_join(probe, build, bkeys, bmask, matched,
                            build_row, build_payload, join_type, suffix,
                            expand, sort_normalized)


# rows of a cartesian product's batch at most
CROSS_MAX_ROWS = 1 << 24


def _cross_join(probe: ColumnBatch, build: ColumnBatch,
                build_payload: list, suffix: str) -> ColumnBatch:
    """Every probe row beside every build row (a comma join with no
    equality between its sides): probe row p, build row b at p * n_b +
    b, live where both are. The planner allows it only over a build
    side of few rows (planner._few_rows)."""
    n_p, n_b = probe.n, build.n
    if n_p * n_b > CROSS_MAX_ROWS:
        raise ValueError(f"a cartesian product of {n_p} x {n_b} rows "
                         f"is past {CROSS_MAX_ROWS}")
    with jax.named_scope("expand"):
        cols, valid = {}, {}
        for i, name in enumerate(probe.names):
            cols[name] = jnp.repeat(probe.data[i], n_b)
            valid[name] = jnp.repeat(probe.valid[i], n_b)
        for name in build_payload:
            cols[name + suffix] = jnp.tile(build.col(name), n_p)
            valid[name + suffix] = jnp.tile(build.col_valid(name), n_p)
        sel = jnp.logical_and(jnp.repeat(probe.sel, n_b),
                              jnp.tile(build.sel, n_p))
    return ColumnBatch.from_dict(cols, valid, sel=sel)


def _dup_chain(bkeys: tuple, bmask, n: int, mode: str = "off"):
    """next_dup[i] = the next live build row with row i's key (or n).
    One stable sort: equal live keys become adjacent runs in
    ascending row order, so chaining is a shifted compare. The chain
    start (min rowid per key) is exactly the row hashtable.build's
    claim resolves to. mode auto/on replaces the variadic lexsort
    with packed-lane argsorts (ops/sortkey.py); adjacency-run
    equality below still compares the RAW key values, so the chains
    are identical either way."""
    order = None
    if mode in ("auto", "on"):
        live = jnp.ones((n,), jnp.bool_)
        specs = [(k, live, False, False, None, None) for k in bkeys]
        fields = sortkey.encode_keys(specs)
        if fields is not None:
            lanes = sortkey.mask_dead(sortkey.pack_lanes(fields, n),
                                      bmask)
            order = sortkey.sort_perm(lanes, kind="join")
        else:
            sortkey.FALLBACKS.bump("join")
    if order is None:
        dead = jnp.logical_not(bmask).astype(jnp.int32)
        order = jnp.lexsort(tuple(reversed(bkeys)) + (dead,))
    same = jnp.ones((n - 1,), dtype=jnp.bool_) if n > 1 else \
        jnp.zeros((0,), dtype=jnp.bool_)
    for k in bkeys:
        s = k[order]
        same = jnp.logical_and(same, s[1:] == s[:-1])
    m_s = bmask[order]
    same = jnp.logical_and(same,
                           jnp.logical_and(m_s[1:], m_s[:-1]))
    nxt = jnp.where(same, order[1:], n)
    return jnp.full((n,), n, dtype=order.dtype).at[order[:-1]].set(nxt)


def _expand_join(probe, build, bkeys, bmask, matched, build_row,
                 build_payload, join_type, suffix, K: int,
                 sort_normalized: str = "off"):
    n_b = build.n
    next_dup = _dup_chain(bkeys, bmask, n_b, sort_normalized)
    # walk the chain K-1 hops: rows_j / has_j per output copy
    rows = [build_row]
    has = [matched]
    for _ in range(K - 1):
        nxt = next_dup[jnp.clip(rows[-1], 0, n_b - 1)]
        has.append(jnp.logical_and(has[-1], nxt < n_b))
        rows.append(jnp.minimum(nxt, n_b - 1))

    def interleave(cols):  # K arrays of [n] -> [n*K], copy-minor
        return jnp.stack(cols, axis=1).reshape(-1)

    has_i = interleave(has)
    cols, valid, names = {}, {}, []
    for i, name in enumerate(probe.names):
        d, v = probe.data[i], probe.valid[i]
        cols[name] = jnp.repeat(d, K)
        valid[name] = jnp.repeat(v, K)
    for name in build_payload:
        src, srcv = build.col(name), build.col_valid(name)
        cols[name + suffix] = interleave([src[r] for r in rows])
        valid[name + suffix] = jnp.logical_and(
            interleave([srcv[r] for r in rows]), has_i)
    sel = jnp.repeat(probe.sel, K)
    if join_type == "inner":
        sel = jnp.logical_and(sel, has_i)
    else:  # left: unmatched probe rows keep exactly copy 0
        copy0 = jnp.tile(
            jnp.arange(K) == 0, probe.n)
        keep = jnp.where(interleave([matched] * K),
                         has_i, copy0)
        sel = jnp.logical_and(sel, keep)
    return ColumnBatch.from_dict(cols, valid, sel=sel)
