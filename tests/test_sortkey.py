"""Normalized sort-key plane tests (ops/sortkey.py + consumers).

Four layers:

1. unit tests for the encoding itself — order-preserving unsigned
   images (int64 extremes, IEEE-754 monotone floats, dictionary
   ranks), lane packing with fields straddling lane boundaries, and
   dead-row demotion;
2. fuzzed parity: `sort_batch` under `sort_normalized=on` is
   permutation-identical (order, NULL placement, tie stability) to
   the lexsort path across int/float/bool/string-dict keys x asc/desc
   x NULLS FIRST/LAST x dead rows, INT64_MIN/MAX included; plus
   window `order_and_segments`, join `_dup_chain`, and
   `distinct_first_mask` parity;
3. legacy-path regressions: the DESC bitwise-NOT fix at INT64_MIN and
   the clipped top-k sentinels that can no longer collide with real
   values >= 2^62;
4. engine-level A/B: the HLO of a 3-key ORDER BY lowers only
   <=2-operand sorts under `auto` while `off` restores the 7-operand
   variadic lexsort; a primary-key-tie top-k workload that trips
   `__topk_inexact` under `off` stays exact (no host fallback) under
   `auto` because the packed word breaks the tie; results match
   between arms everywhere.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from cockroach_tpu.exec import compile as C
from cockroach_tpu.ops import sortkey as sk
from cockroach_tpu.ops import window as W
from cockroach_tpu.ops.agg import distinct_first_mask
from cockroach_tpu.ops.batch import ColumnBatch, read_ts_words
from cockroach_tpu.ops.join import _dup_chain

I64 = np.iinfo(np.int64)


# ---------------------------------------------------------------- encoding

def _img(d, **kw):
    bits, w = sk.encode_value(jnp.asarray(d), **kw)
    return np.asarray(bits), w


class TestEncodeValue:
    def test_int64_extremes_monotone(self):
        vals = np.array([I64.min, I64.min + 1, -1, 0, 1, I64.max - 1,
                         I64.max], np.int64)
        bits, w = _img(vals)
        assert w == 64
        assert (np.diff(bits.astype(object)) > 0).all()

    def test_int32_sign_bias_width(self):
        vals = np.array([-(1 << 31), -1, 0, (1 << 31) - 1], np.int32)
        bits, w = _img(vals)
        assert w == 32
        assert bits[0] == 0 and bits[-1] == (1 << 32) - 1
        assert (np.diff(bits.astype(object)) > 0).all()

    def test_float_monotone_bits_and_no_float64_image(self):
        # IEEE-754 monotone bits for a float32; a float64 has no image
        # (XLA:TPU cannot bitcast it), so its sorts take the lexsort
        vals = np.array([-np.inf, -3e38, -1.5, -1e-38, 0.0, 1e-38,
                         2.5, 3e38, np.inf], np.float32)
        bits, w = _img(vals)
        assert w == 32
        assert (np.diff(bits.astype(object)) > 0).all()
        assert sk.encode_value(jnp.asarray(vals.astype(np.float64))) \
            is None

    def test_float32_width(self):
        bits, w = _img(np.array([-2.0, 0.5], np.float32))
        assert w == 32 and bits[0] < bits[1]

    def test_bool_and_width_hint(self):
        bits, w = _img(np.array([False, True]))
        assert w == 1 and bits[0] == 0 and bits[1] == 1
        bits, w = _img(np.array([3, 7], np.int64), width=5)
        assert w == 5 and list(bits) == [3, 7]

    def test_dict_rank_lut(self):
        # dictionary ['e','a','c']: ranks e=2, a=0, c=1
        lut = np.array([2, 0, 1], np.int32)
        bits, w = _img(np.array([0, 1, 2], np.int32), lut=lut)
        assert w == 2 and list(bits) == [2, 0, 1]


class TestPackLanes:
    def test_field_straddles_lane_boundary(self):
        n = 3
        hi = jnp.asarray(np.array([1, 2, 3], np.uint64))
        lo = jnp.asarray(np.array([(1 << 63) | 5, 6, 7], np.uint64))
        lanes = sk.pack_lanes([(hi, 2), (lo, 64)], n)
        assert len(lanes) == 2
        l0, l1 = (np.asarray(x) for x in lanes)
        # lane0 = hi:2 then the top 62 bits of lo; lane1 = the low 2
        # bits of lo, left-justified
        v = (int(hi[0]) << 64) | int(lo[0])
        assert int(l0[0]) == v >> 2
        assert int(l1[0]) == (v & 3) << 62

    def test_single_small_field_left_justified(self):
        lanes = sk.pack_lanes([(jnp.asarray(np.array([1], np.uint64)),
                                3)], 1)
        assert len(lanes) == 1
        assert int(np.asarray(lanes[0])[0]) == 1 << 61

    def test_empty_fields_one_zero_lane(self):
        lanes = sk.pack_lanes([], 4)
        assert len(lanes) == 1 and not np.asarray(lanes[0]).any()

    def test_mask_dead_strictly_last_and_stable(self):
        n = 8
        rng = np.random.default_rng(3)
        d = jnp.asarray(rng.integers(-50, 50, n).astype(np.int64))
        sel = np.array([1, 0, 1, 0, 0, 1, 1, 1], bool)
        fields = sk.encode_keys([(d, jnp.ones(n, bool), False, False,
                                  None, None)])
        lanes = sk.mask_dead(sk.pack_lanes(fields, n),
                             jnp.asarray(sel))
        perm = np.asarray(sk.sort_perm(lanes))
        live = int(sel.sum())
        assert sel[perm[:live]].all()
        assert list(perm[live:]) == [1, 3, 4]  # dead: stable row order


# ---------------------------------------------------------------- fuzzed
# parity vs the lexsort path

def _fuzz_batch(rng, n, kinds):
    """Build (ColumnBatch, rank_tables) with one key column per kind
    plus an original-index payload column pinning tie stability."""
    cols, valid, ranks = {}, {}, {}
    for i, kind in enumerate(kinds):
        name = f"k{i}"
        if kind == "int64":
            d = rng.integers(-5, 5, n).astype(np.int64)
            # extremes + near-extremes ride along
            d[rng.integers(0, n, 4)] = [I64.min, I64.max, I64.min + 1,
                                        I64.max - 1]
        elif kind == "int32":
            d = rng.integers(-3, 3, n).astype(np.int32)
        elif kind in ("float32", "float64"):
            # a float32 has an image (encode_value); a float64 has
            # none and sorts on the lexsort path, "on" arm included
            d = np.round(rng.standard_normal(n), 2)  # ties, no -0.0
            d = np.abs(d) * np.where(d < 0, -1.0, 1.0)
            d = d.astype(np.dtype(kind))
        elif kind == "bool":
            d = rng.random(n) > 0.5
        elif kind == "dict":
            size = 5
            d = rng.integers(0, size, n).astype(np.int32)
            order = rng.permutation(size)
            rank = np.empty(size, np.int32)
            rank[order] = np.arange(size, dtype=np.int32)
            ranks[name] = rank
        else:
            raise AssertionError(kind)
        cols[name] = jnp.asarray(d)
        valid[name] = jnp.asarray(rng.random(n) > 0.25)
    cols["idx"] = jnp.asarray(np.arange(n, dtype=np.int64))
    b = ColumnBatch.from_dict(cols, valid,
                              sel=jnp.asarray(rng.random(n) > 0.2))
    return b, ranks


def _live_idx(bs: ColumnBatch):
    sel = np.asarray(bs.sel)
    return list(np.asarray(bs.col("idx"))[sel])


@pytest.mark.parametrize("desc", [False, True])
@pytest.mark.parametrize("nulls_first", [None, True, False])
def test_sort_batch_parity_single_key(desc, nulls_first):
    rng = np.random.default_rng(7 + desc + 10 * bool(nulls_first))
    for kind in ("int64", "int32", "float32", "float64", "bool",
                 "dict"):
        b, ranks = _fuzz_batch(rng, 257, [kind])
        key = ("k0", desc) if nulls_first is None \
            else ("k0", desc, nulls_first)
        on = C.sort_batch(b, [key], ranks, "on")
        off = C.sort_batch(b, [key], ranks, "off")
        assert _live_idx(on) == _live_idx(off), (kind, desc,
                                                 nulls_first)


def test_sort_batch_parity_multi_key_mixed():
    rng = np.random.default_rng(42)
    for trial in range(6):
        kinds = list(rng.choice(
            ["int64", "int32", "float32", "float64", "bool", "dict"],
            3))
        b, ranks = _fuzz_batch(rng, 193, kinds)
        keys = []
        for i in range(3):
            nf = [None, True, False][rng.integers(0, 3)]
            desc = bool(rng.integers(0, 2))
            keys.append((f"k{i}", desc) if nf is None
                        else (f"k{i}", desc, nf))
        on = C.sort_batch(b, keys, ranks, "on")
        off = C.sort_batch(b, keys, ranks, "off")
        assert _live_idx(on) == _live_idx(off), (trial, kinds, keys)


def test_sort_batch_tie_stability():
    # constant key: both paths must yield live rows in row order
    n = 64
    rng = np.random.default_rng(5)
    cols = {"k0": jnp.zeros(n, jnp.int64),
            "idx": jnp.asarray(np.arange(n, dtype=np.int64))}
    b = ColumnBatch.from_dict(cols,
                              sel=jnp.asarray(rng.random(n) > 0.3))
    on = C.sort_batch(b, [("k0", True)], {}, "on")
    off = C.sort_batch(b, [("k0", True)], {}, "off")
    want = list(np.flatnonzero(np.asarray(b.sel)))
    assert _live_idx(on) == _live_idx(off) == want


def test_window_order_parity():
    rng = np.random.default_rng(9)
    n = 200
    sel = jnp.asarray(rng.random(n) > 0.15)
    parts = [(jnp.asarray(rng.integers(0, 4, n).astype(np.int64)),
              jnp.asarray(rng.random(n) > 0.2))]
    orders = [(jnp.asarray(np.round(rng.standard_normal(n), 1)),
               jnp.asarray(rng.random(n) > 0.2), True),
              (jnp.asarray(rng.integers(-3, 3, n).astype(np.int64)),
               jnp.asarray(rng.random(n) > 0.2), False)]
    outs = {}
    for mode in ("on", "off"):
        order, seg, peer, in_part = W.order_and_segments(
            parts, orders, sel, mode)
        outs[mode] = tuple(np.asarray(x)
                           for x in (order, seg, peer, in_part))
    live = int(np.asarray(sel).sum())
    for a, b_ in zip(outs["on"], outs["off"]):
        # dead rows tie under normalization (stable row order) but
        # carry their keys through the lexsort — only the live prefix
        # is contractual (in_part excludes the rest)
        assert (a[:live] == b_[:live]).all()


def test_dup_chain_parity():
    rng = np.random.default_rng(13)
    n = 128
    keys = (jnp.asarray(rng.integers(0, 9, n).astype(np.int64)),
            jnp.asarray(rng.integers(-2, 2, n).astype(np.int32)))
    mask = jnp.asarray(rng.random(n) > 0.2)
    on = np.asarray(_dup_chain(keys, mask, n, "on"))
    off = np.asarray(_dup_chain(keys, mask, n, "off"))
    assert (on == off).all()


def test_distinct_first_mask_parity():
    rng = np.random.default_rng(17)
    n = 300
    for dtype in (np.int64, np.float32, np.float64):
        data = jnp.asarray(rng.integers(-4, 4, n).astype(dtype))
        mask = jnp.asarray(rng.random(n) > 0.3)
        gid = jnp.asarray(rng.integers(0, 6, n).astype(np.int32))
        on = np.asarray(distinct_first_mask(data, mask, gid, 6, "on"))
        off = np.asarray(distinct_first_mask(data, mask, gid, 6,
                                             "off"))
        assert (on == off).all(), dtype


# ---------------------------------------------------------------- legacy
# (sort_normalized=off) regressions: DESC negation / sentinel collisions

class TestLegacyExtremes:
    def _batch(self, vals, valid=None):
        n = len(vals)
        cols = {"k0": jnp.asarray(np.array(vals, np.int64)),
                "idx": jnp.asarray(np.arange(n, dtype=np.int64))}
        v = {"k0": jnp.asarray(valid)} if valid is not None else None
        return ColumnBatch.from_dict(cols, v)

    def test_desc_int64_min_sorts_last(self):
        # -INT64_MIN wraps to itself, so the old negation put the
        # MOST negative value FIRST under DESC; bitwise NOT doesn't
        b = self._batch([I64.min, -5, 0, 7, I64.max])
        out = C.sort_batch(b, [("k0", True)], {}, "off")
        assert list(np.asarray(out.col("idx"))) == [4, 3, 2, 1, 0]

    def test_desc_nulls_last_extremes(self):
        b = self._batch([I64.min, I64.max, 0, 0],
                        valid=[True, True, False, False])
        out = C.sort_batch(b, [("k0", True, False)], {}, "off")
        assert list(np.asarray(out.col("idx"))) == [1, 0, 2, 3]

    def test_window_sortable_desc_extremes(self):
        d = jnp.asarray(np.array([I64.min, 3, I64.max], np.int64))
        w = np.asarray(W._sortable(d, True))
        assert w[0] > w[1] > w[2]  # ascending image = DESC value order

    def test_rank_word_sentinels_exclusive(self):
        # live values at/beyond 2^62 used to collide with the NULL
        # (+-2^62) and dead (2^62 + 2^61) sentinels; now they clip to
        # 2^62 - 1 and every live word < null word < dead word
        vals = [I64.max, (1 << 62) + (1 << 61), 1 << 62, 0]
        b = ColumnBatch.from_dict(
            {"k0": jnp.asarray(np.array(vals, np.int64))},
            {"k0": jnp.asarray([True, True, True, False])},
            sel=jnp.asarray([True, True, True, True]))
        w = np.asarray(C._primary_rank_word(b, [("k0", False, False)],
                                            {}, "off"))
        assert (w[:3] < (1 << 62)).all()     # clipped live values
        assert w[3] == 1 << 62               # NULLS LAST sentinel
        dead = ColumnBatch.from_dict(
            {"k0": jnp.asarray(np.array(vals, np.int64))},
            sel=jnp.asarray([False, True, True, True]))
        wd = np.asarray(C._primary_rank_word(
            dead, [("k0", False, False)], {}, "off"))
        assert wd[0] == (1 << 62) + (1 << 61) and (wd[1:] < wd[0]).all()


# ---------------------------------------------------------------- top-k
# exactness: the packed word breaks primary-key ties

def _topk_tie_batch(n=256, dict2=None):
    """200 of n rows tie on the primary dict key; the secondary dict
    key is unique per row, so the packed word (one lane) resolves
    every comparator tie."""
    a = np.zeros(n, np.int32)
    a[200:] = 1
    b2 = np.arange(n, dtype=np.int32)
    rank_a = np.arange(2, dtype=np.int32)
    rank_b = np.arange(n, dtype=np.int32) if dict2 is None else dict2
    cols = {"a": jnp.asarray(a), "b": jnp.asarray(b2),
            "idx": jnp.asarray(np.arange(n, dtype=np.int64))}
    batch = ColumnBatch.from_dict(cols)
    return batch, {"a": rank_a, "b": rank_b}


class TestTopkExactness:
    KEYS = [("a", False), ("b", False)]

    def test_off_primary_ties_trip_inexact(self):
        b, ranks = _topk_tie_batch()
        out = C.topk_sort_limit_batch(b, self.KEYS, ranks, 4, 0, "off")
        assert np.asarray(out.col("__topk_inexact")).any()

    def test_auto_full_word_stays_exact(self):
        b, ranks = _topk_tie_batch()
        out = C.topk_sort_limit_batch(b, self.KEYS, ranks, 4, 0,
                                      "auto")
        assert not np.asarray(out.col("__topk_inexact")).any()
        sel = np.asarray(out.sel)
        got = list(np.asarray(out.col("idx"))[sel])
        full = C.sort_batch(b, self.KEYS, ranks, "auto")
        want = list(np.asarray(full.col("idx"))[:4])
        assert got == want


# ---------------------------------------------------------------- engine
# A/B: HLO operand arity, parity, no host fallback

def _sort_arities(text: str):
    """Operand counts of every stablehlo.sort in lowered MLIR."""
    tok = '"stablehlo.sort"('
    out, i = [], 0
    while True:
        j = text.find(tok, i)
        if j < 0:
            return out
        k = j + len(tok)
        end = text.index(")", k)
        ops = text[k:end].strip()
        out.append(ops.count(",") + 1 if ops else 0)
        i = end


@pytest.fixture(scope="module")
def seng():
    from cockroach_tpu.exec.engine import Engine
    e = Engine()
    e.execute("CREATE TABLE st (k INT, a INT, f REAL, s STRING, "
              "u STRING, g FLOAT)")
    rng = np.random.default_rng(23)
    vals = []
    for i in range(300):
        a = int(rng.integers(-4, 4))
        f = float(np.round(rng.standard_normal(), 2))
        s = "aa" if i < 200 else "bb"
        fv = "NULL" if rng.random() < 0.15 else f"{f}"
        gv = "NULL" if rng.random() < 0.15 else f"{f * 1e-3 + i * 1e-12}"
        vals.append(f"({i}, {a}, {fv}, '{s}', 'u{i:04d}', {gv})")
    e.execute(f"INSERT INTO st VALUES {', '.join(vals)}")
    return e


def _sess(eng, mode):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("sort_normalized", mode)
    return s


ORDER_SQL = ("SELECT k, a, f, s FROM st "
             "ORDER BY a DESC, f NULLS FIRST, s")


class TestEngineAB:
    def _lowered(self, eng, mode):
        s = _sess(eng, mode)
        p = eng.prepare(ORDER_SQL, session=s)
        tsv = read_ts_words(eng._read_ts(s).to_int())
        return p.jfn.lower(p.scans, tsv, np.int32(1),
                           np.int32(0)).as_text()

    def test_hlo_operand_arity(self, seng):
        auto = _sort_arities(self._lowered(seng, "auto"))
        off = _sort_arities(self._lowered(seng, "off"))
        assert auto and max(auto) <= 2, \
            f"auto arm lowered a variadic sort: arities {auto}"
        # 3 keys -> 2K+1 = 7-operand lexsort in the off arm
        assert max(off) >= 7, \
            f"off arm should restore the variadic lexsort: {off}"

    def test_float64_key_sorts_on_the_lexsort(self, seng):
        """A FLOAT (float64) key has no packed image (XLA:TPU cannot
        bitcast a float64): under auto its sort compiles the variadic
        lexsort and tallies the fallback, and the order is exact."""
        sql = "SELECT k, g FROM st ORDER BY g DESC, k"
        s = _sess(seng, "auto")
        p = seng.prepare(sql, session=s)
        tsv = read_ts_words(seng._read_ts(s).to_int())
        before = sk.FALLBACKS.value("sort")
        arities = _sort_arities(p.jfn.lower(
            p.scans, tsv, np.int32(1), np.int32(0)).as_text())
        assert sk.FALLBACKS.value("sort") > before
        assert arities and max(arities) > 2, arities
        rows = seng.execute("SELECT k, g FROM st",
                            session=_sess(seng, "off")).rows
        # DESC puts NULLs first (pg's default)
        want = sorted(rows, key=lambda r: (r[1] is not None,
                                           -(r[1] or 0.0), r[0]))
        assert seng.execute(sql, session=_sess(seng, "auto")).rows \
            == want

    def test_order_by_parity(self, seng):
        want = seng.execute(ORDER_SQL,
                            session=_sess(seng, "off")).rows
        got = seng.execute(ORDER_SQL,
                           session=_sess(seng, "auto")).rows
        assert got == want

    def test_window_and_distinct_parity(self, seng):
        for sql in (
            "SELECT k, row_number() OVER "
            "(PARTITION BY a ORDER BY f DESC, k) AS rn "
            "FROM st ORDER BY k",
            "SELECT a, count(DISTINCT s) AS c FROM st "
            "GROUP BY a ORDER BY a",
        ):
            want = seng.execute(sql, session=_sess(seng, "off")).rows
            got = seng.execute(sql, session=_sess(seng, "auto")).rows
            assert got == want, sql

    def test_topk_no_host_fallback_under_auto(self, seng):
        # 200 rows tie on s; u breaks every tie inside one packed
        # lane, so the candidate cut is provably exact on device
        sql = "SELECT k, s, u FROM st ORDER BY s, u LIMIT 5"
        out = seng.prepare(sql, session=_sess(seng, "auto")).dispatch()
        assert not np.asarray(out.col("__topk_inexact")).any(), \
            "packed-word top-k cut must not flag the host fallback"
        out_off = seng.prepare(sql,
                               session=_sess(seng, "off")).dispatch()
        assert np.asarray(out_off.col("__topk_inexact")).any(), \
            "the off arm's primary-only word should stay conservative"
        # and both arms agree end-to-end (off replans via TopKInexact)
        want = seng.execute(sql, session=_sess(seng, "off")).rows
        got = seng.execute(sql, session=_sess(seng, "auto")).rows
        assert got == want

    def test_metrics_and_tallies(self, seng):
        snap = seng.metrics.snapshot()
        for name in ("exec.sort.normalized",
                     "exec.sort.lexsort_fallback", "exec.sort.lanes"):
            assert name in snap
        assert snap["exec.sort.normalized"] > 0
        assert snap["exec.sort.lanes"] >= snap["exec.sort.normalized"]
        assert sk.NORMALIZED.value("sort") > 0
