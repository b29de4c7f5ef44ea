"""What TPC-H SF10 on one chip asks of the program, at sizes the CPU
runs in seconds (PERF.md, PR 28): the placement model on both sides
of the HBM budget for a kernel-path and a scatter-path plan, bulk
ingest against the value-by-value path it replaced, and the large-G
kernel at the limb widths 2^26 rows give it (5 and 6), where a 64-bit
argument is 13 or 11 limbs and the matmul operand passes 64 rows."""

import copy
import dataclasses

import numpy as np
import pytest

from cockroach_tpu.ops.pallas import groupagg_large as pg
from cockroach_tpu.storage import chunkstats
from cockroach_tpu.storage.columnstore import ColumnStore, Dictionary

SF, N_ROWS = 0.01, 20000
BUCKET = 32768          # the row bucket 20,000 rows pad to


@pytest.fixture(scope="module")
def teng():
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    e = Engine()
    tpch.load(e, SF, rows=N_ROWS, tables=("lineitem",))
    return e


def _without_proofs(aggs):
    """The aggregates as a plan with no value-range proof has them."""
    return [dataclasses.replace(a, arg_bits=0, arg_nonneg=False)
            for a in aggs]


def _session(eng, pallas="auto"):
    s = eng.session()
    s.vars.set("distsql", "off")
    s.vars.set("pallas_groupagg", pallas)
    return s


@pytest.fixture
def budget(teng):
    """Set sql.exec.hbm_budget_bytes for one test, then put it back."""
    name = "sql.exec.hbm_budget_bytes"
    before = teng.settings.get(name)
    yield lambda n: teng.settings.set(name, str(int(n)))
    teng.settings.set(name, str(before))


# -- (a) the placement model -------------------------------------------------

# Q1 reads seven columns: three int32 (4 + 1 validity byte a row) and
# four int64 proven to fit int32, beside the two MVCC int64s
Q1_UPLOAD = (16 + 7 * 5) * BUCKET
# the kernel path: the group ids, one packed mask word, four arguments
# the plan proves under 2^31 as one word each and `charge` (37 bits) as
# two, and the accumulator tiles (twelve words with no proof: PR 32)
Q1_KERNEL_WORDS = 4 * 8 * BUCKET
# the scatter path: 16 bytes a row an aggregate, eight aggregates
Q1_SCATTER = 16 * 8 * BUCKET


class TestPlacementModel:
    def test_the_model_is_the_program_that_will_run(self, teng):
        from cockroach_tpu.models import tpch
        from cockroach_tpu.utils import tracing
        for pallas, lo, hi in (
                ("auto", Q1_UPLOAD + Q1_KERNEL_WORDS,
                 Q1_UPLOAD + Q1_KERNEL_WORDS + (1 << 18)),
                ("off", Q1_UPLOAD + Q1_SCATTER, Q1_UPLOAD + Q1_SCATTER)):
            tracing.start_collector()
            teng.execute(tpch.Q1, session=_session(teng, pallas))
            plan, = [p for r in tracing.stop_collector()
                     for p in r.find_all("plan")]
            assert plan.tags["placement"] == "resident"
            assert lo <= plan.tags["model_bytes"] <= hi, (pallas,
                                                          plan.tags)
        snap = teng.metrics.snapshot()
        assert snap["sql.exec.placement.model_bytes.max"] \
            == Q1_UPLOAD + Q1_SCATTER

    @pytest.mark.parametrize("pallas,budget_bytes,verdict", [
        # between the two models: the kernel path fits where the
        # scatter path's temporaries would not
        ("auto", Q1_UPLOAD + Q1_KERNEL_WORDS + (1 << 19), "resident"),
        ("off", Q1_UPLOAD + Q1_KERNEL_WORDS + (1 << 19), "stream-scan"),
        # under both: a table that does not fit still streams
        ("auto", Q1_UPLOAD + Q1_KERNEL_WORDS - 1, "stream-scan"),
        # over both
        ("off", Q1_UPLOAD + Q1_SCATTER, "resident"),
    ])
    def test_both_sides_of_the_budget(self, teng, budget, pallas,
                                      budget_bytes, verdict):
        from cockroach_tpu.models import tpch
        s = _session(teng, pallas)
        want = teng.execute(tpch.Q1, session=s).rows
        budget(budget_bytes)
        assert teng.stream_verdict(tpch.Q1, s) == verdict
        before = teng.metrics.snapshot()
        got = teng.execute(tpch.Q1, session=s).rows
        after = teng.metrics.snapshot()
        name = "sql.exec.placement." + verdict.split("-")[0]
        assert after[name] == before[name] + 1
        # the same answer from either side
        assert len(got) == len(want) >= 3
        for g, w in zip(got, want):
            assert g[:6] == w[:6] and g[9] == w[9]
            assert g[6:9] == pytest.approx(w[6:9], rel=1e-12)

    def test_q1_at_sf10_is_resident_on_a_v5e(self, teng):
        """The model at the real size, from the plan alone: Q1 over a
        2^26-row bucket on the kernel path is 3.19 GiB of upload and
        2.0 GiB of operand words (3.0 before the plan proved four of
        its five arguments into one word), inside the 12 GiB budget;
        the scatter term read 8 GiB for the same plan."""
        from cockroach_tpu.exec import compile as C
        from cockroach_tpu.exec.stmtutil import _root_aggregate
        from cockroach_tpu.models import tpch
        s = _session(teng)
        node, _ = teng._plan(teng._parse_cached(tpch.Q1), s)
        agg = _root_aggregate(node)
        n = 1 << 26
        params = C.ExecParams(pallas_groupagg="auto",
                              pallas_interpret=False)
        assert C.large_kernel_eligible(agg, n, params)
        kernel = C.large_kernel_bytes(agg, n)
        assert 4 * 8 * n <= kernel <= 4 * 8 * n + (1 << 20)
        assert (16 + 7 * 5) * n + kernel < 12 << 30
        # the accumulator term is the tile the kernel takes for Q1's
        # twelve groups, 128 lanes an accumulator row, not the tile's
        # upper bound of 512
        lay = C.large_layout(agg.aggs, n, agg.max_group_rows)
        assert C.dense_num_groups(agg) == 12
        assert pg.effective_group_tile(12) == 128 < pg.GROUP_TILE
        assert kernel == 4 * n * lay.n_words \
            + 4 * 128 * (max(1, len(lay.f_rows)) + len(lay.i_rows))
        # at the next bucket (12.5 SF) the twelve words of a plan with
        # no proof pass the 12 GiB budget beside the upload, as they
        # always did; the eight of the proven plan stay under it
        n2 = 1 << 27
        bare = copy.copy(agg)
        bare.aggs = _without_proofs(agg.aggs)
        assert (16 + 7 * 5) * n2 + C.large_kernel_bytes(bare, n2) \
            > (16 + 7 * 5) * n2 + 4 * 12 * n2 > 12 << 30 \
            > (16 + 7 * 5) * n2 + C.large_kernel_bytes(agg, n2)
        # the interpreter's grid budget keeps a CPU run of that size
        # on the scatter path, and the model says so
        assert not C.large_kernel_eligible(
            agg, n, C.ExecParams(pallas_groupagg="auto",
                                 pallas_interpret=True))


# -- (b) bulk ingest ---------------------------------------------------------

def _old_bloom_words(keys):
    """BlockedBloom as it was built before: a read-modify-write a key."""
    h = chunkstats.mix64(keys)
    words = np.zeros(len(chunkstats.BlockedBloom(len(keys)).words),
                     dtype=np.uint64)
    one = np.uint64(1)
    m = np.zeros(len(h), dtype=np.uint64)
    for shift in (32, 38, 44, 50):
        m |= one << ((h >> np.uint64(shift)) & np.uint64(63))
    np.bitwise_or.at(words, (h & np.uint64(len(words) - 1))
                     .astype(np.int64), m)
    return words


def _old_sketch_regs(keys):
    """DistinctSketch as it was built before: log2 and maximum.at."""
    h = chunkstats.mix64(keys)
    idx = (h >> np.uint64(56)).astype(np.int64)
    low = (h & np.uint64((1 << 56) - 1)).astype(np.int64)
    nbits = np.zeros(len(low), dtype=np.int64)
    nz = low > 0
    nbits[nz] = np.floor(np.log2(low[nz].astype(np.float64))) + 1
    regs = np.zeros(256, dtype=np.uint8)
    np.maximum.at(regs, idx, (57 - nbits).astype(np.uint8))
    return regs


def _bulk_table(eng, name, n, seed, chunk_rows):
    rng = np.random.default_rng(seed)
    eng.execute(f"CREATE TABLE {name} (k INT8 NOT NULL, flag STRING, "
                "note STRING, price DECIMAL(15,2), day DATE, x FLOAT8)")
    td = eng.store.table(name)
    td.chunk_rows = chunk_rows
    flags = ["R", "A", "N"]
    notes = [f"note {i:05d}" for i in rng.permutation(n // 2)]
    cols = {
        "k": rng.integers(-1 << 40, 1 << 40, n),
        "flag": rng.integers(0, 3, n).astype(np.int32),
        "note": rng.integers(0, len(notes), n).astype(np.int32),
        "price": rng.integers(0, 10 ** 7, n),
        "day": rng.integers(8000, 10500, n).astype(np.int32),
        "x": rng.random(n),
    }
    valid = {"price": rng.random(n) < 0.9, "note": rng.random(n) < 0.95}
    return td, cols, valid, {"flag": flags, "note": notes}


class TestBulkIngest:
    def test_a_seeded_dictionary_is_the_value_by_value_one(self):
        values = [f"v{i}" for i in np.random.default_rng(1).permutation(500)]
        old, new = Dictionary(), Dictionary()
        for v in values:
            old.encode(v)
        new.seed(values)
        assert new.values == old.values and len(new) == 500
        assert new._codes is None       # no value was hashed yet
        assert new.codes == old.codes
        assert new.encode("v7") == old.encode("v7")
        assert new.encode("fresh") == old.encode("fresh") == 500
        # a dictionary that has values goes value by value
        more = Dictionary()
        more.encode("b")
        more.seed(["a", "b", "c"])
        assert more.values == ["b", "a", "c"]
        # repeated values would leave codes that decode but never match
        twice = Dictionary()
        twice.seed(["a", "b", "a"])
        with pytest.raises(ValueError, match="repeated value"):
            twice.codes

    @pytest.mark.parametrize("n,chunk_rows", [(10000, 4096), (3000, 4096),
                                              (8192, 4096)])
    def test_chunks_rowids_and_statistics(self, n, chunk_rows):
        from cockroach_tpu.exec.engine import Engine
        eng = Engine()
        td, cols, valid, dicts = _bulk_table(eng, "b", n, 7, chunk_rows)
        for col, values in dicts.items():
            eng.store.set_dictionary("b", col, values)
        rid0 = td.next_rowid
        assert eng.store.insert_columns("b", cols, eng.clock.now(),
                                        valid=valid) == n
        assert [c.n for c in td.chunks] == \
            [min(chunk_rows, n - lo) for lo in range(0, n, chunk_rows)]
        assert eng.store.ingest_rows == n and eng.store.ingest_seconds > 0
        lo = 0
        for chunk in td.chunks:
            rows = slice(lo, lo + chunk.n)
            assert (chunk.rowid == np.arange(rid0 + lo,
                                             rid0 + lo + chunk.n)).all()
            assert chunk.stats_ready()
            for cn, arr in cols.items():
                assert (chunk.data[cn] == arr[rows]).all()
                v = valid.get(cn, np.ones(n, bool))[rows]
                assert (chunk.valid[cn] == v).all()
                # the statistics of the old path on the same rows
                assert chunk.zone(cn) == chunkstats.column_zone(
                    arr[rows], v)
                if arr.dtype.kind == "f":
                    assert chunk.key_bloom(cn) is None
                    continue
                keys = arr[rows][v]
                assert (chunk.key_bloom(cn).words
                        == _old_bloom_words(keys)).all(), cn
                assert (chunk.distinct_sketch(cn).regs
                        == _old_sketch_regs(keys)).all(), cn
                assert chunk.key_bloom(cn).might_contain(keys).all()
            assert chunk.mvcc_window() == (int(chunk.mvcc_ts[0]),
                                           int(chunk.mvcc_del[0]))
            lo += chunk.n
        # a code past the dictionary is refused, as before
        bad = dict(cols, flag=np.full(n, 3, np.int32))
        with pytest.raises(ValueError, match="out of dictionary range"):
            eng.store.insert_columns("b", bad, eng.clock.now())

    def test_analyze_counts_what_the_sort_counted(self):
        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.sql import stats
        eng = Engine()
        n = 10000
        td, cols, valid, dicts = _bulk_table(eng, "a", n, 11, 4096)
        for col, values in dicts.items():
            eng.store.set_dictionary("a", col, values)
        eng.store.insert_columns("a", cols, eng.clock.now(), valid=valid)
        eng.execute("DELETE FROM a WHERE day < 8100")
        eng.execute("ANALYZE a")
        st = td.stats
        live = cols["day"] >= 8100
        assert st.row_count == int(live.sum()) and st.source == "analyze"
        for cn, arr in cols.items():
            v = valid.get(cn, np.ones(n, bool)) & live
            assert st.distinct[cn] == len(np.unique(arr[v])), cn
            assert st.null_frac[cn] == pytest.approx(
                int((live & ~v).sum()) / int(live.sum()))
        # a domain too wide to flag goes through the sort
        assert cols["k"].max() - cols["k"].min() > stats.ANALYZE_FLAG_DOMAIN

    @pytest.mark.parametrize("cols", [("flag",), ("flag", "day"),
                                      ("note",), ("k",), ("x",)])
    def test_group_bound_without_the_sort(self, cols):
        """key_max_multiplicity over small integer domains counts the
        keys (np.bincount) where it sorted 60M rows; wide and float
        keys still sort. The same number either way."""
        from cockroach_tpu.exec.engine import Engine
        eng = Engine()
        td, data, valid, dicts = _bulk_table(eng, "m", 9000, 5, 4096)
        for col, values in dicts.items():
            eng.store.set_dictionary("m", col, values)
        eng.store.insert_columns("m", data, eng.clock.now(), valid=valid)
        eng.execute("DELETE FROM m WHERE day < 8050")
        ts = eng.clock.now().to_int()
        for nulls in (False, True):
            dense = ColumnStore._dense_key_multiplicity(td, cols, ts, nulls)
            assert (dense is None) == (cols[0] in ("k", "x"))
            got = eng.store._key_max_multiplicity_locked(td, cols, ts,
                                                         nulls)
            # the sort, with the shortcut switched off
            parts = [np.concatenate([c.data[cn] for c in td.chunks])
                     for cn in cols]
            live = np.concatenate([c.live_mask(ts) for c in td.chunks])
            ok = live.copy()
            for cn in cols:
                ok &= np.concatenate([c.valid[cn] for c in td.chunks])
            _, counts = np.unique(np.stack([p[ok] for p in parts]),
                                  axis=1, return_counts=True)
            want = int(counts.max())
            if nulls:
                want = max(want, int((live & ~ok).sum()))
            assert got == want
            if dense is not None:
                assert dense == want


# -- (c) the large-G kernel at limb widths 5 and 6 ---------------------------

SUMS_SQL = ("SELECT g, sum(a), sum(b), sum(c), sum(d), sum(e), sum(f), "
            "count(*) FROM wide GROUP BY g ORDER BY g")


def _wide_table(eng, values_of):
    """4,096 rows, four groups, six int64 columns to sum."""
    n = 4096
    rng = np.random.default_rng(20261001)
    eng.execute("CREATE TABLE wide (g INT8 NOT NULL, a INT8, b INT8, "
                "c INT8, d INT8, e INT8, f INT8)")
    cols = {"g": (np.arange(n) % 4).astype(np.int64)}
    for name in "abcdef":
        cols[name] = values_of(name, rng, n)
    eng.store.insert_columns("wide", cols, eng.clock.now())
    return cols


def _python_sums(cols):
    rows = []
    for g in range(4):
        m = cols["g"] == g
        rows.append((g, *[sum(int(x) for x in cols[c][m])
                          for c in "abcdef"], int(m.sum())))
    return rows


class TestNarrowLimbs:
    @pytest.mark.parametrize("n,max_group_rows,width,proven,limbs,rows", [
        # SF10's bucket under Engine._bound_agg_group_rows' bound: the
        # proven bits 13, 24, 4, 30 and 37 are 3 + 4 + 1 + 5 + 7 limbs
        # of 6, and no group of 29 M rows x 2^37 can pass int64
        (1 << 26, 29_000_000, 6, True, 20, 26),
        # the bound unknown: a group may be all 2^26 rows, limbs of 5,
        # and 2^26 x 2^37 = 2^63 keeps `charge` its shadow row
        (1 << 26, 0, 5, True, 23, 30),
        # SF1's bucket: limbs of 8, 2 + 3 + 1 + 4 + 5
        (1 << 23, 0, 8, True, 15, 21),
        # with no proof every argument is 64 bits and has its shadow:
        # 11 (13, 8) limbs each, what every plan carried before PR 32
        (1 << 26, 29_000_000, 6, False, 55, 66),
        (1 << 26, 0, 5, False, 65, 76),
        (1 << 23, 0, 8, False, 40, 51),
    ])
    def test_q1_layout_at_two_to_the_26(self, teng, n, max_group_rows,
                                        width, proven, limbs, rows):
        """Q1's operand plan from the plan alone, at SF10's row bucket
        and at SF1's: the i32 accumulator bound gives the limb width,
        the plan's value-range proofs the limbs an argument needs,
        the words it travels as and whether it keeps a shadow row."""
        from cockroach_tpu.exec import compile as C
        from cockroach_tpu.exec.stmtutil import _root_aggregate
        from cockroach_tpu.models import tpch
        from cockroach_tpu.ops.pallas import groupagg_large as pgl
        assert pgl.limb_width(n, max_group_rows) == width
        node, _ = teng._plan(teng._parse_cached(tpch.Q1), _session(teng))
        aggs = _root_aggregate(node).aggs
        assert [a.arg_bits for a in aggs] == [13, 24, 30, 37, 13, 24, 4, 0]
        if not proven:
            aggs = _without_proofs(aggs)
        lay = C.large_layout(aggs, n, max_group_rows)
        assert lay.w == width
        assert lay.n_words == (8 if proven else 12)
        assert lay.narrow == ([True, True, True, False, True] if proven
                              else [False] * 5)
        assert sum(r[0] == "limb" for r in lay.i_rows) == limbs
        assert len(lay.f_rows) + len(lay.i_rows) == rows
        if proven:
            # a shadow only where rows-a-group x 2^bits can reach 2^62
            charge = lay.src_of[lay.arg_of[3]]
            assert lay.f_rows == ([] if max_group_rows or n < 1 << 26
                                  else [("shadow", charge)])
            assert {i: k for i, (_, k) in lay.exact.items()} == {
                i: -(-a.arg_bits // width)
                for i, a in enumerate(aggs) if a.arg_bits}
        else:
            assert len(lay.f_rows) == 5
            assert {k for _, k in lay.exact.values()} == {-(-64 // width)}
        # over four shards a group can hold four times a shard's rows:
        # 2^24 x 2^37 stays under 2^62, 2^25 x 2^37 is 2^62
        if proven and n == 1 << 23:
            assert C.large_layout(aggs, n // 2, 0, n_shards=4).f_rows == []
            assert C.large_layout(aggs, n, 0, n_shards=4).f_rows \
                == [("shadow", charge)]

    @pytest.mark.parametrize("width", [5, 6])
    def test_sums_digit_for_digit_past_64_rows(self, monkeypatch, width):
        """Six 64-bit sums at the narrow widths: 78 or 66 limb rows and
        six shadow rows in one matmul. Column `a` sums to 1.5 x 2^62 a
        group and `b` to under -2^60 (1,024 rows a group): too large
        for the cheap bound, so the shadow rows decide, and int64
        holds every sum, so the sentinel must keep still."""
        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.ops.pallas import groupagg_large as pgl
        eng = Engine()

        def values_of(name, rng, n):
            if name == "a":     # 1,024 x 1.5 x 2^52 a group: above 2^62
                return rng.integers(6 << 50, 6 << 50 | 1 << 40, n)
            if name == "b":     # negative, to the same size
                return -rng.integers(1 << 50, 1 << 52, n)
            return rng.integers(-1 << 50, 1 << 50, n)

        cols = _wide_table(eng, values_of)
        want = _python_sums(cols)
        assert all(abs(r[1]) > 1 << 62 and abs(r[2]) > 1 << 60
                   for r in want)
        assert all(abs(x) < 1 << 63 for r in want for x in r[1:7])
        seen = []
        orig = pgl.large_group_aggregate

        def spy(*a, **kw):
            seen.append(kw["layout"])
            return orig(*a, **kw)

        monkeypatch.setattr(pgl, "large_group_aggregate", spy)
        monkeypatch.setattr(pgl, "limb_width", lambda *a, **kw: width)
        before = {k: t.value("large") for k, t in (
            ("bits", pg.LIMB_BITS), ("rows", pg.MATMUL_ROWS))}
        got = eng.execute(SUMS_SQL, session=_session(eng)).rows
        layout, = seen
        limbs = [r for r in layout if r[0] == "limb"]
        # (a column proven non-negative and under 2^63 needs a limb
        # fewer than the 13 or 11 of a full 64 bits)
        assert len(limbs) >= 6 * (-(-64 // width) - 1)
        assert len(layout) > 64 and {r[3] for r in limbs} == {width}
        assert pg.LIMB_BITS.value("large") - before["bits"] == width
        # a shadow rides the bf16 pass as three rows
        shadows = [r for r in layout if r[0] == "shadow"]
        assert len(shadows) == 6
        assert pg.MATMUL_ROWS.value("large") - before["rows"] \
            == len(layout) + 2 * len(shadows)
        assert [tuple(int(x) for x in r) for r in got] == want
        # and the scatter path agrees
        off = eng.execute(SUMS_SQL, session=_session(eng, "off")).rows
        assert [tuple(int(x) for x in r) for r in off] == want

    @pytest.mark.parametrize("width", [5, 6, 8])
    @pytest.mark.parametrize("past", [False, True])
    def test_the_sentinel_fires_when_a_sum_passes_int64(
            self, monkeypatch, width, past):
        """One group's sum of column `c` is 2^63 - 2^51 (held) or
        2^63 + 2^51 (wrapped): the statement answers exactly, or is
        refused. Nothing else in the table is near. The shadow that
        decides it is summed as three bf16 pieces in the limbs' own
        MXU pass: 24 significant bits, as the f32 row it replaced."""
        from cockroach_tpu.exec.engine import Engine, EngineError
        from cockroach_tpu.ops.pallas import groupagg_large as pgl
        eng = Engine()
        each = 1 << 53      # 1,024 of these a group: 2^63

        def values_of(name, rng, n):
            v = rng.integers(-1 << 40, 1 << 40, n)
            if name == "c":
                rows = np.arange(n) % 4 == 2
                v[rows] = each
                first = np.flatnonzero(rows)[0]
                v[first] += (1 << 51) if past else -(1 << 51)
            return v

        cols = _wide_table(eng, values_of)
        monkeypatch.setattr(pgl, "limb_width", lambda *a, **kw: width)
        fallbacks = pg.FALLBACKS.value()
        if past:
            with pytest.raises(EngineError, match="overflowed int64"):
                eng.execute(SUMS_SQL, session=_session(eng))
        else:
            got = eng.execute(SUMS_SQL, session=_session(eng)).rows
            want = _python_sums(cols)
            assert want[2][3] == (1 << 63) - (1 << 51)
            assert [tuple(int(x) for x in r) for r in got] == want
        assert pg.FALLBACKS.value() == fallbacks    # the kernel path
