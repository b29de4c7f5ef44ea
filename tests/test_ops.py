"""Unit tests for the device columnar core (ops/)."""

import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.ops import agg, hashtable, kernels
from cockroach_tpu.ops.batch import ColumnBatch, concat, pad_to
from cockroach_tpu.ops.join import hash_join


def mk(vals, valid=None):
    v = jnp.asarray(vals)
    m = jnp.ones(v.shape, jnp.bool_) if valid is None else jnp.asarray(valid)
    return (v, m)


class TestKernels:
    def test_arith_null_propagation(self):
        a = mk([1, 2, 3], [True, False, True])
        b = mk([10, 20, 30])
        v, m = kernels.add(a, b)
        assert v[0] == 11 and v[2] == 33
        assert list(np.asarray(m)) == [True, False, True]

    def test_div_by_zero_is_null(self):
        v, m = kernels.div(mk([10.0, 4.0]), mk([2.0, 0.0]))
        assert v[0] == 5.0
        assert not bool(m[1])

    def test_kleene_and(self):
        # (TRUE, NULL, FALSE) x (TRUE, NULL, FALSE) truth table
        t, n, f = (True, True), (False, False), (False, True)  # (val, valid)
        vals = [t, n, f]
        expect = {
            (0, 0): (True, True), (0, 1): (None, False), (0, 2): (False, True),
            (1, 0): (None, False), (1, 1): (None, False), (1, 2): (False, True),
            (2, 0): (False, True), (2, 1): (False, True), (2, 2): (False, True),
        }
        for (i, j), (ev, em) in expect.items():
            a = mk([vals[i][0]], [vals[i][1]])
            b = mk([vals[j][0]], [vals[j][1]])
            v, m = kernels.and_(a, b)
            assert bool(m[0]) == em, (i, j)
            if em:
                assert bool(v[0]) == ev, (i, j)

    def test_kleene_or(self):
        # NULL OR TRUE = TRUE; NULL OR FALSE = NULL
        v, m = kernels.or_(mk([False], [False]), mk([True]))
        assert bool(m[0]) and bool(v[0])
        v, m = kernels.or_(mk([False], [False]), mk([False]))
        assert not bool(m[0])

    def test_case_when(self):
        c1 = mk([True, False, False])
        c2 = mk([False, True, False])
        out_v, out_m = kernels.case_when(
            [(c1, mk([1, 1, 1])), (c2, mk([2, 2, 2]))], mk([9, 9, 9]))
        assert list(np.asarray(out_v)) == [1, 2, 9]

    def test_between_in(self):
        v, m = kernels.between(mk([1, 5, 9]), mk([2, 2, 2]), mk([6, 6, 6]))
        assert list(np.asarray(v)) == [False, True, False]
        v, m = kernels.in_list(mk([1, 5, 9]), [5, 9])
        assert list(np.asarray(v)) == [False, True, True]


class TestBatch:
    def test_roundtrip_and_filter(self):
        b = ColumnBatch.from_dict({"a": jnp.arange(5), "b": jnp.arange(5) * 10})
        b2 = b.and_sel(b.col("a") >= 2)
        host = b2.to_host()
        assert list(host["a"]) == [2, 3, 4]
        assert list(host["b"]) == [20, 30, 40]

    def test_with_column_replace(self):
        b = ColumnBatch.from_dict({"a": jnp.arange(3)})
        b = b.with_column("c", b.col("a") + 100)
        b = b.with_column("c", b.col("c") + 1)
        assert list(b.to_host()["c"]) == [101, 102, 103]

    def test_pad_and_concat(self):
        b = ColumnBatch.from_dict({"a": jnp.arange(3)})
        p = pad_to(b, 8)
        assert p.n == 8
        assert int(p.sel.sum()) == 3
        c = concat([b, b])
        assert c.n == 6

    def test_null_masking_to_host(self):
        b = ColumnBatch.from_dict(
            {"a": jnp.array([1, 2, 3])},
            valid={"a": jnp.array([True, False, True])})
        out = b.to_host()["a"]
        assert bool(out.mask[1]) and not bool(out.mask[0])


class TestAgg:
    def test_masked_reductions(self):
        d = jnp.array([1.0, 2.0, 3.0, 4.0])
        m = jnp.array([True, False, True, True])
        assert float(agg.masked_sum(d, m)) == 8.0
        assert int(agg.masked_count(m)) == 3
        assert float(agg.masked_min(d, m)) == 1.0
        assert float(agg.masked_max(d, m)) == 4.0

    def test_group_aggs(self):
        d = jnp.array([1, 2, 3, 4, 5], dtype=jnp.int64)
        g = jnp.array([0, 1, 0, 1, 2], dtype=jnp.int32)
        m = jnp.array([True, True, True, True, False])
        s = agg.group_sum(d, g, m, 4)
        assert list(np.asarray(s))[:3] == [4, 6, 0]
        c = agg.group_count(g, m, 4)
        assert list(np.asarray(c))[:3] == [2, 2, 0]
        mx = agg.group_max(d, g, m, 4)
        assert int(mx[1]) == 4

    def test_group_any_constant_groups(self):
        """group_any picks the per-group value (inputs constant per
        group by the FD-reduction contract) across dtypes, including
        the 64-bit limb path and negative values; empty/masked groups
        hold a very negative identity (pmax-merge safe)."""
        g = jnp.array([0, 0, 1, 2, 1], dtype=jnp.int32)
        m = jnp.array([True, True, True, False, True])
        for G in (4, 40):  # 4 = unrolled small-G branch, 40 = limbs
            for dtype, vals in [
                (jnp.int64, [-7, -7, 123456789012345, 9,
                             123456789012345]),
                (jnp.int32, [5, 5, -2, 9, -2]),
                (jnp.float64, [1.5, 1.5, -2.25, 9.0, -2.25]),
                (jnp.float32, [1.5, 1.5, -2.25, 9.0, -2.25]),
            ]:
                d = jnp.array(vals, dtype=dtype)
                out = np.asarray(agg.group_any(d, g, m, G))
                assert out[0] == vals[0] and out[1] == vals[2], \
                    (G, dtype, out)
                # masked-out group 2 and the never-scattered empty
                # group 3 both hold the identity: below any real value
                for slot in (2, 3):
                    assert out[slot] < -1e15 \
                        or out[slot] == np.iinfo(np.int32).min \
                        or out[slot] == -np.inf, (G, dtype, slot, out)

    def test_avg_decomposition(self):
        spec = agg.AggSpec("avg", "x", "avg_x")
        assert spec.local_funcs == ["sum", "count"]
        assert spec.merge_ops == ["psum", "psum"]


class TestHashTable:
    def test_group_ids_dense(self):
        keys = (jnp.array([7, 7, 3, 9, 3, 7], dtype=jnp.int64),)
        mask = jnp.ones(6, jnp.bool_)
        gid, ng, rep = hashtable.group_ids(keys, mask, 16)
        gid = np.asarray(gid)
        assert int(ng) == 3
        # same key -> same gid, different key -> different gid
        assert gid[0] == gid[1] == gid[5]
        assert gid[2] == gid[4]
        assert len({gid[0], gid[2], gid[3]}) == 3
        # rep rows map back to the right keys
        k = np.asarray(keys[0])
        assert {int(k[r]) for r in np.asarray(rep)[:3]} == {7, 3, 9}

    def test_group_ids_multicol_and_mask(self):
        k1 = jnp.array([1, 1, 1, 2], dtype=jnp.int64)
        k2 = jnp.array([5, 6, 5, 5], dtype=jnp.int64)
        mask = jnp.array([True, True, True, False])
        gid, ng, _ = hashtable.group_ids((k1, k2), mask, 16)
        assert int(ng) == 2
        assert int(gid[0]) == int(gid[2])
        assert int(gid[0]) != int(gid[1])

    def test_probe(self):
        bkeys = (jnp.array([10, 20, 30], dtype=jnp.int64),)
        claim, _, conv = hashtable.build(bkeys, jnp.ones(3, jnp.bool_), 16)
        assert bool(conv)
        pkeys = (jnp.array([20, 99, 10, 30], dtype=jnp.int64),)
        matched, row = hashtable.probe(claim, bkeys, pkeys,
                                       jnp.ones(4, jnp.bool_), 16, 3)
        assert list(np.asarray(matched)) == [True, False, True, True]
        assert list(np.asarray(row)[[0, 2, 3]]) == [1, 0, 2]

    def test_many_collisions(self):
        # All keys congruent mod capacity -> long probe chains
        keys = (jnp.arange(0, 640, 64, dtype=jnp.int64) * 0 +
                jnp.arange(10, dtype=jnp.int64) * 1024,)
        gid, ng, _ = hashtable.group_ids(keys, jnp.ones(10, jnp.bool_), 32)
        assert int(ng) == 10
        assert len(set(np.asarray(gid).tolist())) == 10


class TestJoin:
    def _sides(self):
        probe = ColumnBatch.from_dict({
            "pk": jnp.array([1, 2, 3, 4, 2], dtype=jnp.int64),
            "val": jnp.array([10, 20, 30, 40, 21], dtype=jnp.int64)})
        build = ColumnBatch.from_dict({
            "bk": jnp.array([2, 4, 8], dtype=jnp.int64),
            "name": jnp.array([200, 400, 800], dtype=jnp.int64)})
        return probe, build

    def test_inner(self):
        probe, build = self._sides()
        out = hash_join(probe, build, ["pk"], ["bk"], ["name"], "inner")
        h = out.to_host()
        assert list(h["pk"]) == [2, 4, 2]
        assert list(h["name"]) == [200, 400, 200]

    def test_left(self):
        probe, build = self._sides()
        out = hash_join(probe, build, ["pk"], ["bk"], ["name"], "left")
        h = out.to_host()
        assert len(h["pk"]) == 5
        assert list(h["name"].mask) == [True, False, True, False, False]

    def test_semi_anti(self):
        probe, build = self._sides()
        semi = hash_join(probe, build, ["pk"], ["bk"], [], "semi").to_host()
        assert list(semi["pk"]) == [2, 4, 2]
        anti = hash_join(probe, build, ["pk"], ["bk"], [], "anti").to_host()
        assert list(anti["pk"]) == [1, 3]

    def test_null_keys_never_match(self):
        probe = ColumnBatch.from_dict(
            {"pk": jnp.array([2, 2], dtype=jnp.int64)},
            valid={"pk": jnp.array([True, False])})
        build = ColumnBatch.from_dict({"bk": jnp.array([2], dtype=jnp.int64),
                                       "x": jnp.array([7], dtype=jnp.int64)})
        out = hash_join(probe, build, ["pk"], ["bk"], ["x"], "inner")
        assert len(out.to_host()["pk"]) == 1


if __name__ == "__main__":
    pytest.main([__file__, "-v"])


def _has_compact(n):
    from cockroach_tpu.sql import plan as P
    for a in ("child", "left", "right"):
        c = getattr(n, a, None)
        if c is not None and (isinstance(c, P.Compact) or _has_compact(c)):
            return True
    return isinstance(n, P.Compact)


class TestCompaction:
    """Selection compaction (compile.compact_batch): low-selectivity
    scans under aggregation pack survivors before join probes / agg
    partials. Round-3 perf work; correctness pinned here."""

    def _engine_with_skew(self, rows=1 << 17, sorted_=False):
        import numpy as np
        from cockroach_tpu.exec.engine import Engine
        e = Engine()
        e.execute("CREATE TABLE sk (k INT PRIMARY KEY, d INT, v INT)")
        rng = np.random.default_rng(0)
        d = rng.integers(0, 100, rows)
        if sorted_:
            d = np.sort(d)  # matching rows cluster into few blocks
        cols = {"k": np.arange(rows, dtype=np.int64),
                "d": d.astype(np.int64),
                "v": rng.integers(0, 1000, rows).astype(np.int64)}
        e.store.insert_columns("sk", cols, e.clock.now())
        return e, cols

    def _add_dim(self, e, rows):
        import numpy as np
        e.execute("CREATE TABLE skdim (id INT PRIMARY KEY, w INT)")
        g = np.random.default_rng(7)
        w = g.integers(0, 9, 100)
        e.store.insert_columns(
            "skdim", {"id": np.arange(100, dtype=np.int64),
                      "w": w.astype(np.int64)}, e.clock.now())
        return w

    JOINQ = ("SELECT count(*), sum(skdim.w) FROM sk "
             "JOIN skdim ON skdim.id = sk.d WHERE sk.d < 10")

    def test_compacted_join_aggregate_exact(self):
        import numpy as np
        e, cols = self._engine_with_skew()
        w = self._add_dim(e, len(cols["d"]))
        got = e.execute(self.JOINQ).rows
        m = cols["d"] < 10
        assert got == [(int(m.sum()), int(w[cols["d"][m]].sum()))]
        # the plan really compacted (selectivity ~0.1 <= 1/8, probe
        # side of a join under aggregation)
        from cockroach_tpu.sql import parser
        node, _ = e._plan(parser.parse(self.JOINQ), e.session())
        assert _has_compact(e._insert_compaction(node))

    def test_no_join_scan_agg_stays_masked(self):
        """Q6-shaped scan+filter+agg must NOT compact: the masked
        pipeline fuses fully; compaction only pays on join probes
        (measured 1.9B -> 33M rows/s when Q6 was compacted)."""
        from cockroach_tpu.sql import parser
        e, cols = self._engine_with_skew()
        q = "SELECT count(*), sum(v) FROM sk WHERE d < 10"
        node, _ = e._plan(parser.parse(q), e.session())
        assert not _has_compact(e._insert_compaction(node))
        m = cols["d"] < 10
        assert e.execute(q).rows == [(int(m.sum()),
                                      int(cols["v"][m].sum()))]

    def test_skewed_blocks_overflow_and_replan(self):
        """Sorted data clusters every match into a few blocks: the
        per-block capacity overflows, the sentinel trips, and the
        engine replans uncompacted — same answer, no missing rows."""
        import numpy as np
        e, cols = self._engine_with_skew(sorted_=True)
        w = self._add_dim(e, len(cols["d"]))
        got = e.execute(self.JOINQ).rows
        m = cols["d"] < 10
        assert got == [(int(m.sum()), int(w[cols["d"][m]].sum()))]

    def test_small_batches_skip_compaction(self):
        import numpy as np
        e, cols = self._engine_with_skew(rows=4096)
        w = self._add_dim(e, 4096)
        got = e.execute(self.JOINQ).rows
        m = cols["d"] < 10
        assert got == [(int(m.sum()), int(w[cols["d"][m]].sum()))]

    def test_compacted_join_probe(self):
        """Compaction under a join probe: the direct-address gather
        runs at frac width; result matches the uncompacted path."""
        import numpy as np
        from cockroach_tpu.exec.engine import Engine
        rows = 1 << 17
        e = Engine()
        e.execute("CREATE TABLE dim (id INT PRIMARY KEY, w INT)")
        e.execute("CREATE TABLE fact (k INT PRIMARY KEY, fk INT, "
                  "d INT)")
        rng = np.random.default_rng(1)
        dim_n = 500
        e.store.insert_columns(
            "dim", {"id": np.arange(dim_n, dtype=np.int64),
                    "w": rng.integers(0, 9, dim_n).astype(np.int64)},
            e.clock.now())
        d = rng.integers(0, 100, rows)
        fk = rng.integers(0, dim_n, rows)
        e.store.insert_columns(
            "fact", {"k": np.arange(rows, dtype=np.int64),
                     "fk": fk.astype(np.int64),
                     "d": d.astype(np.int64)}, e.clock.now())
        q = ("SELECT sum(dim.w) FROM fact JOIN dim ON dim.id = fact.fk "
             "WHERE fact.d < 7")
        got = e.execute(q).rows
        # numpy oracle from the same generator sequence
        g = np.random.default_rng(1)
        wdim = g.integers(0, 9, dim_n)
        d2 = g.integers(0, 100, rows)
        fk2 = g.integers(0, dim_n, rows)
        want = int(wdim[fk2[d2 < 7]].sum())
        assert got == [(want,)]


# -- compact_batch: the displacement network, the body the chip runs ---------

def _compact_case(density, block, nb, kb, seed):
    """A selection mask by name: how many rows of each block survive."""
    rng = np.random.default_rng(seed)
    sel = np.zeros((nb, block), bool)
    per_block = {"none": 0, "sparse": kb // 5, "exactly_kb": kb,
                 "over_kb": kb + 1 + kb // 3, "all": block}[density]
    for i in range(nb):
        sel[i, rng.choice(block, per_block, replace=False)] = True
    if density == "over_kb":
        sel[1:] = False                 # one block over, the rest empty
        sel[1, :7] = True
    return sel.reshape(-1)


@pytest.mark.parametrize("inner_flag", [False, True],
                         ids=["alone", "inner_overflow"])
@pytest.mark.parametrize("block", [1024, 32768])
@pytest.mark.parametrize("density", ["none", "sparse", "exactly_kb",
                                     "over_kb", "all"])
def test_compact_batch_equals_the_plain_reference(density, block,
                                                  inner_flag):
    """Every block's survivors, first kb of them, in ascending order,
    of every column kind, against numpy's x[sel][:kb]; the overflow
    flag where a block holds more than kb or an inner Compact said
    so."""
    from cockroach_tpu.exec.compile import (_compact_block_rows,
                                            compact_batch)
    nb, frac = 3, 0.25
    n = nb * block
    kb = _compact_block_rows(n, frac, block)
    assert kb == block // 4
    sel = _compact_case(density, block, nb, kb, seed=block + len(density))
    rng = np.random.default_rng(5)
    cols = {
        "row": np.arange(n, dtype=np.int32),
        "i32": rng.integers(-2**31, 2**31, n).astype(np.int32),
        "i64": rng.integers(-2**62, 2**62, n),
        # a 64-bit column the plan proves within int32: one word
        "i64_narrow": rng.integers(-2**31 + 1, 2**31 - 1, n),
        "flag": rng.random(n) < 0.5,
        "f32": rng.random(n).astype(np.float32),
        "f64": rng.random(n),       # fetched by packed row numbers
        "nullable": rng.integers(0, 1000, n),
    }
    nulls = rng.random(n) < 0.7
    b = ColumnBatch.from_dict(
        {k: jnp.asarray(v) for k, v in cols.items()},
        {"nullable": jnp.asarray(nulls)}, sel=jnp.asarray(sel))
    if inner_flag:
        b = b.with_column("__compact_overflow", jnp.ones((n,), bool))
    out = compact_batch(b, frac, block, frozenset({"i64_narrow"}),
                        interpret=True)
    assert out.n == nb * kb
    osel = np.asarray(out.sel).reshape(nb, kb)
    for name, x in cols.items():
        got = np.asarray(out.col(name))
        assert got.dtype == x.dtype
        got = got.reshape(nb, kb)
        gotv = np.asarray(out.col_valid(name)).reshape(nb, kb)
        for i in range(nb):
            s = sel[i * block:(i + 1) * block]
            want = x[i * block:(i + 1) * block][s][:kb]
            k = len(want)
            assert osel[i, :k].all() and not osel[i, k:].any()
            np.testing.assert_array_equal(got[i, :k], want)
            wantv = (nulls[i * block:(i + 1) * block][s][:kb]
                     if name == "nullable" else np.ones(k, bool))
            np.testing.assert_array_equal(gotv[i, :k], wantv)
            # behind the survivors one row repeats, the block's first:
            # one address a block for what reads unselected rows too
            assert (got[i, k:] == got[i, 0]).all()
    rows = np.asarray(out.col("row")).reshape(nb, kb)
    for i in range(nb):
        k = int(osel[i].sum())
        assert (np.diff(rows[i, :k]) > 0).all()     # ascending
    flag = np.asarray(out.col("__compact_overflow"))
    assert flag.all() == flag.any() == (density in ("over_kb", "all")
                                        or inner_flag)


def test_compact_counters_and_the_plan_span_tag_read_the_plan():
    """exec.compact.* and the `plan` span's `compacts`: a Q3-shaped
    join holds one Compact, a Q6-shaped scan none, a spine wrapped
    again two."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.utils import tracing

    block = 32768
    n = 16 * block
    eng = Engine()
    eng.execute("CREATE TABLE f (k1 INT8 NOT NULL, k2 INT8 NOT NULL, "
                "g INT8 NOT NULL, v INT8 NOT NULL)")
    ids = np.arange(1, 1025, dtype=np.int64)
    for t in ("d1", "d2"):
        eng.execute(f"CREATE TABLE {t} (id INT8 PRIMARY KEY, "
                    "a INT8 NOT NULL)")
        eng.store.insert_columns(t, {"id": ids, "a": ids % 16},
                                 eng.clock.now())
    rng = np.random.default_rng(37)
    f = {c: rng.integers(1, 1025, n) for c in ("k1", "k2")}
    f["g"] = rng.integers(0, 100, n)
    f["v"] = rng.integers(1, 1000, n)
    eng.store.insert_columns("f", f, eng.clock.now())
    for t in ("f", "d1", "d2"):
        eng.execute(f"ANALYZE {t}")
    s = eng.session()
    s.vars.set("distsql", "off")
    keep1, keep2 = f["k1"] % 16 == 0, f["k2"] % 16 == 0

    def counts():
        snap = eng.metrics.snapshot()
        return [snap[f"exec.compact.{k}"]
                for k in ("compacts", "rows_in", "rows_out", "columns")]

    cases = [
        ("select g, sum(v) from f, d1 where k1 = d1.id and d1.a = 0 "
         "group by g order by g", keep1, [(n, n // 4)]),
        ("select sum(v) from f where g < 5",
         int(f["v"][f["g"] < 5].sum()), []),
        ("select g, sum(v) from f, d1, d2 where k1 = d1.id and "
         "k2 = d2.id and d1.a = 0 and d2.a = 0 group by g order by g",
         keep1 & keep2, [(n, n // 4), (n // 4, n // 64)]),
    ]
    for sql, want, shapes in cases:
        before = counts()
        tracing.start_collector()
        try:
            got = eng.execute(sql, session=s).rows
        finally:
            roots = tracing.stop_collector()

        def plans(span):
            if span.name == "plan":
                yield span.tags
            for c in span.children:
                yield from plans(c)

        tags = [t for r in roots for t in plans(r)]
        assert [t["compacts"] for t in tags] == [len(shapes)], sql
        d = [a - b for a, b in zip(counts(), before)]
        assert d[:3] == [len(shapes), sum(a for a, _ in shapes),
                         sum(b for _, b in shapes)], sql
        assert (d[3] > 0) == bool(shapes)
        if isinstance(want, np.ndarray):
            want = [(int(x), int(f["v"][want & (f["g"] == x)].sum()))
                    for x in np.unique(f["g"][want])]
            assert [tuple(r) for r in got] == want
        else:
            assert got == [(want,)]


def test_a_compact_carries_proven_int32_columns_as_one_word():
    """P.Compact.narrow: the stored columns beneath whose all-versions
    range fits int32 (a probe column, a payload); a column past 2^31
    keeps both words, and both kinds sum exactly."""
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.sql import parser
    from cockroach_tpu.sql import plan as P

    n = 1 << 17
    eng = Engine()
    eng.execute("CREATE TABLE f (k INT8 NOT NULL, g INT8 NOT NULL, "
                "small INT8 NOT NULL, big INT8 NOT NULL)")
    eng.execute("CREATE TABLE d (id INT8 PRIMARY KEY, a INT8 NOT NULL, "
                "w INT8 NOT NULL)")
    ids = np.arange(1, 1025, dtype=np.int64)
    eng.store.insert_columns("d", {"id": ids, "a": ids % 16,
                                   "w": -ids}, eng.clock.now())
    rng = np.random.default_rng(3)
    f = {"k": rng.integers(1, 1025, n), "g": rng.integers(0, 100, n),
         "small": rng.integers(-2**31 + 2, 2**31 - 2, n),
         "big": rng.integers(-2**40, 2**40, n)}
    eng.store.insert_columns("f", f, eng.clock.now())
    for t in ("f", "d"):
        eng.execute(f"ANALYZE {t}")
    s = eng.session()
    s.vars.set("distsql", "off")
    sql = ("select g, sum(small), sum(big), sum(w) from f, d "
           "where k = d.id and d.a = 0 group by g order by g")
    node, _ = eng._plan(parser.parse(sql), s)
    eng._check_join_builds(node, eng._read_ts(s), {})
    node = eng._insert_compaction(node)
    compacts = []
    while node is not None:
        if isinstance(node, P.Compact):
            compacts.append(node)
        node = getattr(node, "child", None) or getattr(node, "left", None)
    assert len(compacts) == 1
    narrow = compacts[0].narrow
    assert {"f.small", "f.g", "f.k"} <= narrow and "f.big" not in narrow
    keep = f["k"] % 16 == 0
    want = [(int(x), int(f["small"][keep & (f["g"] == x)].sum()),
             int(f["big"][keep & (f["g"] == x)].sum()),
             int(-f["k"][keep & (f["g"] == x)].sum()))
            for x in np.unique(f["g"][keep])]
    assert [tuple(r) for r in eng.execute(sql, session=s).rows] == want
