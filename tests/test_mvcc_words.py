"""The MVCC pair as 32-bit words on the device (ops/batch.py).

The store keeps a row version's timestamps as int64; a device batch
holds each as a signed high word and an unsigned low word, and the
scan compares words. Pinned here: the word compare is the int64
compare for every int64 triple; every producer of a scan batch hands
the scan the four word columns and no 64-bit `_mvcc_*` column; and
`exec.scan.wide_args` counts the 64-bit row-length arguments a
prepared statement's scans still hold.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.distsql.node import Gateway, _arrays_to_batch
from cockroach_tpu.exec.ctecompose import make_glue
from cockroach_tpu.exec.engine import Engine
from cockroach_tpu.exec.stream import PageSource
from cockroach_tpu.models import tpch
from cockroach_tpu.ops.batch import (MAX_TS, MVCC_COLUMNS, MVCC_DEL_HI,
                                     MVCC_DEL_LO, MVCC_TS_HI, MVCC_TS_LO,
                                     NEVER_TS, SCAN_WIDE_ARGS, ColumnBatch,
                                     alloc_mvcc_words, fill_mvcc_words,
                                     mvcc_live, put_mvcc_words,
                                     read_ts_words, ts_words)
from cockroach_tpu.parallel.mesh import make_mesh

I64_MIN, I64_MAX = -2 ** 63, 2 ** 63 - 1
# where a word carries, borrows or changes sign, and the sentinels
BOUNDARIES = sorted({
    I64_MIN, I64_MIN + 1, -2 ** 62, -2 ** 32 - 1, -2 ** 32,
    -2 ** 32 + 1, -2 ** 31 - 1, -2 ** 31, -2 ** 31 + 1, -2, -1, 0, 1,
    2, 2 ** 31 - 1, 2 ** 31, 2 ** 31 + 1, 2 ** 32 - 1, 2 ** 32,
    2 ** 32 + 1, 2 ** 33 - 1, NEVER_TS - 1, NEVER_TS, NEVER_TS + 1,
    MAX_TS - 1, MAX_TS,
    # an HLC timestamp of 2026 and its neighbours across a low word
    1_790_000_000_000_000_000, (416_000_000 << 32) - 1,
    416_000_000 << 32, (416_000_000 << 32) + 1})


def _int64_of(hi, lo) -> np.ndarray:
    """The int64 values a pair of word columns holds."""
    return (np.asarray(hi).astype(np.int64) << 32) \
        | np.asarray(lo).astype(np.int64)


def _word_batch(ts: np.ndarray, dl: np.ndarray) -> ColumnBatch:
    words = alloc_mvcc_words(len(ts))
    put_mvcc_words(words, 0, ts, dl)
    return ColumnBatch.from_dict({k: jnp.asarray(v)
                                  for k, v in words.items()})


_live = jax.jit(mvcc_live)


def _assert_compare_equal(ts, dl, reads) -> None:
    ts = np.asarray(ts, dtype=np.int64)
    dl = np.asarray(dl, dtype=np.int64)
    b = _word_batch(ts, dl)
    for r in reads:
        got = np.asarray(_live(b, read_ts_words(int(r))))
        want = (ts <= np.int64(r)) & (np.int64(r) < dl)
        bad = np.flatnonzero(got != want)
        assert not len(bad), (int(r), int(ts[bad[0]]), int(dl[bad[0]]))


class TestWordCompare:
    def test_every_boundary_triple(self):
        pairs = np.array(list(itertools.product(BOUNDARIES, repeat=2)),
                         dtype=np.int64)
        _assert_compare_equal(pairs[:, 0], pairs[:, 1], BOUNDARIES)

    @pytest.mark.parametrize("seed", range(4))
    def test_random_triples(self, seed):
        rng = np.random.default_rng(seed)
        n = 4096

        def draw(k):
            # whole range, and values that share a high word with a
            # boundary so the low words decide
            wide = rng.integers(I64_MIN, I64_MAX, size=k, dtype=np.int64,
                                endpoint=True)
            near = rng.choice(np.array(BOUNDARIES, dtype=np.int64), k) \
                // 2 ** 32 * 2 ** 32 \
                + rng.integers(0, 2 ** 32, size=k, dtype=np.int64)
            return np.where(rng.random(k) < 0.5, wide, near)
        _assert_compare_equal(draw(n), draw(n), draw(48))

    def test_host_words_round_trip(self):
        vals = np.array(BOUNDARIES, dtype=np.int64)
        words = alloc_mvcc_words(len(vals) + 3)
        put_mvcc_words(words, 0, vals, vals[::-1])
        fill_mvcc_words(words, len(vals), len(vals) + 3, NEVER_TS, 0)
        assert [words[c].dtype for c in MVCC_COLUMNS] == [
            np.int32, np.uint32, np.int32, np.uint32]
        ts = _int64_of(words[MVCC_TS_HI], words[MVCC_TS_LO])
        dl = _int64_of(words[MVCC_DEL_HI], words[MVCC_DEL_LO])
        assert (ts[:len(vals)] == vals).all()
        assert (dl[:len(vals)] == vals[::-1]).all()
        assert (ts[len(vals):] == NEVER_TS).all()
        assert (dl[len(vals):] == 0).all()
        for v in BOUNDARIES:
            hi, lo = ts_words(v)
            assert int(_int64_of(hi, lo)) == v
            r = read_ts_words(v)
            assert r.dtype == np.uint32 and r.shape == (2,)
            assert int(_int64_of(r[:1].view(np.int32), r[1:])[0]) == v

    @pytest.mark.parametrize("bad", [I64_MAX + 1, I64_MIN - 1])
    def test_past_int64_is_refused(self, bad):
        with pytest.raises(OverflowError):
            ts_words(bad)


# ---------------------------------------------------------------------------
# every producer of a scan batch writes words
# ---------------------------------------------------------------------------

N_ROWS = 3000


@pytest.fixture(scope="module")
def weng():
    eng = Engine(mesh=make_mesh(n=4))
    eng.execute("CREATE TABLE t (k INT8 NOT NULL PRIMARY KEY, v INT8)")
    for lo in range(0, N_ROWS, 1000):
        eng.execute("INSERT INTO t (k, v) VALUES " + ", ".join(
            f"({i}, {i % 7})" for i in range(lo, lo + 1000)))
    eng.execute("DELETE FROM t WHERE k < 10")
    eng.store.seal("t")
    return eng


def _store_pair(eng, chunks=None):
    chunks = eng.store.table("t").chunks if chunks is None else chunks
    return (np.concatenate([c.mvcc_ts for c in chunks]),
            np.concatenate([c.mvcc_del for c in chunks]))


def _resident(eng):
    return eng._device_table("t"), _store_pair(eng)


def _sharded(eng):
    b = eng._device_table("t", "sharded")
    for c in MVCC_COLUMNS:
        assert len(b.col(c).sharding.device_set) == 4
    return b, _store_pair(eng)


def _overlay(eng):
    s = eng.session()
    eng.execute("BEGIN", s)
    try:
        eng.execute("INSERT INTO t (k, v) VALUES (900001, 1)", s)
        eng.execute("DELETE FROM t WHERE k = 20", s)
        rts = eng._read_ts(s)
        b = eng._overlay_batch("t", s.effects, rts)
        chunks = eng._overlay_chunks("t", s.effects, rts)
        # the txn's own delete and insert are in what was uploaded
        assert sum(c.n for c in chunks) == N_ROWS + 1
        return b, _store_pair(eng, chunks)
    finally:
        eng.execute("ROLLBACK", s)


def _streamed_page(eng):
    src = PageSource(eng.store.table("t"), frozenset({"k"}), 1024)
    ts, dl = _store_pair(eng)
    return next(iter(src.pages())), (ts[:1024], dl[:1024])


def _streamed_last_page(eng):
    src = PageSource(eng.store.table("t"), frozenset({"k"}), 1024)
    ts, dl = _store_pair(eng)
    at = N_ROWS // 1024 * 1024
    return list(src.pages())[-1], (ts[at:], dl[at:])


def _empty_page(eng):
    src = PageSource(eng.store.table("t"), frozenset({"k"}), 1024)
    return src.empty_page(), (np.zeros(0, np.int64),) * 2


def _spill_gather(eng):
    src = PageSource(eng.store.table("t"), frozenset({"k"}), 1024)
    idx = np.arange(5, N_ROWS, 3, dtype=np.int64)
    ts, dl = _store_pair(eng)
    return src.gather_batch(idx, 1024), (ts[idx], dl[idx])


def _always_visible(n):
    return np.zeros(n, np.int64), np.full(n, MAX_TS, np.int64)


def _distsql_exchange(eng):
    chunk = (5, {"a": np.arange(5, dtype=np.int64)},
             {"a": np.ones(5, dtype=bool)})
    return (_arrays_to_batch([chunk, chunk], ["a"], {}, None),
            _always_visible(10))


def _distsql_union(eng):
    chunk = (5, {"a": np.arange(5, dtype=np.int64)},
             {"a": np.ones(5, dtype=bool)})
    b, _dicts = Gateway._union_batch(None, [chunk], ["a"], {})
    return b, _always_visible(5)


def _cte_glue(eng):
    template = eng._device_table("t")
    sub = ColumnBatch.from_dict(
        {"x": jnp.arange(64, dtype=jnp.int64),
         "y": jnp.arange(64, dtype=jnp.int64)},
        sel=jnp.arange(64) % 2 == 0)
    glue = make_glue(template, {"k": "x", "v": "y"}, {}, 1024)
    b, overflow = glue(sub)
    assert not bool(overflow)
    assert b.names == template.names
    return b, (np.ones(32, np.int64), np.full(32, MAX_TS, np.int64))


PRODUCERS = {"resident": _resident, "sharded_mesh": _sharded,
             "overlay": _overlay, "streamed_page": _streamed_page,
             "streamed_last_page": _streamed_last_page,
             "empty_page": _empty_page, "spill_gather": _spill_gather,
             "distsql_exchange": _distsql_exchange,
             "distsql_union": _distsql_union, "cte_glue": _cte_glue}


@pytest.mark.parametrize("producer", sorted(PRODUCERS))
def test_producer_hands_the_scan_words(weng, producer):
    b, (ts, dl) = PRODUCERS[producer](weng)
    assert [n for n in b.names if n.startswith("_mvcc_")] \
        == list(MVCC_COLUMNS)
    for c, dt in zip(MVCC_COLUMNS,
                     (np.int32, np.uint32, np.int32, np.uint32)):
        assert b.col(c).dtype == dt and b.col(c).shape == (b.n,), c
    n = len(ts)
    got_ts = _int64_of(b.col(MVCC_TS_HI), b.col(MVCC_TS_LO))
    got_dl = _int64_of(b.col(MVCC_DEL_HI), b.col(MVCC_DEL_LO))
    assert (got_ts[:n] == ts).all() and (got_dl[:n] == dl).all()
    # the padding no statement sees, at the last snapshot before it
    at = NEVER_TS - 1
    live = np.asarray(mvcc_live(b, read_ts_words(at)))
    assert not live[n:].any()
    assert (live[:n] == ((ts <= at) & (at < dl))).all()


def test_snapshot_reads_through_words(weng):
    """The scan, end to end: a delete is invisible to a snapshot
    taken before it and visible after."""
    r = weng.execute("SELECT count(*) AS c FROM t")
    assert r.rows == [(N_ROWS - 10,)]
    ts, dl = _store_pair(weng)
    before = int(dl[dl != MAX_TS].min()) - 1
    b = weng._device_table("t")
    live = np.asarray(mvcc_live(b, read_ts_words(before)))
    assert int(live.sum()) == int(((ts <= before) & (before < dl)).sum())
    assert int(live.sum()) > N_ROWS - 10


# ---------------------------------------------------------------------------
# exec.scan.wide_args
# ---------------------------------------------------------------------------

def test_wide_args_counts_64_bit_scan_columns():
    eng = Engine()
    tpch.load(eng, sf=0.01, rows=5000)
    s = eng.session()
    s.vars.set("distsql", "off")

    def wide_args(sql):
        before = SCAN_WIDE_ARGS.value()
        p = eng.prepare(sql, session=s)
        p.run()
        return SCAN_WIDE_ARGS.value() - before, p

    for sql in (tpch.Q6, tpch.Q1):
        n, p = wide_args(sql)
        assert n == 0, [(nm, str(d.dtype)) for b in p.scans.values()
                        for nm, d in zip(b.names, b.data)]
    assert eng.metrics.snapshot()["exec.scan.wide_args"] \
        == SCAN_WIDE_ARGS.value()
    eng.execute("CREATE TABLE w (k INT8 NOT NULL PRIMARY KEY, "
                "big INT8, small INT8)")
    eng.execute(f"INSERT INTO w (k, big, small) VALUES "
                f"(1, {2 ** 40}, 3), (2, 5, 4)")
    n, p = wide_args("SELECT sum(big) AS b, sum(small) AS s FROM w")
    assert n == 1
    assert str(p.scans["w"].col("big").dtype) == "int64"
