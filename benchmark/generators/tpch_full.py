"""All eight TPC-H tables for the benchmark, made from one seed.

`lineitem`, `part`, `orders` and `customer` are generators/tpch.py's,
array for array: this module hands them on. It adds the other four of
the schema (spec 1.4.1), every column at the spec's width: `partsupp`
(5 columns, 4 rows a part), `supplier` (7), `nation` (4, the spec's 25
rows) and `region` (3, its 5 rows), with the domains of spec 4.2.3:
`ps_availqty` 1..9,999, `ps_supplycost` 1.00..1,000.00, `s_acctbal`
-999.99..9,999.99, comments of the spec's lengths cut from the run's
text pool, phones whose country code is the nation key plus 10
(4.2.2.9), and in `s_comment` the spec's share of rows that hold
"Customer ... Complaints" and "Customer ... Recommends" (5 x SF each,
at least one). The suppliers of a part follow the rule tpch.py's
lineitem draws `l_suppkey` by, `(partkey + 7 i) mod S + 1` for i in
0..3, so every `(l_partkey, l_suppkey)` of lineitem is a row of
partsupp, as the spec guarantees. Every column leaves here in the form
the store keeps it in (DECIMAL as int64 hundredths, a string as int32
codes into a dictionary returned beside the columns), and the integer
references (statements/nested_*.py) read the very arrays the engine
ingests.

Imports nothing of the program. Found by the name a configuration
gives under `generator`; offers DDL, TABLE_ORDER and generate().

One refusal, as generators/ssb.py and tpch_bulk.py have one. Q22's
`c_acctbal > (select avg(c_acctbal) ...)` is an uncorrelated scalar
subquery. A program that runs such a subquery while it prepares the
statement and writes the value it finds into the plan as a constant
(every one before PR 36) builds another compiled program for every
seed's data, and materializes every derived table through the host on
every execution: minutes of XLA:TPU compile in every run of every
check, and uploads and compiles inside the window. Such a program
cannot run the configuration inside the check's time, and says so in
its first seconds with an exit code other than 0. The mark of a
program that hands a subquery's result to the compiled program as an
argument is `cockroach_tpu.exec.planparam.SubqueryArg`; the program
keeps that name while a configuration names this generator. The
reference worker imports no part of the program, so nothing is asked
there.
"""

from __future__ import annotations

import sys

import numpy as np

from generators import tpch
from generators.tpch import (_BLOCK, _KEEP, SUPP_PER_SF, _text, _text_pool,
                             _v_string)

NATION_REGION = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
NATIONS = [n for n, _ in NATION_REGION]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SUPPLIERS_PER_PART = 4

DDL = dict(tpch.DDL)
DDL.update({
    "partsupp": """
CREATE TABLE partsupp (
    ps_partkey    INT8 NOT NULL,
    ps_suppkey    INT8 NOT NULL,
    ps_availqty   INT8 NOT NULL,
    ps_supplycost DECIMAL(15,2) NOT NULL,
    ps_comment    VARCHAR(199) NOT NULL
)""",
    "supplier": """
CREATE TABLE supplier (
    s_suppkey   INT8 NOT NULL,
    s_name      CHAR(25) NOT NULL,
    s_address   VARCHAR(40) NOT NULL,
    s_nationkey INT8 NOT NULL,
    s_phone     CHAR(15) NOT NULL,
    s_acctbal   DECIMAL(15,2) NOT NULL,
    s_comment   VARCHAR(101) NOT NULL
)""",
    "nation": """
CREATE TABLE nation (
    n_nationkey INT8 NOT NULL,
    n_name      CHAR(25) NOT NULL,
    n_regionkey INT8 NOT NULL,
    n_comment   VARCHAR(152) NOT NULL
)""",
    "region": """
CREATE TABLE region (
    r_regionkey INT8 NOT NULL,
    r_name      CHAR(25) NOT NULL,
    r_comment   VARCHAR(152) NOT NULL
)""",
})

TABLE_ORDER = tpch.TABLE_ORDER + ("partsupp", "supplier", "nation",
                                  "region")

PLANPARAM = "cockroach_tpu.exec.planparam"


def require_runtime_subqueries() -> None:
    planparam = sys.modules.get(PLANPARAM)
    if planparam is not None and not hasattr(planparam, "SubqueryArg"):
        raise SystemExit(
            "generators/tpch_full.py: this program writes the result of "
            "an uncorrelated subquery into the plan as constants (no "
            f"{PLANPARAM}.SubqueryArg), so Q22 is another compiled "
            "program for every seed's data, every derived table goes "
            "through the host on every execution, and the set-up "
            "would run past the check's time limit. Refusing to "
            "start it.")


def n_suppliers(sf: float) -> int:
    """The S of tpch.py's lineitem, which draws l_suppkey in 1..S."""
    return max(int(SUPP_PER_SF * max(sf, 0.01)), 100)


def n_rows(table: str, sf: float) -> int:
    if table == "partsupp":
        return SUPPLIERS_PER_PART * tpch.n_rows("part", sf)
    if table == "supplier":
        return n_suppliers(sf)
    if table in ("nation", "region"):
        return len(NATIONS if table == "nation" else REGIONS)
    return tpch.n_rows(table, sf)


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), TABLE_ORDER.index(table)])


def _column_order(table: str) -> list:
    return [ln.split()[0] for ln in DDL[table].splitlines()
            if ln.startswith("    ")]


def part_suppliers(partkey: np.ndarray, i, sf: float) -> np.ndarray:
    """The i-th (0..3) supplier of a part: the rule tpch.py's lineitem
    draws l_suppkey by."""
    return (partkey + i * 7) % n_suppliers(sf) + 1


# tpch.py's hash of a string has multipliers for 16 words, 128 bytes;
# ps_comment is up to 198
_HASH_MULT = np.random.default_rng(0x7ca).integers(
    0, 1 << 63, size=32, dtype=np.uint64) * np.uint64(2) + np.uint64(1)


def _long_text(rng, n: int, lo: int, hi: int, pool: np.ndarray) -> tuple:
    """tpch.py's `_text` for strings past 128 bytes: the same cut of
    the pool, equal strings found by a hash over up to 32 words."""
    word = np.arange(-(-hi // 8))[:, None]
    start = rng.integers(0, len(pool) - len(word), size=n, dtype=np.int32)
    length = rng.integers(lo, hi + 1, size=n, dtype=np.int16)

    def words_of(rows):
        return (pool[start[rows] + word]
                & _KEEP[np.clip(length[rows] - 8 * word, 0, 8)])

    h = np.empty(n, dtype=np.uint64)
    for b in range(0, n, _BLOCK):
        words = words_of(slice(b, b + _BLOCK))
        h[b:b + _BLOCK] = (words * _HASH_MULT[:len(words), None]).sum(
            axis=0, dtype=np.uint64)
    _, first, codes = np.unique(h, return_index=True, return_inverse=True)
    values: list = []
    for b in range(0, len(first), _BLOCK):
        words = words_of(first[b:b + _BLOCK])
        raw = np.ascontiguousarray(words.T).view(f"S{8 * len(words)}")
        values.extend(map(bytes.decode, raw.ravel().tolist()))
    return codes.astype(np.int32), values


def _partsupp(sf: float, seed: int):
    nparts = tpch.n_rows("part", sf)
    n = SUPPLIERS_PER_PART * nparts
    rng = _rng(seed, "partsupp")
    partkey = np.repeat(np.arange(1, nparts + 1, dtype=np.int64),
                        SUPPLIERS_PER_PART)
    i = np.tile(np.arange(SUPPLIERS_PER_PART, dtype=np.int64), nparts)
    cols = {
        "ps_partkey": partkey,
        "ps_suppkey": part_suppliers(partkey, i, sf),
        "ps_availqty": rng.integers(1, 10000, size=n, dtype=np.int64),
        "ps_supplycost": rng.integers(100, 100001, size=n,
                                      dtype=np.int64),
    }
    dicts = {}
    cols["ps_comment"], dicts["ps_comment"] = _long_text(
        rng, n, 49, 198, _text_pool(seed))
    return cols, dicts


def _phones(rng, nation: np.ndarray) -> tuple:
    """spec 4.2.2.9: country code = nationkey + 10."""
    n = len(nation)
    local = rng.integers(100, 999, size=(n, 2))
    line = rng.integers(1000, 9999, size=n)
    values, codes = np.unique(
        np.array([f"{c}-{a}-{b}-{d}" for c, (a, b), d in
                  zip((nation + 10).tolist(), local.tolist(),
                      line.tolist())]), return_inverse=True)
    return codes.astype(np.int32), values.tolist()


def _supplier(sf: float, seed: int):
    n = n_suppliers(sf)
    rng = _rng(seed, "supplier")
    suppkey = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, size=n, dtype=np.int64)
    cols = {
        "s_suppkey": suppkey,
        "s_name": np.arange(n, dtype=np.int32),
        "s_nationkey": nation,
        "s_acctbal": rng.integers(-99999, 1000000, size=n,
                                  dtype=np.int64),
    }
    dicts = {"s_name": [f"Supplier#{k:09d}" for k in suppkey.tolist()]}
    cols["s_phone"], dicts["s_phone"] = _phones(rng, nation)
    cols["s_address"], dicts["s_address"] = _v_string(rng, n, 10, 40)
    codes, values = _text(rng, n, 25, 100, _text_pool(seed))
    # spec 4.2.3: 5 x SF rows hold "Customer" ... "Complaints" and as
    # many "Customer" ... "Recommends", each pair at random places of a
    # comment of the row's own length
    marked = max(int(5 * sf), 1)
    rows = rng.choice(n, size=2 * marked, replace=False)
    codes = codes.copy()
    values = list(values)
    for k, row in enumerate(rows.tolist()):
        tail = "Complaints" if k < marked else "Recommends"
        text = values[codes[row]].ljust(25)
        room = len(text) - len("Customer") - len(tail)
        a = int(rng.integers(0, room + 1))
        b = int(rng.integers(a, room + 1))
        text = (text[:a] + "Customer" + text[a + 8:b + 8] + tail
                + text[b + 18:])
        codes[row] = len(values)
        values.append(text)
    cols["s_comment"], dicts["s_comment"] = codes, values
    return {c: cols[c] for c in _column_order("supplier")}, dicts


def _nation(sf: float, seed: int):
    rng = _rng(seed, "nation")
    cols = {
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": np.arange(len(NATIONS), dtype=np.int32),
        "n_regionkey": np.array([r for _, r in NATION_REGION],
                                dtype=np.int64),
    }
    dicts = {"n_name": list(NATIONS)}
    cols["n_comment"], dicts["n_comment"] = _text(
        rng, len(NATIONS), 31, 114, _text_pool(seed))
    return cols, dicts


def _region(sf: float, seed: int):
    rng = _rng(seed, "region")
    cols = {
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
        "r_name": np.arange(len(REGIONS), dtype=np.int32),
    }
    dicts = {"r_name": list(REGIONS)}
    cols["r_comment"], dicts["r_comment"] = _text(
        rng, len(REGIONS), 31, 115, _text_pool(seed))
    return cols, dicts


_MAKERS = {"partsupp": _partsupp, "supplier": _supplier,
           "nation": _nation, "region": _region}


def generate(table: str, sf: float, seed: int):
    """(columns, dictionaries) of one table: numpy arrays in stored form
    and, for each STRING column, the list its int32 codes index."""
    require_runtime_subqueries()
    if table in _MAKERS:
        return _MAKERS[table](sf, seed)
    return tpch.generate(table, sf, seed)
