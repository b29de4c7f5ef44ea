"""Star Schema Benchmark data for the benchmark, made from one seed.

The schema of section 2 of the paper (O'Neil, O'Neil, Chen, Revilak,
"Star Schema Benchmark", revision 3, June 2009) in full: `lineorder`
(17 columns), `customer` (8), `supplier` (7), `part` (9), `date` (17),
at the paper's cardinalities (6,000,000 x SF lineorder rows, 30,000 x
SF customers, 2,000 x SF suppliers, 200,000 x (1 + log2 SF) parts,
seven years of days) and value domains. Synthetic, not the paper's
`dbgen`; independent of cockroach_tpu/workload/ssb.py, with which it
shares the schema and the domains and not the data. Money is integer,
as the paper has it. Every column leaves here in the form the store
keeps it in: an integer column as int64, a string as int32 codes into a
dictionary that is returned beside the columns. A low-cardinality
string's dictionary is its whole domain in the paper's order (25
nations, 250 cities, 1,000 brands), whether or not a small scale factor
draws every value: the group domains of the thirteen queries are the
paper's at any size. The values of a column are made in bulk with
numpy, and the integer references (statements/ssb_*.py) read the very
arrays the engine ingests.

Imports nothing of the program. Found by the name a configuration
gives under `generator`; offers DDL, TABLE_ORDER and generate().

One refusal, as generators/tpch_bulk.py has one: Q2.2 asks for `p_brand1
between 'MFGR#2221' and 'MFGR#2228'`, an order predicate over a
dictionary-coded string, and a program whose binder has no
`Binder.bind_string_between` (every one before PR 33) answers it with an
error, after it has compiled Q1.1 to Q2.1 cold, minutes into set-up.
Such a program cannot run the configuration, and says so in its first
seconds with an exit code other than 0. The program keeps that name
while a configuration names this generator. The reference worker
imports no part of the program, so nothing is asked there.
"""

from __future__ import annotations

import datetime
import math
import sys

import numpy as np

from generators.tpch import COLORS, _v_string

LINEORDER_PER_SF = 6_000_000
CUSTOMER_PER_SF = 30_000
SUPPLIER_PER_SF = 2_000
PART_BASE = 200_000

# the paper's section 2, every column at its declared type; the engine
# reads fixed and variable text as STRING, a 1-bit flag as INT8 0 / 1
DDL = {
    "lineorder": """
CREATE TABLE lineorder (
    lo_orderkey      INT8 NOT NULL,
    lo_linenumber    INT8 NOT NULL,
    lo_custkey       INT8 NOT NULL,
    lo_partkey       INT8 NOT NULL,
    lo_suppkey       INT8 NOT NULL,
    lo_orderdate     INT8 NOT NULL,
    lo_orderpriority CHAR(15) NOT NULL,
    lo_shippriority  CHAR(1) NOT NULL,
    lo_quantity      INT8 NOT NULL,
    lo_extendedprice INT8 NOT NULL,
    lo_ordtotalprice INT8 NOT NULL,
    lo_discount      INT8 NOT NULL,
    lo_revenue       INT8 NOT NULL,
    lo_supplycost    INT8 NOT NULL,
    lo_tax           INT8 NOT NULL,
    lo_commitdate    INT8 NOT NULL,
    lo_shipmode      CHAR(10) NOT NULL
)""",
    "customer": """
CREATE TABLE customer (
    c_custkey    INT8 NOT NULL PRIMARY KEY,
    c_name       VARCHAR(25) NOT NULL,
    c_address    VARCHAR(25) NOT NULL,
    c_city       CHAR(10) NOT NULL,
    c_nation     CHAR(15) NOT NULL,
    c_region     CHAR(12) NOT NULL,
    c_phone      CHAR(15) NOT NULL,
    c_mktsegment CHAR(10) NOT NULL
)""",
    "supplier": """
CREATE TABLE supplier (
    s_suppkey INT8 NOT NULL PRIMARY KEY,
    s_name    CHAR(25) NOT NULL,
    s_address VARCHAR(25) NOT NULL,
    s_city    CHAR(10) NOT NULL,
    s_nation  CHAR(15) NOT NULL,
    s_region  CHAR(12) NOT NULL,
    s_phone   CHAR(15) NOT NULL
)""",
    "part": """
CREATE TABLE part (
    p_partkey   INT8 NOT NULL PRIMARY KEY,
    p_name      VARCHAR(22) NOT NULL,
    p_mfgr      CHAR(6) NOT NULL,
    p_category  CHAR(7) NOT NULL,
    p_brand1    CHAR(9) NOT NULL,
    p_color     VARCHAR(11) NOT NULL,
    p_type      VARCHAR(25) NOT NULL,
    p_size      INT8 NOT NULL,
    p_container CHAR(10) NOT NULL
)""",
    "date": """
CREATE TABLE date (
    d_datekey          INT8 NOT NULL PRIMARY KEY,
    d_date             CHAR(18) NOT NULL,
    d_dayofweek        CHAR(9) NOT NULL,
    d_month            CHAR(9) NOT NULL,
    d_year             INT8 NOT NULL,
    d_yearmonthnum     INT8 NOT NULL,
    d_yearmonth        CHAR(7) NOT NULL,
    d_daynuminweek     INT8 NOT NULL,
    d_daynuminmonth    INT8 NOT NULL,
    d_daynuminyear     INT8 NOT NULL,
    d_monthnuminyear   INT8 NOT NULL,
    d_weeknuminyear    INT8 NOT NULL,
    d_sellingseason    VARCHAR(12) NOT NULL,
    d_lastdayinweekfl  INT8 NOT NULL,
    d_lastdayinmonthfl INT8 NOT NULL,
    d_holidayfl        INT8 NOT NULL,
    d_weekdayfl        INT8 NOT NULL
)""",
}

TABLE_ORDER = ("lineorder", "customer", "supplier", "part", "date")

# TPC-H's 25 nations, each with its region (spec 4.2.3)
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATION_REGION = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
NATIONS = [n for n, _ in NATION_REGION]
REGION_OF_NATION = np.array([r for _, r in NATION_REGION])
# a city: its nation's first nine characters, padded, plus a digit
CITIES = [f"{n[:9]:<9}{d}" for n in NATIONS for d in range(10)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
BRANDS = [f"{c}{b}" for c in CATEGORIES for b in range(1, 41)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
            "HOUSEHOLD"]
ORDER_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECI",
                    "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
PART_TYPES = [f"{a} {b} {c}"
              for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                        "PROMO")
              for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                        "BRUSHED")
              for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
SEASONS = ["Christmas", "Winter", "Spring", "Summer", "Fall"]
SEASON_OF_MONTH = [1, 1, 2, 2, 2, 3, 3, 3, 4, 4, 4, 0]  # January first
FIRST_DAY = datetime.date(1992, 1, 1)
LAST_DAY = datetime.date(1998, 12, 31)
LAST_ORDER_DAY = datetime.date(1998, 8, 2)  # TPC-H's last o_orderdate


def n_rows(table: str, sf: float) -> int:
    """Section 2's cardinalities. Below SF1 (the CPU rehearsal) the
    dimensions shrink with the fact table, down to a floor."""
    if table == "lineorder":
        return int(LINEORDER_PER_SF * sf)
    if table == "customer":
        return max(int(CUSTOMER_PER_SF * sf), 300)
    if table == "supplier":
        return max(int(SUPPLIER_PER_SF * sf), 100)
    if table == "part":
        if sf >= 1:
            return PART_BASE * int(1 + math.floor(math.log2(sf)))
        return max(int(PART_BASE * sf), 2000)
    if table == "date":
        return (LAST_DAY - FIRST_DAY).days + 1
    raise KeyError(table)


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), 0x55b,
                                  TABLE_ORDER.index(table)])


def part_price(partkey: np.ndarray) -> np.ndarray:
    """A part's retail price, integer money (TPC-H 4.2.3's formula)."""
    return 90000 + (partkey % 200001) // 10 + 100 * (partkey % 1000)


def _unique_coded(strings: list) -> tuple:
    values, codes = np.unique(np.array(strings), return_inverse=True)
    return codes.ravel().astype(np.int32), values.tolist()


def _geography(rng, n: int, p: str) -> tuple:
    """city, nation, region and phone of n customers or suppliers."""
    nation = rng.integers(0, 25, size=n)
    local = rng.integers(100, 1000, size=(n, 2))
    line = rng.integers(1000, 10000, size=n)
    phone, phones = _unique_coded(
        [f"{c}-{a}-{b}-{d}" for c, (a, b), d in
         zip((nation + 10).tolist(), local.tolist(), line.tolist())])
    cols = {p + "city": (nation * 10 + rng.integers(0, 10, size=n)
                         ).astype(np.int32),
            p + "nation": nation.astype(np.int32),
            p + "region": REGION_OF_NATION[nation].astype(np.int32),
            p + "phone": phone}
    dicts = {p + "city": CITIES, p + "nation": NATIONS,
             p + "region": REGIONS, p + "phone": phones}
    return cols, dicts


def _column_order(table: str) -> list:
    return [ln.split()[0] for ln in DDL[table].splitlines()
            if ln.startswith("    ")]


def _customer(sf: float, seed: int):
    n = n_rows("customer", sf)
    rng = _rng(seed, "customer")
    key = np.arange(1, n + 1, dtype=np.int64)
    cols, dicts = _geography(rng, n, "c_")
    cols["c_custkey"] = key
    cols["c_name"] = np.arange(n, dtype=np.int32)
    dicts["c_name"] = [f"Customer#{k:09d}" for k in key.tolist()]
    cols["c_address"], dicts["c_address"] = _v_string(rng, n, 10, 25)
    cols["c_mktsegment"] = rng.integers(0, len(SEGMENTS), size=n,
                                        dtype=np.int32)
    dicts["c_mktsegment"] = SEGMENTS
    return {c: cols[c] for c in _column_order("customer")}, dicts


def _supplier(sf: float, seed: int):
    n = n_rows("supplier", sf)
    rng = _rng(seed, "supplier")
    key = np.arange(1, n + 1, dtype=np.int64)
    cols, dicts = _geography(rng, n, "s_")
    cols["s_suppkey"] = key
    cols["s_name"] = np.arange(n, dtype=np.int32)
    dicts["s_name"] = [f"Supplier#{k:09d}" for k in key.tolist()]
    cols["s_address"], dicts["s_address"] = _v_string(rng, n, 10, 25)
    return {c: cols[c] for c in _column_order("supplier")}, dicts


def _part(sf: float, seed: int):
    n = n_rows("part", sf)
    rng = _rng(seed, "part")
    brand = rng.integers(0, len(BRANDS), size=n, dtype=np.int32)
    colors = rng.integers(0, len(COLORS), size=(n, 2), dtype=np.int32)
    cols = {
        "p_partkey": np.arange(1, n + 1, dtype=np.int64),
        # two colours a name, every pair in the dictionary
        "p_name": colors[:, 0] * len(COLORS) + colors[:, 1],
        "p_mfgr": brand // 200,
        "p_category": brand // 40,
        "p_brand1": brand,
        "p_color": colors[:, 0].copy(),
        "p_type": rng.integers(0, len(PART_TYPES), size=n,
                               dtype=np.int32),
        "p_size": rng.integers(1, 51, size=n, dtype=np.int64),
        "p_container": rng.integers(0, len(CONTAINERS), size=n,
                                    dtype=np.int32),
    }
    dicts = {"p_name": [f"{a} {b}" for a in COLORS for b in COLORS],
             "p_mfgr": MFGRS, "p_category": CATEGORIES,
             "p_brand1": BRANDS, "p_color": COLORS,
             "p_type": PART_TYPES, "p_container": CONTAINERS}
    return cols, dicts


def _days() -> list:
    return [FIRST_DAY + datetime.timedelta(days=i)
            for i in range(n_rows("date", 1))]


def datekeys() -> np.ndarray:
    return np.array([d.year * 10000 + d.month * 100 + d.day
                     for d in _days()], dtype=np.int64)


def _date(sf: float, seed: int):
    days = _days()

    def ints(f):
        return np.array([f(d) for d in days], dtype=np.int64)

    def coded(f, values=None):
        strings = [f(d) for d in days]
        if values is None:
            values = list(dict.fromkeys(strings))  # in calendar order
        at = {v: i for i, v in enumerate(values)}
        return np.array([at[s] for s in strings], dtype=np.int32), values

    cols, dicts = {}, {}
    cols["d_datekey"] = datekeys()
    cols["d_date"], dicts["d_date"] = coded(
        lambda d: f"{d:%B} {d.day}, {d.year}")
    cols["d_dayofweek"], dicts["d_dayofweek"] = coded(
        lambda d: f"{d:%A}")
    cols["d_month"], dicts["d_month"] = coded(lambda d: f"{d:%B}")
    cols["d_year"] = ints(lambda d: d.year)
    cols["d_yearmonthnum"] = ints(lambda d: d.year * 100 + d.month)
    cols["d_yearmonth"], dicts["d_yearmonth"] = coded(
        lambda d: f"{d:%b}{d.year}")
    # Sunday is the first day of the week
    cols["d_daynuminweek"] = ints(lambda d: d.isoweekday() % 7 + 1)
    cols["d_daynuminmonth"] = ints(lambda d: d.day)
    cols["d_daynuminyear"] = ints(lambda d: d.timetuple().tm_yday)
    cols["d_monthnuminyear"] = ints(lambda d: d.month)
    cols["d_weeknuminyear"] = ints(
        lambda d: (d.timetuple().tm_yday - 1) // 7 + 1)
    cols["d_sellingseason"], dicts["d_sellingseason"] = coded(
        lambda d: SEASONS[SEASON_OF_MONTH[d.month - 1]], SEASONS)
    cols["d_lastdayinweekfl"] = ints(lambda d: int(d.isoweekday() == 6))
    cols["d_lastdayinmonthfl"] = ints(
        lambda d: int((d + datetime.timedelta(days=1)).month != d.month))
    cols["d_holidayfl"] = ints(
        lambda d: int((d.month, d.day) in ((1, 1), (7, 4), (12, 25))))
    cols["d_weekdayfl"] = ints(lambda d: int(d.isoweekday() <= 5))
    return {c: cols[c] for c in _column_order("date")}, dicts


def _lineorder(sf: float, seed: int):
    """Orders of one to seven lines; what belongs to the order (its
    customer, date, priority and total) repeats on each of its lines."""
    n = n_rows("lineorder", sf)
    rng = _rng(seed, "lineorder")
    lines = rng.integers(1, 8, size=n // 4 + 64)
    while int(lines.sum()) < n:
        lines = np.concatenate(
            [lines, rng.integers(1, 8, size=n // 16 + 64)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    first = np.r_[0, np.cumsum(lines)[:-1]]
    norders = int(order[-1]) + 1
    starts = first[:norders]
    at = np.arange(norders, dtype=np.int64)
    # TPC-H's sparse order keys: the first 8 of every 32
    okey = (at // 8) * 32 + at % 8 + 1
    keys = datekeys()
    oday = rng.integers(0, (LAST_ORDER_DAY - FIRST_DAY).days + 1,
                        size=norders)
    partkey = rng.integers(1, n_rows("part", sf) + 1, size=n,
                           dtype=np.int64)
    price = part_price(partkey)
    quantity = rng.integers(1, 51, size=n, dtype=np.int64)
    eprice = quantity * price
    discount = rng.integers(0, 11, size=n, dtype=np.int64)
    tax = rng.integers(0, 9, size=n, dtype=np.int64)
    revenue = eprice * (100 - discount) // 100
    total = np.add.reduceat(revenue * (100 + tax) // 100, starts)
    cols = {
        "lo_orderkey": okey[order],
        "lo_linenumber": np.arange(n, dtype=np.int64) - first[order] + 1,
        "lo_custkey": rng.integers(1, n_rows("customer", sf) + 1,
                                   size=norders, dtype=np.int64)[order],
        "lo_partkey": partkey,
        "lo_suppkey": rng.integers(1, n_rows("supplier", sf) + 1, size=n,
                                   dtype=np.int64),
        "lo_orderdate": keys[oday][order],
        "lo_orderpriority": rng.integers(
            0, len(ORDER_PRIORITIES), size=norders,
            dtype=np.int32)[order],
        "lo_shippriority": np.zeros(n, dtype=np.int32),
        "lo_quantity": quantity,
        "lo_extendedprice": eprice,
        "lo_ordtotalprice": total[order],
        "lo_discount": discount,
        "lo_revenue": revenue,
        # the part's cost to the supplier, six tenths of its price
        "lo_supplycost": 6 * price // 10,
        "lo_tax": tax,
        "lo_commitdate": keys[oday[order]
                              + rng.integers(30, 91, size=n)],
        "lo_shipmode": rng.integers(0, len(SHIPMODES), size=n,
                                    dtype=np.int32),
    }
    dicts = {"lo_orderpriority": ORDER_PRIORITIES,
             "lo_shippriority": ["0"], "lo_shipmode": SHIPMODES}
    return cols, dicts


_MAKERS = {"lineorder": _lineorder, "customer": _customer,
           "supplier": _supplier, "part": _part, "date": _date}


BINDER = "cockroach_tpu.sql.binder"


def require_string_between() -> None:
    binder = sys.modules.get(BINDER)
    if binder is not None and not hasattr(
            getattr(binder, "Binder", None), "bind_string_between"):
        raise SystemExit(
            "generators/ssb.py: this program's binder cannot bind BETWEEN "
            f"over a string column ({BINDER}.Binder.bind_string_between): "
            "it answers SSB Q2.2 with an error, after compiling the four "
            "statements before it. Refusing to start it.")


def generate(table: str, sf: float, seed: int):
    """(columns, dictionaries) of one table: numpy arrays in stored form
    and, for each STRING column, the list its int32 codes index."""
    require_string_between()
    return _MAKERS[table](sf, seed)
