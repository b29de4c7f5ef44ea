"""SSB — the Star Schema Benchmark (O'Neil, O'Neil, Chen, Revilak,
revision 3, June 2009), named in BASELINE.md's bench ladder.

Section 2's schema in full: the `lineorder` fact table (17 columns)
and the `customer` (8), `supplier` (7), `part` (9) and `date` (17)
dimensions, money as integers as the paper has it. Section 3's thirteen
queries as the paper prints them (SSB has no substitution parameters):
flight 1 (a restricted scan and one join), flight 2 (brand roll-up),
flight 3 (customer / supplier geography), flight 4 (profit).

Mirrors the reference's workload-generator shape
(pkg/workload/tpch/tpch.go style): seeded numpy columns with the
paper's value domains (synthetic, not `dbgen`), DDL, query texts, and a
plain numpy integer oracle `ref_q*` for each query.
"""

from __future__ import annotations

import datetime

import numpy as np

LINEORDER_PER_SF = 6_000_000
CUSTOMER_PER_SF = 30_000
SUPPLIER_PER_SF = 2_000
PART_BASE = 200_000  # x (1 + log2 SF)

DDL = {
    "date": """
CREATE TABLE date (
    d_datekey          INT8 NOT NULL PRIMARY KEY,
    d_date             STRING NOT NULL,
    d_dayofweek        STRING NOT NULL,
    d_month            STRING NOT NULL,
    d_year             INT8 NOT NULL,
    d_yearmonthnum     INT8 NOT NULL,
    d_yearmonth        STRING NOT NULL,
    d_daynuminweek     INT8 NOT NULL,
    d_daynuminmonth    INT8 NOT NULL,
    d_daynuminyear     INT8 NOT NULL,
    d_monthnuminyear   INT8 NOT NULL,
    d_weeknuminyear    INT8 NOT NULL,
    d_sellingseason    STRING NOT NULL,
    d_lastdayinweekfl  INT8 NOT NULL,
    d_lastdayinmonthfl INT8 NOT NULL,
    d_holidayfl        INT8 NOT NULL,
    d_weekdayfl        INT8 NOT NULL
)""",
    "supplier": """
CREATE TABLE supplier (
    s_suppkey INT8 NOT NULL PRIMARY KEY,
    s_name    STRING NOT NULL,
    s_address STRING NOT NULL,
    s_city    STRING NOT NULL,
    s_nation  STRING NOT NULL,
    s_region  STRING NOT NULL,
    s_phone   STRING NOT NULL
)""",
    "part": """
CREATE TABLE part (
    p_partkey   INT8 NOT NULL PRIMARY KEY,
    p_name      STRING NOT NULL,
    p_mfgr      STRING NOT NULL,
    p_category  STRING NOT NULL,
    p_brand1    STRING NOT NULL,
    p_color     STRING NOT NULL,
    p_type      STRING NOT NULL,
    p_size      INT8 NOT NULL,
    p_container STRING NOT NULL
)""",
    "customer": """
CREATE TABLE customer (
    c_custkey    INT8 NOT NULL PRIMARY KEY,
    c_name       STRING NOT NULL,
    c_address    STRING NOT NULL,
    c_city       STRING NOT NULL,
    c_nation     STRING NOT NULL,
    c_region     STRING NOT NULL,
    c_phone      STRING NOT NULL,
    c_mktsegment STRING NOT NULL
)""",
    "lineorder": """
CREATE TABLE lineorder (
    lo_orderkey      INT8 NOT NULL,
    lo_linenumber    INT8 NOT NULL,
    lo_custkey       INT8 NOT NULL,
    lo_partkey       INT8 NOT NULL,
    lo_suppkey       INT8 NOT NULL,
    lo_orderdate     INT8 NOT NULL,
    lo_orderpriority STRING NOT NULL,
    lo_shippriority  STRING NOT NULL,
    lo_quantity      INT8 NOT NULL,
    lo_extendedprice INT8 NOT NULL,
    lo_ordtotalprice INT8 NOT NULL,
    lo_discount      INT8 NOT NULL,
    lo_revenue       INT8 NOT NULL,
    lo_supplycost    INT8 NOT NULL,
    lo_tax           INT8 NOT NULL,
    lo_commitdate    INT8 NOT NULL,
    lo_shipmode      STRING NOT NULL
)""",
}

# TPC-H's 25 nations and the region of each (spec 4.2.3)
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
# a city is its nation's first nine characters, padded, and a digit
CITIES = [f"{n[:9]:<9}{d}" for n in NATIONS for d in range(10)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
BRANDS = [f"{c}{b}" for c in CATEGORIES for b in range(1, 41)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
            "HOUSEHOLD"]
ORDER_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECI",
                    "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
COLORS = """almond antique aquamarine azure beige bisque black blanched
blue blush brown burlywood burnished chartreuse chiffon chocolate coral
cornflower cornsilk cream cyan dark deep dim dodger drab firebrick
floral forest frosted gainsboro ghost goldenrod green grey honeydew hot
indian ivory khaki lace lavender lawn lemon light lime linen magenta
maroon medium metallic midnight mint misty moccasin navajo navy olive
orange orchid pale papaya peach peru pink plum powder puff purple red
rose rosy royal saddle salmon sandy seashell sienna sky slate smoke snow
spring steel tan thistle tomato turquoise violet wheat white
yellow""".split()
PART_TYPES = [f"{a} {b} {c}"
              for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                        "PROMO")
              for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                        "BRUSHED")
              for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
SEASONS = {12: "Christmas", 1: "Winter", 2: "Winter", 3: "Spring",
           4: "Spring", 5: "Spring", 6: "Summer", 7: "Summer",
           8: "Summer", 9: "Fall", 10: "Fall", 11: "Fall"}
FIRST_DAY = datetime.date(1992, 1, 1)
LAST_DAY = datetime.date(1998, 12, 31)
LAST_ORDER_DAY = datetime.date(1998, 8, 2)  # TPC-H's last o_orderdate
ADDRESS_CHARS = np.array(list(
    "0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ ,"))


def n_rows(table: str, sf: float) -> int:
    """Section 2's cardinalities; below SF1 the dimensions shrink with
    the fact table (the paper defines none there) down to a floor that
    keeps every domain populated."""
    if table == "lineorder":
        return int(LINEORDER_PER_SF * sf)
    if table == "customer":
        return max(int(CUSTOMER_PER_SF * sf), 300)
    if table == "supplier":
        return max(int(SUPPLIER_PER_SF * sf), 100)
    if table == "part":
        if sf >= 1:
            return PART_BASE * int(1 + np.floor(np.log2(sf)))
        return max(int(PART_BASE * sf), 2_000)
    raise KeyError(table)


def _obj(values, codes) -> np.ndarray:
    return np.array(values, dtype=object)[codes]


def _addresses(rng, n: int) -> np.ndarray:
    chars = ADDRESS_CHARS[rng.integers(0, len(ADDRESS_CHARS),
                                       size=(n, 25))]
    lens = rng.integers(10, 26, size=n)
    return np.array(["".join(row[:k]) for row, k in zip(chars, lens)],
                    dtype=object)


def _phones(rng, nation: np.ndarray) -> np.ndarray:
    parts = rng.integers(100, 1000, size=(len(nation), 2))
    line = rng.integers(1000, 10000, size=len(nation))
    return np.array([f"{c}-{a}-{b}-{d}" for c, (a, b), d in
                     zip((nation + 10).tolist(), parts.tolist(),
                         line.tolist())], dtype=object)


def part_price(partkey: np.ndarray) -> np.ndarray:
    """A part's retail price, integer money (TPC-H 4.2.3's formula)."""
    return 90000 + (partkey % 200001) // 10 + 100 * (partkey % 1000)


def gen_date() -> dict:
    """The seven-year date dimension, one row a day, 1992 through 1998
    (datekey yyyymmdd)."""
    days = [FIRST_DAY + datetime.timedelta(days=i)
            for i in range((LAST_DAY - FIRST_DAY).days + 1)]

    def ints(f):
        return np.array([f(d) for d in days], dtype=np.int64)

    def strs(f):
        return np.array([f(d) for d in days], dtype=object)

    def month_end(d):
        return (d + datetime.timedelta(days=1)).month != d.month

    return {
        "d_datekey": ints(lambda d: d.year * 10000 + d.month * 100 + d.day),
        "d_date": strs(lambda d: f"{d:%B} {d.day}, {d.year}"),
        "d_dayofweek": strs(lambda d: f"{d:%A}"),
        "d_month": strs(lambda d: f"{d:%B}"),
        "d_year": ints(lambda d: d.year),
        "d_yearmonthnum": ints(lambda d: d.year * 100 + d.month),
        "d_yearmonth": strs(lambda d: f"{d:%b}{d.year}"),
        # Sunday is day 1 of the week
        "d_daynuminweek": ints(lambda d: d.isoweekday() % 7 + 1),
        "d_daynuminmonth": ints(lambda d: d.day),
        "d_daynuminyear": ints(lambda d: d.timetuple().tm_yday),
        "d_monthnuminyear": ints(lambda d: d.month),
        "d_weeknuminyear": ints(
            lambda d: (d.timetuple().tm_yday - 1) // 7 + 1),
        "d_sellingseason": strs(lambda d: SEASONS[d.month]),
        "d_lastdayinweekfl": ints(lambda d: int(d.isoweekday() == 6)),
        "d_lastdayinmonthfl": ints(lambda d: int(month_end(d))),
        "d_holidayfl": ints(lambda d: int((d.month, d.day) in
                                          ((1, 1), (7, 4), (12, 25)))),
        "d_weekdayfl": ints(lambda d: int(d.isoweekday() <= 5)),
    }


def _geography(rng, n: int, prefix: str) -> dict:
    nation = rng.integers(0, 25, size=n)
    city = nation * 10 + rng.integers(0, 10, size=n)
    region = np.array([r for _, r in NATION_REGION])[nation]
    return {f"{prefix}_city": _obj(CITIES, city),
            f"{prefix}_nation": _obj(NATIONS, nation),
            f"{prefix}_region": _obj(REGIONS, region),
            f"{prefix}_phone": _phones(rng, nation)}


def gen_dims(sf: float, seed: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    ns = n_rows("supplier", sf)
    skey = np.arange(1, ns + 1, dtype=np.int64)
    geo = _geography(rng, ns, "s")
    supplier = {
        "s_suppkey": skey,
        "s_name": np.array([f"Supplier#{k:09d}" for k in skey.tolist()],
                           dtype=object),
        "s_address": _addresses(rng, ns),
        "s_city": geo["s_city"], "s_nation": geo["s_nation"],
        "s_region": geo["s_region"], "s_phone": geo["s_phone"],
    }
    npart = n_rows("part", sf)
    pkey = np.arange(1, npart + 1, dtype=np.int64)
    brand = rng.integers(0, len(BRANDS), size=npart)
    colors = rng.integers(0, len(COLORS), size=(npart, 2))
    part = {
        "p_partkey": pkey,
        "p_name": np.array([f"{COLORS[a]} {COLORS[b]}"
                            for a, b in colors.tolist()], dtype=object),
        "p_mfgr": _obj(MFGRS, brand // 200),
        "p_category": _obj(CATEGORIES, brand // 40),
        "p_brand1": _obj(BRANDS, brand),
        "p_color": _obj(COLORS, colors[:, 0]),
        "p_type": _obj(PART_TYPES,
                       rng.integers(0, len(PART_TYPES), size=npart)),
        "p_size": rng.integers(1, 51, size=npart).astype(np.int64),
        "p_container": _obj(CONTAINERS,
                            rng.integers(0, len(CONTAINERS), size=npart)),
    }
    nc = n_rows("customer", sf)
    ckey = np.arange(1, nc + 1, dtype=np.int64)
    geo = _geography(rng, nc, "c")
    customer = {
        "c_custkey": ckey,
        "c_name": np.array([f"Customer#{k:09d}" for k in ckey.tolist()],
                           dtype=object),
        "c_address": _addresses(rng, nc),
        "c_city": geo["c_city"], "c_nation": geo["c_nation"],
        "c_region": geo["c_region"], "c_phone": geo["c_phone"],
        "c_mktsegment": _obj(SEGMENTS,
                             rng.integers(0, len(SEGMENTS), size=nc)),
    }
    return {"date": gen_date(), "supplier": supplier, "part": part,
            "customer": customer}


def gen_lineorder(sf: float, dims: dict, seed: int = 0,
                  rows: int | None = None) -> dict:
    """The fact table: orders of one to seven lines; what belongs to
    the order (customer, date, priority, total) is the same on each of
    its lines."""
    n = rows if rows is not None else n_rows("lineorder", sf)
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, size=n // 4 + 8)
    while lines.sum() < n:
        lines = np.concatenate([lines, rng.integers(1, 8, size=n // 4 + 8)])
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    first = np.r_[0, np.cumsum(lines)[:-1]]
    norders = int(order[-1]) + 1 if n else 0
    # TPC-H's sparse order keys: the first 8 of every 32
    okey = (np.arange(norders) // 8) * 32 + np.arange(norders) % 8 + 1
    datekeys = dims["date"]["d_datekey"]
    n_order_days = (LAST_ORDER_DAY - FIRST_DAY).days + 1
    oday = rng.integers(0, n_order_days, size=norders)
    quantity = rng.integers(1, 51, size=n).astype(np.int64)
    partkey = rng.integers(1, len(dims["part"]["p_partkey"]) + 1,
                           size=n).astype(np.int64)
    price = part_price(partkey)
    eprice = quantity * price
    discount = rng.integers(0, 11, size=n).astype(np.int64)
    tax = rng.integers(0, 9, size=n).astype(np.int64)
    revenue = eprice * (100 - discount) // 100
    total = np.zeros(norders, dtype=np.int64)
    np.add.at(total, order, revenue * (100 + tax) // 100)
    return {
        "lo_orderkey": okey[order].astype(np.int64),
        "lo_linenumber": (np.arange(n) - first[order] + 1).astype(np.int64),
        "lo_custkey": rng.integers(
            1, len(dims["customer"]["c_custkey"]) + 1, size=norders
        ).astype(np.int64)[order],
        "lo_partkey": partkey,
        "lo_suppkey": rng.integers(
            1, len(dims["supplier"]["s_suppkey"]) + 1, size=n
        ).astype(np.int64),
        "lo_orderdate": datekeys[oday][order],
        "lo_orderpriority": _obj(
            ORDER_PRIORITIES,
            rng.integers(0, len(ORDER_PRIORITIES), size=norders))[order],
        "lo_shippriority": _obj(["0"], np.zeros(n, dtype=np.int64)),
        "lo_quantity": quantity,
        "lo_extendedprice": eprice,
        "lo_ordtotalprice": total[order],
        "lo_discount": discount,
        "lo_revenue": revenue,
        "lo_supplycost": 6 * price // 10,
        "lo_tax": tax,
        "lo_commitdate": datekeys[oday[order]
                                  + rng.integers(30, 91, size=n)],
        "lo_shipmode": _obj(SHIPMODES,
                            rng.integers(0, len(SHIPMODES), size=n)),
    }


# the whole domain of each low-cardinality string column, in the
# paper's order: seeded as the column's dictionary, so the group
# domains of the queries are the paper's (25 nations, 250 cities, 1,000
# brands, one NULL slot each) whatever a small scale factor draws
DOMAINS = {
    "customer": {"c_city": CITIES, "c_nation": NATIONS,
                 "c_region": REGIONS, "c_mktsegment": SEGMENTS},
    "supplier": {"s_city": CITIES, "s_nation": NATIONS,
                 "s_region": REGIONS},
    "part": {"p_mfgr": MFGRS, "p_category": CATEGORIES,
             "p_brand1": BRANDS, "p_color": COLORS, "p_type": PART_TYPES,
             "p_container": CONTAINERS},
    "lineorder": {"lo_orderpriority": ORDER_PRIORITIES,
                  "lo_shipmode": SHIPMODES},
}


def create_tables(engine) -> None:
    """The five tables, each low-cardinality string column's dictionary
    seeded with its whole domain."""
    for name, ddl in DDL.items():
        engine.execute(ddl)
        for col, values in DOMAINS.get(name, {}).items():
            engine.store.set_dictionary(name, col, values)


def insert(engine, dims: dict, lo: dict) -> None:
    ts = engine.clock.now()
    for name in DDL:
        engine.store.insert_columns(
            name, dims[name] if name != "lineorder" else lo, ts)


def load(engine, sf: float = 0.01, seed: int = 0,
         rows: int | None = None) -> dict:
    dims = gen_dims(sf, seed=seed + 1)
    lo = gen_lineorder(sf, dims, seed=seed, rows=rows)
    create_tables(engine)
    insert(engine, dims, lo)
    return {"dims": dims, "lineorder": lo}


# -- queries (section 3 of the paper, as printed) ----------------------------

Q1_1 = """
select sum(lo_extendedprice*lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
and d_year = 1993
and lo_discount between 1 and 3
and lo_quantity < 25
"""

Q1_2 = """
select sum(lo_extendedprice*lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
and d_yearmonthnum = 199401
and lo_discount between 4 and 6
and lo_quantity between 26 and 35
"""

Q1_3 = """
select sum(lo_extendedprice*lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
and d_weeknuminyear = 6
and d_year = 1994
and lo_discount between 5 and 7
and lo_quantity between 26 and 35
"""

Q2_1 = """
select sum(lo_revenue), d_year, p_brand1
from lineorder, date, part, supplier
where lo_orderdate = d_datekey
and lo_partkey = p_partkey
and lo_suppkey = s_suppkey
and p_category = 'MFGR#12'
and s_region = 'AMERICA'
group by d_year, p_brand1
order by d_year, p_brand1
"""

Q2_2 = """
select sum(lo_revenue), d_year, p_brand1
from lineorder, date, part, supplier
where lo_orderdate = d_datekey
and lo_partkey = p_partkey
and lo_suppkey = s_suppkey
and p_brand1 between 'MFGR#2221' and 'MFGR#2228'
and s_region = 'ASIA'
group by d_year, p_brand1
order by d_year, p_brand1
"""

Q2_3 = """
select sum(lo_revenue), d_year, p_brand1
from lineorder, date, part, supplier
where lo_orderdate = d_datekey
and lo_partkey = p_partkey
and lo_suppkey = s_suppkey
and p_brand1 = 'MFGR#2221'
and s_region = 'EUROPE'
group by d_year, p_brand1
order by d_year, p_brand1
"""

Q3_1 = """
select c_nation, s_nation, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and c_region = 'ASIA' and s_region = 'ASIA'
and d_year >= 1992 and d_year <= 1997
group by c_nation, s_nation, d_year
order by d_year asc, revenue desc
"""

Q3_2 = """
select c_city, s_city, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and c_nation = 'UNITED STATES'
and s_nation = 'UNITED STATES'
and d_year >= 1992 and d_year <= 1997
group by c_city, s_city, d_year
order by d_year asc, revenue desc
"""

Q3_3 = """
select c_city, s_city, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and (c_city='UNITED KI1' or c_city='UNITED KI5')
and (s_city='UNITED KI1' or s_city='UNITED KI5')
and d_year >= 1992 and d_year <= 1997
group by c_city, s_city, d_year
order by d_year asc, revenue desc
"""

Q3_4 = """
select c_city, s_city, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and (c_city='UNITED KI1' or c_city='UNITED KI5')
and (s_city='UNITED KI1' or s_city='UNITED KI5')
and d_yearmonth = 'Dec1997'
group by c_city, s_city, d_year
order by d_year asc, revenue desc
"""

Q4_1 = """
select d_year, c_nation, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_partkey = p_partkey
and lo_orderdate = d_datekey
and c_region = 'AMERICA'
and s_region = 'AMERICA'
and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
group by d_year, c_nation
order by d_year, c_nation
"""

Q4_2 = """
select d_year, s_nation, p_category, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_partkey = p_partkey
and lo_orderdate = d_datekey
and c_region = 'AMERICA'
and s_region = 'AMERICA'
and (d_year = 1997 or d_year = 1998)
and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
group by d_year, s_nation, p_category
order by d_year, s_nation, p_category
"""

Q4_3 = """
select d_year, s_city, p_brand1, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_partkey = p_partkey
and lo_orderdate = d_datekey
and c_region = 'AMERICA'
and s_nation = 'UNITED STATES'
and (d_year = 1997 or d_year = 1998)
and p_category = 'MFGR#14'
group by d_year, s_city, p_brand1
order by d_year, s_city, p_brand1
"""

QUERIES = {"q1.1": Q1_1, "q1.2": Q1_2, "q1.3": Q1_3,
           "q2.1": Q2_1, "q2.2": Q2_2, "q2.3": Q2_3,
           "q3.1": Q3_1, "q3.2": Q3_2, "q3.3": Q3_3, "q3.4": Q3_4,
           "q4.1": Q4_1, "q4.2": Q4_2, "q4.3": Q4_3}


# -- numpy oracles -----------------------------------------------------------
# Each joins by position: a dimension's keys are 1..n in row order, a
# datekey is looked up in the sorted d_datekey. Sums are int64.

def _at(dims: dict, table: str, col: str, keys: np.ndarray) -> np.ndarray:
    """Dimension column `col` at the fact table's foreign keys."""
    dim = dims[table]
    if table == "date":
        return dim[col][np.searchsorted(dim["d_datekey"], keys)]
    return dim[col][keys - 1]


def _year(lo, dims):
    return _at(dims, "date", "d_year", lo["lo_orderdate"])


def _flight1(lo, date_mask, dlo, dhi, qlo, qhi) -> int:
    m = (date_mask & (lo["lo_discount"] >= dlo) & (lo["lo_discount"] <= dhi)
         & (lo["lo_quantity"] >= qlo) & (lo["lo_quantity"] <= qhi))
    return int((lo["lo_extendedprice"][m] * lo["lo_discount"][m]).sum())


def ref_q1_1(lo: dict, dims: dict) -> int:
    return _flight1(lo, _year(lo, dims) == 1993, 1, 3, 1, 24)


def ref_q1_2(lo: dict, dims: dict) -> int:
    ym = _at(dims, "date", "d_yearmonthnum", lo["lo_orderdate"])
    return _flight1(lo, ym == 199401, 4, 6, 26, 35)


def ref_q1_3(lo: dict, dims: dict) -> int:
    week = _at(dims, "date", "d_weeknuminyear", lo["lo_orderdate"])
    return _flight1(lo, (week == 6) & (_year(lo, dims) == 1994),
                    5, 7, 26, 35)


def _grouped(mask: np.ndarray, keys: list, values: np.ndarray) -> list:
    """[(key..., int sum)] over the masked rows, one row a distinct key
    tuple, ascending by the keys."""
    out: dict = {}
    cols = [k[mask].tolist() for k in keys]
    for *key, v in zip(*cols, values[mask].tolist()):
        key = tuple(key)
        out[key] = out.get(key, 0) + v
    return [k + (v,) for k, v in sorted(out.items())]


def _flight2(lo, dims, part_mask, region) -> list:
    """Rows (sum(lo_revenue), d_year, p_brand1) by d_year, p_brand1."""
    m = (part_mask[lo["lo_partkey"] - 1]
         & (_at(dims, "supplier", "s_region", lo["lo_suppkey"]) == region))
    rows = _grouped(m, [_year(lo, dims),
                        _at(dims, "part", "p_brand1", lo["lo_partkey"])],
                    lo["lo_revenue"])
    return [(r, y, b) for y, b, r in rows]


def ref_q2_1(lo: dict, dims: dict) -> list[tuple]:
    return _flight2(lo, dims, dims["part"]["p_category"] == "MFGR#12",
                    "AMERICA")


def ref_q2_2(lo: dict, dims: dict) -> list[tuple]:
    brand = dims["part"]["p_brand1"]
    return _flight2(lo, dims,
                    (brand >= "MFGR#2221") & (brand <= "MFGR#2228"), "ASIA")


def ref_q2_3(lo: dict, dims: dict) -> list[tuple]:
    return _flight2(lo, dims, dims["part"]["p_brand1"] == "MFGR#2221",
                    "EUROPE")


def _flight3(lo, dims, level, cust_mask, supp_mask, date_mask) -> list:
    """Rows (c_<level>, s_<level>, d_year, revenue) by d_year, revenue
    descending."""
    m = (cust_mask[lo["lo_custkey"] - 1] & supp_mask[lo["lo_suppkey"] - 1]
         & date_mask)
    rows = _grouped(
        m, [_at(dims, "customer", "c_" + level, lo["lo_custkey"]),
            _at(dims, "supplier", "s_" + level, lo["lo_suppkey"]),
            _year(lo, dims)], lo["lo_revenue"])
    return sorted(rows, key=lambda r: (r[2], -r[3]))


def _years_92_97(lo, dims):
    y = _year(lo, dims)
    return (y >= 1992) & (y <= 1997)


def ref_q3_1(lo: dict, dims: dict) -> list[tuple]:
    return _flight3(lo, dims, "nation",
                    dims["customer"]["c_region"] == "ASIA",
                    dims["supplier"]["s_region"] == "ASIA",
                    _years_92_97(lo, dims))


def ref_q3_2(lo: dict, dims: dict) -> list[tuple]:
    return _flight3(lo, dims, "city",
                    dims["customer"]["c_nation"] == "UNITED STATES",
                    dims["supplier"]["s_nation"] == "UNITED STATES",
                    _years_92_97(lo, dims))


_KI = ("UNITED KI1", "UNITED KI5")


def ref_q3_3(lo: dict, dims: dict) -> list[tuple]:
    return _flight3(lo, dims, "city",
                    np.isin(dims["customer"]["c_city"], _KI),
                    np.isin(dims["supplier"]["s_city"], _KI),
                    _years_92_97(lo, dims))


def ref_q3_4(lo: dict, dims: dict) -> list[tuple]:
    ym = _at(dims, "date", "d_yearmonth", lo["lo_orderdate"])
    return _flight3(lo, dims, "city",
                    np.isin(dims["customer"]["c_city"], _KI),
                    np.isin(dims["supplier"]["s_city"], _KI),
                    ym == "Dec1997")


def _flight4(lo, mask, keys) -> list:
    return _grouped(mask, keys, lo["lo_revenue"] - lo["lo_supplycost"])


def _america(lo, dims, table, prefix, key):
    return _at(dims, table, prefix + "_region", lo[key]) == "AMERICA"


def _mfgr_1_2(lo, dims):
    return np.isin(_at(dims, "part", "p_mfgr", lo["lo_partkey"]),
                   ("MFGR#1", "MFGR#2"))


def ref_q4_1(lo: dict, dims: dict) -> list[tuple]:
    m = (_america(lo, dims, "customer", "c", "lo_custkey")
         & _america(lo, dims, "supplier", "s", "lo_suppkey")
         & _mfgr_1_2(lo, dims))
    return _flight4(lo, m, [
        _year(lo, dims),
        _at(dims, "customer", "c_nation", lo["lo_custkey"])])


def ref_q4_2(lo: dict, dims: dict) -> list[tuple]:
    y = _year(lo, dims)
    m = (_america(lo, dims, "customer", "c", "lo_custkey")
         & _america(lo, dims, "supplier", "s", "lo_suppkey")
         & ((y == 1997) | (y == 1998)) & _mfgr_1_2(lo, dims))
    return _flight4(lo, m, [
        y, _at(dims, "supplier", "s_nation", lo["lo_suppkey"]),
        _at(dims, "part", "p_category", lo["lo_partkey"])])


def ref_q4_3(lo: dict, dims: dict) -> list[tuple]:
    y = _year(lo, dims)
    m = (_america(lo, dims, "customer", "c", "lo_custkey")
         & (_at(dims, "supplier", "s_nation", lo["lo_suppkey"])
            == "UNITED STATES")
         & ((y == 1997) | (y == 1998))
         & (_at(dims, "part", "p_category", lo["lo_partkey"]) == "MFGR#14"))
    return _flight4(lo, m, [
        y, _at(dims, "supplier", "s_city", lo["lo_suppkey"]),
        _at(dims, "part", "p_brand1", lo["lo_partkey"])])


ORACLES = {"q1.1": ref_q1_1, "q1.2": ref_q1_2, "q1.3": ref_q1_3,
           "q2.1": ref_q2_1, "q2.2": ref_q2_2, "q2.3": ref_q2_3,
           "q3.1": ref_q3_1, "q3.2": ref_q3_2, "q3.3": ref_q3_3,
           "q3.4": ref_q3_4, "q4.1": ref_q4_1, "q4.2": ref_q4_2,
           "q4.3": ref_q4_3}


class SSB:
    """Workload-registry wrapper: load + run the query flights."""

    name = "ssb"

    def __init__(self, engine, sf: float = 0.01, seed: int = 0,
                 rows: int | None = None):
        self.engine = engine
        self.sf = sf
        self.seed = seed
        self.rows = rows
        self.data = None

    def setup(self) -> None:
        self.data = load(self.engine, self.sf, seed=self.seed,
                         rows=self.rows)

    def run(self, steps: int = 1) -> dict:
        import time
        out = {}
        for name, sql in QUERIES.items():
            t0 = time.monotonic()
            for _ in range(steps):
                r = self.engine.execute(sql)
            out[name] = {"rows": len(r.rows),
                         "seconds": (time.monotonic() - t0) / steps}
        return out
