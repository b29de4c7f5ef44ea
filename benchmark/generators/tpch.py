"""TPC-H data for the benchmark, made from one seed.

A copy of the generator in cockroach_tpu/models/tpch.py, independent of
it from now on: the same domains and correlations (synthetic, not
`dbgen`), with three changes. Every table's random stream derives from
the `seed` argument. Every table has every column of the TPC-H schema
(spec 1.4, as pkg/workload/tpch declares it) at the spec's width:
models/tpch.py leaves out the comments, c_address and o_clerk and
draws p_name from eight values; here they are the spec's text strings,
v-strings and five-of-92-colour names (4.2.2.10, 4.2.3). And every
column leaves here in the form the store keeps it in: DECIMAL(15,2) as
int64 hundredths, DATE as int32 days since 1970-01-01, a string as
int32 codes into a dictionary of distinct values that is returned
beside the columns, which is the only form the store has for a string.
The values of a column are made in bulk with numpy, and the integer
reference reads the very arrays the engine ingests.

A generator module is found by the name a configuration gives under
`generator`; it offers DDL, TABLE_ORDER and generate().
"""

from __future__ import annotations

import datetime

import numpy as np

LINEITEM_PER_SF = 6_001_215  # pkg/workload/tpch/tpch.go:39
PART_PER_SF = 200_000
SUPP_PER_SF = 10_000
ORDERS_PER_SF = 1_500_000
CUST_PER_SF = 150_000

EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def iso(day: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
            "HOUSEHOLD"]
ORDER_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM",
                    "4-NOT SPECIFIED", "5-LOW"]
ORDER_STATUS = ["F", "P", "O"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
TYPES_SYL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPES_SYL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPES_SYL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
PART_TYPES = [f"{a} {b} {c}" for a in TYPES_SYL1 for b in TYPES_SYL2
              for c in TYPES_SYL3]
CONTAINERS = ["SM CASE", "SM BOX", "MED BAG", "MED BOX", "LG CASE",
              "LG BOX", "JUMBO PACK", "WRAP JAR"]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
MFGRS = [f"Manufacturer#{i}" for i in range(1, 6)]
# spec 4.2.3: P_NAME is five of these 92, separated by single spaces
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
assert len(COLORS) == 92
# the words of the spec's pseudo-text grammar (4.2.2.14), for the pool
# the comment columns are cut from
TEXT_WORDS = """foxes ideas theodolites pinto beans instructions
dependencies excuses platelets asymptotes courts dolphins multipliers
sauternes warthogs frets dinos attainments somas Tiresias' patterns
forges braids hockey players frays warhorses dugouts notornis epitaphs
pearls tithes waters orbits gifts sheaves depths sentiments decoys
realms pains grouches escapades sleep wake are cajole haggle nag use
boost affix detect integrate maintain nod was lose sublate solve thrash
promise engage hinder print x-ray breach eat grow impress mold poach
serve run dazzle snooze doze unwind kindle play hang believe doubt
furious sly careful blithe quick fluffy slow quiet ruthless thin close
dogged daring brave stealthy permanent enticing idle busy regular final
ironic even bold silent sometimes always never furiously slyly
carefully blithely quickly fluffily slowly quietly ruthlessly thinly
closely doggedly daringly bravely stealthily permanently enticingly idly
busily regularly finally ironically evenly boldly silently about above
according to across after against along alongside of among around at
atop before behind beneath beside besides between beyond by despite
during except for from in place of inside instead of into near of on
outside over past since through throughout to toward under until up
upon without with within do may might shall will would can could should
ought to must will have to shall have to need to try to . ; : ? !
--""".split()
TEXT_POOL_BYTES = 1 << 23  # the spec's pool is 300 MB
ADDRESS_CHARS = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ ,",
    dtype=np.uint8)
RETURNFLAGS = ["R", "A", "N"]
LINESTATUS = ["O", "F"]

# the TPC-H schema (spec 1.4.1), every column at its declared width;
# the engine reads CHAR(n) and VARCHAR(n) as STRING
DDL = {
    "lineitem": """
CREATE TABLE lineitem (
    l_orderkey      INT8 NOT NULL,
    l_partkey       INT8 NOT NULL,
    l_suppkey       INT8 NOT NULL,
    l_linenumber    INT8 NOT NULL,
    l_quantity      DECIMAL(15,2) NOT NULL,
    l_extendedprice DECIMAL(15,2) NOT NULL,
    l_discount      DECIMAL(15,2) NOT NULL,
    l_tax           DECIMAL(15,2) NOT NULL,
    l_returnflag    CHAR(1) NOT NULL,
    l_linestatus    CHAR(1) NOT NULL,
    l_shipdate      DATE NOT NULL,
    l_commitdate    DATE NOT NULL,
    l_receiptdate   DATE NOT NULL,
    l_shipinstruct  CHAR(25) NOT NULL,
    l_shipmode      CHAR(10) NOT NULL,
    l_comment       VARCHAR(44) NOT NULL
)""",
    "part": """
CREATE TABLE part (
    p_partkey     INT8 NOT NULL,
    p_name        VARCHAR(55) NOT NULL,
    p_mfgr        CHAR(25) NOT NULL,
    p_brand       CHAR(10) NOT NULL,
    p_type        VARCHAR(25) NOT NULL,
    p_size        INT8 NOT NULL,
    p_container   CHAR(10) NOT NULL,
    p_retailprice DECIMAL(15,2) NOT NULL,
    p_comment     VARCHAR(23) NOT NULL
)""",
    "orders": """
CREATE TABLE orders (
    o_orderkey      INT8 NOT NULL,
    o_custkey       INT8 NOT NULL,
    o_orderstatus   CHAR(1) NOT NULL,
    o_totalprice    DECIMAL(15,2) NOT NULL,
    o_orderdate     DATE NOT NULL,
    o_orderpriority CHAR(15) NOT NULL,
    o_clerk         CHAR(15) NOT NULL,
    o_shippriority  INT8 NOT NULL,
    o_comment       VARCHAR(79) NOT NULL
)""",
    "customer": """
CREATE TABLE customer (
    c_custkey    INT8 NOT NULL,
    c_name       VARCHAR(25) NOT NULL,
    c_address    VARCHAR(40) NOT NULL,
    c_nationkey  INT8 NOT NULL,
    c_phone      CHAR(15) NOT NULL,
    c_acctbal    DECIMAL(15,2) NOT NULL,
    c_mktsegment CHAR(10) NOT NULL,
    c_comment    VARCHAR(117) NOT NULL
)""",
}

TABLE_ORDER = ("lineitem", "part", "orders", "customer")
# currentdate of the spec: lines received by then were returned or
# accepted, lines shipped after it are still open
CURRENT_DATE = days("1995-06-17")


def _column_order(table: str) -> list:
    """The table's column names in the order its DDL declares them."""
    return [ln.split()[0] for ln in DDL[table].splitlines()
            if ln.startswith("    ")]


def n_rows(table: str, sf: float) -> int:
    floor_sf = max(sf, 0.01)
    return {
        "lineitem": int(LINEITEM_PER_SF * sf),
        "part": max(int(PART_PER_SF * floor_sf), 1000),
        "orders": int(ORDERS_PER_SF * floor_sf),
        "customer": max(int(CUST_PER_SF * floor_sf), 500),
    }[table]


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), TABLE_ORDER.index(table)])


# odd 64-bit multipliers of the hash that finds equal strings
_HASH_MULT = np.random.default_rng(0x7c9).integers(
    0, 1 << 63, size=16, dtype=np.uint64) * np.uint64(2) + np.uint64(1)
# _KEEP[k] keeps the first k bytes of a little-endian 8-byte word
_KEEP = np.array([(1 << (8 * k)) - 1 for k in range(9)], dtype=np.uint64)


_BLOCK = 1 << 16  # rows a step: the temporaries stay in the cache


def _coded(n: int, words_of) -> tuple:
    """(int32 codes, distinct strings) of a column of n strings.
    `words_of(rows)` gives the strings of those rows (a slice or an
    index array) as a uint64 matrix, one column a string: its bytes in
    little-endian words, zero past its end. Equal strings are found by
    a 64-bit hash rather than by sorting strings; two strings that
    collide (about n^2 / 2^64) would share one's text, which is still
    a table. The strings themselves are made for the distinct rows
    only, a block at a time."""
    h = np.empty(n, dtype=np.uint64)
    for b in range(0, n, _BLOCK):
        words = words_of(slice(b, b + _BLOCK))
        h[b:b + _BLOCK] = (words * _HASH_MULT[:len(words), None]).sum(
            axis=0, dtype=np.uint64)
    order = np.argsort(h)
    new = np.ones(n, dtype=bool)
    new[1:] = h[order[1:]] != h[order[:-1]]
    codes = np.empty(n, dtype=np.int32)
    codes[order] = np.cumsum(new, dtype=np.int32) - 1
    first = order[new]
    values: list = []
    for b in range(0, len(first), _BLOCK):
        words = words_of(first[b:b + _BLOCK])
        raw = np.ascontiguousarray(words.T).view(f"S{8 * len(words)}")
        values.extend(map(bytes.decode, raw.ravel().tolist()))
    return codes, values


def _text_pool(seed: int) -> np.ndarray:
    """The pool every comment of a run is cut from, as 8-byte words:
    words of the spec's grammar drawn from the seed, separated by
    single spaces."""
    rng = np.random.default_rng([int(seed), 99])
    words = np.array(TEXT_WORDS)
    mean = sum(len(w) + 1 for w in TEXT_WORDS) / len(TEXT_WORDS)
    picks = rng.integers(0, len(words), int(TEXT_POOL_BYTES / mean * 1.1))
    text = " ".join(words[picks].tolist()).encode()
    assert len(text) >= TEXT_POOL_BYTES
    return np.frombuffer(text[:TEXT_POOL_BYTES], dtype=np.uint64)


def _text(rng, n: int, lo: int, hi: int, pool: np.ndarray) -> tuple:
    """A `text string [lo, hi]` column (spec 4.2.2.10): a substring of
    the pool from a random place (here a multiple of 8 bytes, so that a
    string is a gather of whole words), of a random length in lo..hi."""
    word = np.arange(-(-hi // 8))[:, None]
    start = rng.integers(0, len(pool) - len(word), size=n, dtype=np.int32)
    length = rng.integers(lo, hi + 1, size=n, dtype=np.int16)

    def words_of(rows):
        return (pool[start[rows] + word]
                & _KEEP[np.clip(length[rows] - 8 * word, 0, 8)])

    return _coded(n, words_of)


def _v_string(rng, n: int, lo: int, hi: int) -> tuple:
    """A `v-string [lo, hi]` column (spec 4.2.2.7): random characters
    out of an alphabet of 64, of a random length in lo..hi."""
    word = np.arange(-(-hi // 8))[:, None]
    chars = ADDRESS_CHARS[rng.integers(0, len(ADDRESS_CHARS),
                                       size=(n, 8 * len(word)))]
    words = np.ascontiguousarray(chars.view(np.uint64).T)
    words &= _KEEP[np.clip(rng.integers(lo, hi + 1, size=n) - 8 * word,
                           0, 8)]
    return _coded(n, lambda rows: words[:, rows])


def part_price_cents(partkey: np.ndarray) -> np.ndarray:
    """Retail price of a part in hundredths (spec 4.2.3's formula)."""
    return 90000 + (partkey % 200001) // 10 + 100 * (partkey % 1000)


def _lineitem(sf: float, seed: int):
    n = n_rows("lineitem", sf)
    rng = _rng(seed, "lineitem")
    nsupp = max(int(SUPP_PER_SF * max(sf, 0.01)), 100)
    # l_orderkey and l_quantity alone do not follow the seed. The engine
    # evaluates Q18's IN-subquery (the orders whose quantities sum above
    # the threshold) while it prepares the statement and bakes the keys
    # it finds into the compiled program as constants, so any change to
    # these two columns is a new program and a 160 s compile (PERF.md
    # findings, PR 22). Held still, a new seed still changes every other
    # column and table, and every answer, and finds Q18 in the cache.
    still = np.random.default_rng([20260926, n])
    orderkey = np.sort(still.integers(1, n_rows("orders", sf) + 1, size=n,
                                      dtype=np.int64))
    quantity = still.integers(1, 51, size=n, dtype=np.int64)
    partkey = rng.integers(1, n_rows("part", sf) + 1, size=n,
                           dtype=np.int64)
    suppkey = (partkey + rng.integers(0, 4, size=n) * 7) % nsupp + 1
    shipdate = rng.integers(days("1992-01-02"), days("1998-12-02"),
                            size=n, dtype=np.int32)
    commitdate = shipdate + rng.integers(-60, 60, size=n, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, size=n, dtype=np.int32)
    received = receiptdate <= CURRENT_DATE
    coin = rng.random(n) < 0.5
    cols = {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_suppkey": suppkey.astype(np.int64),
        "l_linenumber": rng.integers(1, 8, size=n, dtype=np.int64),
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * part_price_cents(partkey),
        "l_discount": rng.integers(0, 11, size=n, dtype=np.int64),
        "l_tax": rng.integers(0, 9, size=n, dtype=np.int64),
        # R or A once received, N while under way (codes into
        # RETURNFLAGS); F once shipped, O while open
        "l_returnflag": np.where(received, np.where(coin, 0, 1),
                                 2).astype(np.int32),
        "l_linestatus": np.where(shipdate > CURRENT_DATE, 0,
                                 1).astype(np.int32),
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), size=n,
                                       dtype=np.int32),
        "l_shipmode": rng.integers(0, len(SHIPMODES), size=n,
                                   dtype=np.int32),
    }
    dicts = {"l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUS,
             "l_shipinstruct": SHIPINSTRUCT, "l_shipmode": SHIPMODES}
    cols["l_comment"], dicts["l_comment"] = _text(rng, n, 10, 43,
                                                  _text_pool(seed))
    return cols, dicts


def _part(sf: float, seed: int):
    n = n_rows("part", sf)
    rng = _rng(seed, "part")
    partkey = np.arange(1, n + 1, dtype=np.int64)
    cols = {
        "p_partkey": partkey,
        "p_mfgr": rng.integers(0, len(MFGRS), size=n, dtype=np.int32),
        "p_brand": rng.integers(0, len(BRANDS), size=n, dtype=np.int32),
        "p_type": rng.integers(0, len(PART_TYPES), size=n,
                               dtype=np.int32),
        "p_size": rng.integers(1, 51, size=n, dtype=np.int64),
        "p_container": rng.integers(0, len(CONTAINERS), size=n,
                                    dtype=np.int32),
        "p_retailprice": part_price_cents(partkey),
    }
    dicts = {"p_mfgr": MFGRS, "p_brand": BRANDS,
             "p_type": PART_TYPES, "p_container": CONTAINERS}
    # five distinct colours a name: a first one, then four steps of
    # 1..18 round the list, which cannot come back to a colour (72 < 92)
    steps = np.concatenate([rng.integers(0, 92, size=(n, 1)),
                            rng.integers(1, 19, size=(n, 4))], axis=1)
    picks = np.cumsum(steps, axis=1) % 92
    names, name_code = np.unique(picks, axis=0, return_inverse=True)
    cols["p_name"] = name_code.ravel().astype(np.int32)
    dicts["p_name"] = [" ".join(COLORS[i] for i in row)
                       for row in names.tolist()]
    cols["p_comment"], dicts["p_comment"] = _text(rng, n, 5, 22,
                                                  _text_pool(seed))
    return {c: cols[c] for c in _column_order("part")}, dicts


def _orders(sf: float, seed: int):
    n = n_rows("orders", sf)
    rng = _rng(seed, "orders")
    orderdate = rng.integers(days("1992-01-01"), days("1998-08-02"),
                             size=n, dtype=np.int32)
    status = np.where(orderdate < CURRENT_DATE - 90, 0,
                      np.where(orderdate < CURRENT_DATE, 1, 2))
    # spec 4.2.3: a custkey divisible by 3 places no order
    ncust = n_rows("customer", sf)
    idx = rng.integers(0, ncust - ncust // 3, size=n, dtype=np.int64)
    cols = {
        "o_orderkey": np.arange(1, n + 1, dtype=np.int64),
        "o_custkey": 3 * (idx // 2) + 1 + (idx % 2),
        "o_orderstatus": status.astype(np.int32),
        "o_totalprice": rng.integers(90000, 50000000, size=n,
                                     dtype=np.int64),
        "o_orderdate": orderdate,
        "o_orderpriority": rng.integers(0, len(ORDER_PRIORITIES), size=n,
                                        dtype=np.int32),
        "o_shippriority": np.zeros(n, dtype=np.int64),
    }
    dicts = {"o_orderstatus": ORDER_STATUS,
             "o_orderpriority": ORDER_PRIORITIES}
    # spec 4.2.3: Clerk#<9 digits>, one of sf * 1000 clerks
    nclerk = max(int(1000 * sf), 10)
    cols["o_clerk"] = rng.integers(0, nclerk, size=n, dtype=np.int32)
    dicts["o_clerk"] = [f"Clerk#{k:09d}" for k in range(1, nclerk + 1)]
    cols["o_comment"], dicts["o_comment"] = _text(rng, n, 19, 78,
                                                  _text_pool(seed))
    return {c: cols[c] for c in _column_order("orders")}, dicts


def _customer(sf: float, seed: int):
    n = n_rows("customer", sf)
    rng = _rng(seed, "customer")
    custkey = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, size=n, dtype=np.int64)
    local = rng.integers(100, 999, size=(n, 2))
    line = rng.integers(1000, 9999, size=n)
    # spec 4.2.2.9: country code = nationkey + 10
    phones, phone_code = np.unique(
        np.array([f"{c}-{a}-{b}-{d}" for c, (a, b), d in
                  zip((nation + 10).tolist(), local.tolist(),
                      line.tolist())]), return_inverse=True)
    cols = {
        "c_custkey": custkey,
        "c_name": np.arange(n, dtype=np.int32),
        "c_nationkey": nation,
        "c_phone": phone_code.astype(np.int32),
        "c_acctbal": rng.integers(-99900, 999900, size=n,
                                  dtype=np.int64),
        "c_mktsegment": rng.integers(0, len(SEGMENTS), size=n,
                                     dtype=np.int32),
    }
    dicts = {"c_name": [f"Customer#{k:09d}" for k in custkey.tolist()],
             "c_phone": phones.tolist(), "c_mktsegment": SEGMENTS}
    cols["c_address"], dicts["c_address"] = _v_string(rng, n, 10, 40)
    cols["c_comment"], dicts["c_comment"] = _text(rng, n, 29, 116,
                                                  _text_pool(seed))
    return {c: cols[c] for c in _column_order("customer")}, dicts


_MAKERS = {"lineitem": _lineitem, "part": _part, "orders": _orders,
           "customer": _customer}


def generate(table: str, sf: float, seed: int):
    """(columns, dictionaries) of one table: numpy arrays in stored form
    and, for each STRING column, the list its int32 codes index."""
    return _MAKERS[table](sf, seed)
