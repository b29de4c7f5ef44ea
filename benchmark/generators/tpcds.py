"""TPC-DS's store channel for the benchmark, made from one seed.

Five of the 24 tables of the TPC-DS specification v3.2.0 (sections 2.3,
2.4), every column at its declared type: `store_sales` (23 columns),
`item` (22), `date_dim` (28), `store` (29), `customer_demographics`
(9), at Table 3-2's row counts for the scale factor (2,880,404
store_sales and 18,000 items at SF1, 12 stores, the 73,049 days of
1900-01-02 to 2100-01-01, the demographics' cross product of 1,920,800
rows). Synthetic, not `dsdgen`; independent of
cockroach_tpu/workload/tpcds.py, with which it shares the schema and
the domains. Every column leaves here in the form the store keeps it
in: an integer or a DECIMAL(7,2) (integer hundredths) as int64, a DATE
as int32 days since 1970-01-01, a string as int32 codes into a
dictionary returned beside the columns (a low-cardinality string's
dictionary is its whole domain). No value is NULL: run.py's ingest
hands the store no validity masks. Made in bulk with numpy; the
integer references (statements/ds_q*.py, dsref.py) read the very
arrays the engine ingests.

Imports nothing of the program. Found by the name a configuration
gives under `generator`; offers DDL, TABLE_ORDER and generate().

One refusal, as generators/ssb.py and tpch_full.py have one: the four
statements group by ROLLUP and rank over grouped rows, and a program
whose parser has no `Parser.parse_group_by` (every one before PR 40)
reads `rollup(...)` as an unknown function and answers Q27 with an
error. Such a program cannot run the configuration, and says so in its
first seconds with an exit code other than 0. The program keeps that
name while a configuration names this generator. The reference worker
imports no part of the program, so nothing is asked there.
"""

from __future__ import annotations

import datetime
import sys

import numpy as np

STORE_SALES_PER_SF = 2_880_404
ITEM_PER_SF = 18_000
DATE_ROWS = 73_049
DATE_SK0 = 2_415_022                       # 1900-01-02
FIRST_DAY = datetime.date(1900, 1, 2)
EPOCH = datetime.date(1970, 1, 1)
SALES_LO = datetime.date(1998, 1, 2)
SALES_HI = datetime.date(2003, 1, 2)

GENDERS = ["M", "F"]
MARITAL = ["M", "S", "D", "W", "U"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown"]
CREDIT = ["Good", "High Risk", "Low Risk", "Unknown"]
# the hierarchy of the specification's item distributions: a class
# name can stand under two categories (computers, kids, ...)
CATEGORIES = {
    "Books": ["arts", "business", "computers", "cooking",
              "entertainments", "fiction", "history", "home repair",
              "mystery", "parenting", "reference", "romance", "science",
              "self-help", "sports", "travel"],
    "Children": ["infants", "newborn", "school-uniforms", "toddlers"],
    "Electronics": ["audio", "automotive", "camcorders", "cameras",
                    "computers", "disk drives", "dvd/vcr players",
                    "karoke", "memory", "monitors", "musical", "personal",
                    "portable", "scanners", "stereo", "televisions",
                    "wireless"],
    "Home": ["accent", "bathroom", "bedding", "blinds/shades",
             "curtains/drapes", "decor", "flatware", "furniture",
             "glassware", "kids", "lighting", "mattresses", "paint",
             "rugs", "tables", "wallpaper"],
    "Jewelry": ["birdal", "bracelets", "consignment", "costume",
                "custom", "diamonds", "earings", "estate", "gold",
                "jewelry boxes", "loose stones", "mens watch",
                "pendants", "rings", "semi-precious", "womens watch"],
    "Men": ["accessories", "pants", "shirts", "sports-apparel"],
    "Music": ["classical", "country", "pop", "rock"],
    "Shoes": ["athletic", "kids", "mens", "womens"],
    "Sports": ["archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf",
               "guns", "hockey", "optics", "outdoor", "pools", "sailing",
               "tennis"],
    "Women": ["dresses", "fragrances", "maternity", "swimwear"],
}
CATEGORY_NAMES = list(CATEGORIES)
CLASS_NAMES = sorted({c for cs in CATEGORIES.values() for c in cs})
BRANDS_PER_CLASS = 9
# dsdgen builds names from these ten syllables, one a digit
SYLLABLES = ["ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "bar", "n st"]
SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
         "N/A"]
UNITS = ["Each", "Dozen", "Case", "Pallet", "Gross", "Carton", "Box",
         "Bunch", "Bundle", "Oz", "Lb", "Ton", "Ounce", "Pound", "Tsp",
         "Tbl", "Cup", "Dram", "Gram", "N/A", "Unknown"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "burnished", "chartreuse", "chiffon", "chocolate", "coral",
          "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
          "dim", "dodger", "drab", "firebrick", "floral", "forest",
          "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
          "honeydew", "hot", "indian", "ivory", "khaki", "lace",
          "lavender", "lawn", "lemon", "light", "lime", "linen",
          "magenta", "maroon", "medium", "metallic", "midnight", "mint",
          "misty", "moccasin", "navajo", "navy", "olive", "orange",
          "orchid", "pale", "papaya", "peach", "peru", "pink", "plum",
          "powder", "puff", "purple", "red", "rose", "rosy", "royal",
          "saddle", "salmon", "sandy", "seashell", "sienna", "sky",
          "slate", "smoke", "snow", "spring", "steel", "tan", "thistle",
          "tomato", "turquoise", "violet", "wheat", "white", "yellow"]
DAY_NAMES = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
             "Saturday", "Sunday"]
YN = ["N", "Y"]

_ID_LETTERS = np.array(list("ABCDEFGHIJKLMNOP"))

DDL = {
    "store_sales": """
CREATE TABLE store_sales (
    ss_sold_date_sk       INT8,
    ss_sold_time_sk       INT8,
    ss_item_sk            INT8 NOT NULL,
    ss_customer_sk        INT8,
    ss_cdemo_sk           INT8,
    ss_hdemo_sk           INT8,
    ss_addr_sk            INT8,
    ss_store_sk           INT8,
    ss_promo_sk           INT8,
    ss_ticket_number      INT8 NOT NULL,
    ss_quantity           INT8,
    ss_wholesale_cost     DECIMAL(7,2),
    ss_list_price         DECIMAL(7,2),
    ss_sales_price        DECIMAL(7,2),
    ss_ext_discount_amt   DECIMAL(7,2),
    ss_ext_sales_price    DECIMAL(7,2),
    ss_ext_wholesale_cost DECIMAL(7,2),
    ss_ext_list_price     DECIMAL(7,2),
    ss_ext_tax            DECIMAL(7,2),
    ss_coupon_amt         DECIMAL(7,2),
    ss_net_paid           DECIMAL(7,2),
    ss_net_paid_inc_tax   DECIMAL(7,2),
    ss_net_profit         DECIMAL(7,2)
)""",
    "item": """
CREATE TABLE item (
    i_item_sk        INT8 NOT NULL PRIMARY KEY,
    i_item_id        CHAR(16) NOT NULL,
    i_rec_start_date DATE,
    i_rec_end_date   DATE,
    i_item_desc      VARCHAR(200),
    i_current_price  DECIMAL(7,2),
    i_wholesale_cost DECIMAL(7,2),
    i_brand_id       INT8,
    i_brand          CHAR(50),
    i_class_id       INT8,
    i_class          CHAR(50),
    i_category_id    INT8,
    i_category       CHAR(50),
    i_manufact_id    INT8,
    i_manufact       CHAR(50),
    i_size           CHAR(20),
    i_formulation    CHAR(20),
    i_color          CHAR(20),
    i_units          CHAR(10),
    i_container      CHAR(10),
    i_manager_id     INT8,
    i_product_name   CHAR(50)
)""",
    "date_dim": """
CREATE TABLE date_dim (
    d_date_sk           INT8 NOT NULL PRIMARY KEY,
    d_date_id           CHAR(16) NOT NULL,
    d_date              DATE,
    d_month_seq         INT8,
    d_week_seq          INT8,
    d_quarter_seq       INT8,
    d_year              INT8,
    d_dow               INT8,
    d_moy               INT8,
    d_dom               INT8,
    d_qoy               INT8,
    d_fy_year           INT8,
    d_fy_quarter_seq    INT8,
    d_fy_week_seq       INT8,
    d_day_name          CHAR(9),
    d_quarter_name      CHAR(6),
    d_holiday           CHAR(1),
    d_weekend           CHAR(1),
    d_following_holiday CHAR(1),
    d_first_dom         INT8,
    d_last_dom          INT8,
    d_same_day_ly       INT8,
    d_same_day_lq       INT8,
    d_current_day       CHAR(1),
    d_current_week      CHAR(1),
    d_current_month     CHAR(1),
    d_current_quarter   CHAR(1),
    d_current_year      CHAR(1)
)""",
    "store": """
CREATE TABLE store (
    s_store_sk         INT8 NOT NULL PRIMARY KEY,
    s_store_id         CHAR(16) NOT NULL,
    s_rec_start_date   DATE,
    s_rec_end_date     DATE,
    s_closed_date_sk   INT8,
    s_store_name       VARCHAR(50),
    s_number_employees INT8,
    s_floor_space      INT8,
    s_hours            CHAR(20),
    s_manager          VARCHAR(40),
    s_market_id        INT8,
    s_geography_class  VARCHAR(100),
    s_market_desc      VARCHAR(100),
    s_market_manager   VARCHAR(40),
    s_division_id      INT8,
    s_division_name    VARCHAR(50),
    s_company_id       INT8,
    s_company_name     VARCHAR(50),
    s_street_number    VARCHAR(10),
    s_street_name      VARCHAR(60),
    s_street_type      CHAR(15),
    s_suite_number     CHAR(10),
    s_city             VARCHAR(60),
    s_county           VARCHAR(30),
    s_state            CHAR(2),
    s_zip              CHAR(10),
    s_country          VARCHAR(20),
    s_gmt_offset       DECIMAL(5,2),
    s_tax_percentage   DECIMAL(5,2)
)""",
    "customer_demographics": """
CREATE TABLE customer_demographics (
    cd_demo_sk            INT8 NOT NULL PRIMARY KEY,
    cd_gender             CHAR(1),
    cd_marital_status     CHAR(1),
    cd_education_status   CHAR(20),
    cd_purchase_estimate  INT8,
    cd_credit_rating      CHAR(10),
    cd_dep_count          INT8,
    cd_dep_employed_count INT8,
    cd_dep_college_count  INT8
)""",
}
TABLE_ORDER = ["date_dim", "store", "item", "customer_demographics",
               "store_sales"]

def n_rows(table: str, sf: float) -> int:
    if table == "store_sales":
        return max(int(round(STORE_SALES_PER_SF * sf)), 1000)
    if table == "item":
        return max(int(round(ITEM_PER_SF * sf)) // 2 * 2, 200)
    if table == "store":
        return 12
    if table == "date_dim":
        return DATE_ROWS
    if table == "customer_demographics":
        return 2 * 5 * 7 * len(purchase_estimates(sf)) * 4 * 7 * 7 * 7
    raise KeyError(table)


def purchase_estimates(sf: float) -> list:
    """cd_purchase_estimate's domain: 500 to 10,000 by 500 (twenty
    values). Below SF 0.1 only the first, so that a test's table is
    96,040 rows and not 1,920,800; every other domain is whole."""
    return list(range(500, 10_001, 500)) if sf >= 0.1 else [500]


def date_sk(day: datetime.date) -> int:
    return DATE_SK0 + (day - FIRST_DAY).days


def _ids(numbers: np.ndarray) -> list:
    """dsdgen's 16-character business keys: 'AAAAAAAA' and eight
    letters A-P of the number, most significant first."""
    out = []
    for k in numbers.tolist():
        digits = [(k >> (4 * i)) & 15 for i in range(7, -1, -1)]
        out.append("AAAAAAAA" + "".join(_ID_LETTERS[digits]))
    return out


def _word(numbers) -> list:
    """A name of syllables, one a decimal digit (dsdgen's mk_word)."""
    return ["".join(SYLLABLES[int(c)] for c in str(int(k)))
            for k in numbers]


def gen_date_dim() -> tuple:
    n = DATE_ROWS
    sk = np.arange(DATE_SK0, DATE_SK0 + n, dtype=np.int64)
    days = (FIRST_DAY - EPOCH).days + np.arange(n, dtype=np.int64)
    dt = days.astype("datetime64[D]")
    year = dt.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = dt.astype("datetime64[M]").astype(np.int64)
    moy = month0 - (year - 1970) * 12 + 1
    first = month0.astype("datetime64[M]").astype("datetime64[D]")
    dom = (dt - first).astype(np.int64) + 1
    last = (month0 + 1).astype("datetime64[M]").astype(
        "datetime64[D]") - 1
    dow = (days + 4) % 7                          # 0 = Sunday
    qoy = (moy - 1) // 3 + 1
    month_seq = (year - 1900) * 12 + moy - 1
    qnames = sorted({f"{y}Q{q}" for y in range(1900, 2101)
                     for q in range(1, 5)})
    qidx = (year - 1900) * 4 + qoy - 1
    holiday = ((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4)) \
        | ((moy == 12) & (dom == 25))
    cols = {
        "d_date_sk": sk,
        "d_date_id": np.arange(n, dtype=np.int32),
        "d_date": days.astype(np.int32),
        "d_month_seq": month_seq,
        "d_week_seq": (np.arange(n, dtype=np.int64) + 1) // 7 + 1,
        "d_quarter_seq": (year - 1900) * 4 + qoy,
        "d_year": year,
        "d_dow": dow,
        "d_moy": moy,
        "d_dom": dom,
        "d_qoy": qoy,
        "d_fy_year": year,
        "d_fy_quarter_seq": (year - 1900) * 4 + qoy,
        "d_fy_week_seq": (np.arange(n, dtype=np.int64) + 1) // 7 + 1,
        "d_day_name": ((dow + 6) % 7).astype(np.int32),
        "d_quarter_name": (qidx).astype(np.int32),
        "d_holiday": holiday.astype(np.int32),
        "d_weekend": ((dow == 0) | (dow == 6)).astype(np.int32),
        "d_following_holiday": np.roll(holiday, 1).astype(np.int32),
        "d_first_dom": sk - dom + 1,
        "d_last_dom": sk + (last - dt).astype(np.int64),
        "d_same_day_ly": sk - 365,
        "d_same_day_lq": sk - 91,
        "d_current_day": np.zeros(n, dtype=np.int32),
        "d_current_week": np.zeros(n, dtype=np.int32),
        "d_current_month": np.zeros(n, dtype=np.int32),
        "d_current_quarter": np.zeros(n, dtype=np.int32),
        "d_current_year": np.zeros(n, dtype=np.int32),
    }
    dicts = {"d_date_id": _ids(np.arange(n)), "d_day_name": DAY_NAMES,
             "d_quarter_name": qnames, "d_holiday": YN, "d_weekend": YN,
             "d_following_holiday": YN, "d_current_day": YN,
             "d_current_week": YN, "d_current_month": YN,
             "d_current_quarter": YN, "d_current_year": YN}
    return cols, dicts


STORE_NAMES = SYLLABLES


def gen_store(seed: int) -> tuple:
    rng = np.random.default_rng([seed, 3])
    n = 12
    sk = np.arange(1, n + 1, dtype=np.int64)
    bkey = (sk - 1) // 2                 # two versions a store id
    start = np.where(sk % 2 == 1, (datetime.date(1997, 3, 13) - EPOCH).days,
                     (datetime.date(2000, 3, 13) - EPOCH).days)
    cols = {
        "s_store_sk": sk,
        "s_store_id": bkey.astype(np.int32),
        "s_rec_start_date": start.astype(np.int32),
        "s_rec_end_date": (start + 3 * 365).astype(np.int32),
        "s_closed_date_sk": np.full(n, date_sk(datetime.date(2003, 1, 2)),
                                    dtype=np.int64),
        "s_store_name": bkey.astype(np.int32),
        "s_number_employees": rng.integers(200, 301, n),
        "s_floor_space": rng.integers(5_000_000, 10_000_001, n),
        "s_hours": rng.integers(0, 3, n).astype(np.int32),
        "s_manager": np.arange(n, dtype=np.int32),
        "s_market_id": rng.integers(1, 11, n),
        "s_geography_class": np.zeros(n, dtype=np.int32),
        "s_market_desc": np.arange(n, dtype=np.int32),
        "s_market_manager": np.arange(n, dtype=np.int32),
        "s_division_id": np.ones(n, dtype=np.int64),
        "s_division_name": np.zeros(n, dtype=np.int32),
        "s_company_id": np.ones(n, dtype=np.int64),
        "s_company_name": np.zeros(n, dtype=np.int32),
        "s_street_number": np.arange(n, dtype=np.int32),
        "s_street_name": np.arange(n, dtype=np.int32),
        "s_street_type": rng.integers(0, 4, n).astype(np.int32),
        "s_suite_number": np.arange(n, dtype=np.int32),
        "s_city": rng.integers(0, 2, n).astype(np.int32),
        "s_county": np.zeros(n, dtype=np.int32),
        "s_state": np.zeros(n, dtype=np.int32),
        "s_zip": rng.integers(0, 2, n).astype(np.int32),
        "s_country": np.zeros(n, dtype=np.int32),
        "s_gmt_offset": np.full(n, -500, dtype=np.int64),
        "s_tax_percentage": rng.integers(0, 12, n),
    }
    people = [f"{a} {b}" for a, b in zip(
        ["William", "Scott", "Edwin", "Charles", "Robert", "David",
         "Matthew", "Michael", "Jason", "Thomas", "Larry", "Ryan"],
        ["Ward", "Smith", "Adams", "Hackett", "Thompson", "Jones",
         "Miller", "Davis", "Brown", "Wilson", "Moore", "Taylor"])]
    dicts = {
        "s_store_id": _ids(np.arange(1, n // 2 + 1)),
        "s_store_name": STORE_NAMES,
        "s_hours": ["8AM-4PM", "8AM-8AM", "8AM-12AM"],
        "s_manager": people,
        "s_geography_class": ["Unknown"],
        "s_market_desc": [f"Market description {i}" for i in range(n)],
        "s_market_manager": people[::-1],
        "s_division_name": ["Unknown"],
        "s_company_name": ["Unknown"],
        "s_street_number": [str(100 + 37 * i) for i in range(n)],
        "s_street_name": [f"{w} " for w in _word(range(10, 10 + n))],
        "s_street_type": ["Street", "Avenue", "Boulevard", "Lane"],
        "s_suite_number": [f"Suite {10 * i}" for i in range(n)],
        "s_city": ["Midway", "Fairview"],
        "s_county": ["Williamson County"],
        "s_state": ["TN"],
        "s_zip": ["35709", "31904"],
        "s_country": ["United States"],
    }
    return cols, dicts


def gen_item(sf: float, seed: int) -> tuple:
    rng = np.random.default_rng([seed, 2])
    n = n_rows("item", sf)
    sk = np.arange(1, n + 1, dtype=np.int64)
    bkey = (sk - 1) // 2                 # two versions an item id
    cat = rng.integers(0, len(CATEGORY_NAMES), n)
    ncls = np.array([len(CATEGORIES[c]) for c in CATEGORY_NAMES])
    cls_in_cat = (rng.random(n) * ncls[cat]).astype(np.int64)
    class_code = np.array([[CLASS_NAMES.index(c) for c in
                            CATEGORIES[name]] + [0] * (17 - len(
                                CATEGORIES[name]))
                           for name in CATEGORY_NAMES])[cat, cls_in_cat]
    k = rng.integers(1, BRANDS_PER_CLASS + 1, n)
    brands = [f"{SYLLABLES[c]}{SYLLABLES[j % 10]}{SYLLABLES[j // 10]} #{b}"
              for c in range(len(CATEGORY_NAMES)) for j in range(17)
              for b in range(1, BRANDS_PER_CLASS + 1)]
    brand_code = ((cat * 17 + cls_in_cat) * BRANDS_PER_CLASS + k - 1)
    start = np.where(sk % 2 == 1, (datetime.date(1997, 10, 27) - EPOCH).days,
                     (datetime.date(2000, 10, 27) - EPOCH).days)
    price = rng.integers(9, 10_000, n)
    manufact = rng.integers(1, 1001, n)
    cols = {
        "i_item_sk": sk,
        "i_item_id": bkey.astype(np.int32),
        "i_rec_start_date": start.astype(np.int32),
        "i_rec_end_date": (start + 3 * 365).astype(np.int32),
        "i_item_desc": np.arange(n, dtype=np.int32),
        "i_current_price": price,
        "i_wholesale_cost": np.maximum(price * 7 // 10, 2),
        "i_brand_id": (cat + 1) * 1_000_000 + (cls_in_cat + 1) * 1000 + k,
        "i_brand": brand_code.astype(np.int32),
        "i_class_id": cls_in_cat + 1,
        "i_class": class_code.astype(np.int32),
        "i_category_id": cat + 1,
        "i_category": cat.astype(np.int32),
        "i_manufact_id": manufact,
        "i_manufact": (manufact - 1).astype(np.int32),
        "i_size": rng.integers(0, len(SIZES), n).astype(np.int32),
        "i_formulation": np.arange(n, dtype=np.int32),
        "i_color": rng.integers(0, len(COLORS), n).astype(np.int32),
        "i_units": rng.integers(0, len(UNITS), n).astype(np.int32),
        "i_container": np.zeros(n, dtype=np.int32),
        "i_manager_id": rng.integers(1, 101, n),
        "i_product_name": np.arange(n, dtype=np.int32),
    }
    dicts = {
        "i_item_id": _ids(np.arange(1, n // 2 + 1)),
        "i_item_desc": [f"{COLORS[i % 92]} item {i}" for i in range(n)],
        "i_brand": brands, "i_class": CLASS_NAMES,
        "i_category": CATEGORY_NAMES,
        "i_manufact": _word(range(1, 1001)),
        "i_size": SIZES,
        "i_formulation": [f"{i:08d}{COLORS[i % 92]}"[:20]
                          for i in range(n)],
        "i_color": COLORS, "i_units": UNITS, "i_container": ["Unknown"],
        "i_product_name": _word(range(1, n + 1)),
    }
    return cols, dicts


def gen_customer_demographics(sf: float) -> tuple:
    pe = purchase_estimates(sf)
    sizes = [2, 5, 7, len(pe), 4, 7, 7, 7]   # gender varies fastest
    n = int(np.prod(sizes))
    idx = np.arange(n, dtype=np.int64)
    digits = []
    for s in sizes:
        digits.append(idx % s)
        idx //= s
    cols = {
        "cd_demo_sk": np.arange(1, n + 1, dtype=np.int64),
        "cd_gender": digits[0].astype(np.int32),
        "cd_marital_status": digits[1].astype(np.int32),
        "cd_education_status": digits[2].astype(np.int32),
        "cd_purchase_estimate": np.array(pe, dtype=np.int64)[digits[3]],
        "cd_credit_rating": digits[4].astype(np.int32),
        "cd_dep_count": digits[5],
        "cd_dep_employed_count": digits[6],
        "cd_dep_college_count": digits[7],
    }
    dicts = {"cd_gender": GENDERS, "cd_marital_status": MARITAL,
             "cd_education_status": EDUCATION, "cd_credit_rating": CREDIT}
    return cols, dicts


def gen_store_sales(sf: float, seed: int):
    """(columns, dictionaries) of store_sales: uniform keys over the other
    tables (dates in the sales window), the measures by the
    specification's pricing rules (3.6: list = wholesale x (1 + markup),
    sales = list x (1 - discount), the extended amounts x quantity, net
    profit = net paid - extended wholesale cost)."""
    rng = np.random.default_rng([seed, 1])
    n = n_rows("store_sales", sf)
    lo, hi = date_sk(SALES_LO), date_sk(SALES_HI)
    qty = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)
    markup = rng.integers(0, 201, n)                  # percent
    listp = wholesale * (100 + markup) // 100
    discount = rng.integers(0, 101, n)                # percent
    sales = listp * (100 - discount) // 100
    ext_sales = sales * qty
    ext_whole = wholesale * qty
    ext_list = listp * qty
    tax = ext_sales * rng.integers(0, 10, n) // 100
    coupon = np.where(rng.random(n) < 0.2,
                      ext_sales * rng.integers(0, 101, n) // 100, 0)
    net_paid = ext_sales - coupon
    cols = {
        "ss_sold_date_sk": rng.integers(lo, hi + 1, n),
        "ss_sold_time_sk": rng.integers(0, 86_400, n),
        "ss_item_sk": rng.integers(1, n_rows("item", sf) + 1, n),
        "ss_customer_sk": rng.integers(1, max(int(100_000 * sf), 100) + 1,
                                       n),
        "ss_cdemo_sk": rng.integers(
            1, n_rows("customer_demographics", sf) + 1, n),
        "ss_hdemo_sk": rng.integers(1, 7201, n),
        "ss_addr_sk": rng.integers(1, max(int(50_000 * sf), 100) + 1, n),
        "ss_store_sk": rng.integers(1, 13, n),
        "ss_promo_sk": rng.integers(1, 301, n),
        "ss_ticket_number": np.arange(n, dtype=np.int64) // 12 + 1,
        "ss_quantity": qty,
        "ss_wholesale_cost": wholesale,
        "ss_list_price": listp,
        "ss_sales_price": sales,
        "ss_ext_discount_amt": (listp - sales) * qty,
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_whole,
        "ss_ext_list_price": ext_list,
        "ss_ext_tax": tax,
        "ss_coupon_amt": coupon,
        "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": net_paid + tax,
        "ss_net_profit": net_paid - ext_whole,
    }
    return cols, {}


_MAKERS = {"date_dim": lambda sf, seed: gen_date_dim(),
           "store": lambda sf, seed: gen_store(seed),
           "item": gen_item,
           "customer_demographics": lambda sf, seed:
           gen_customer_demographics(sf),
           "store_sales": gen_store_sales}

PARSER = "cockroach_tpu.sql.parser"


def require_rollup() -> None:
    parser = sys.modules.get(PARSER)
    if parser is not None and not hasattr(
            getattr(parser, "Parser", None), "parse_group_by"):
        raise SystemExit(
            "generators/tpcds.py: this program's parser has no GROUP BY "
            f"ROLLUP ({PARSER}.Parser.parse_group_by): it answers TPC-DS "
            "Q27, Q36 and Q67 with an error. Refusing to start it.")


def generate(table: str, sf: float, seed: int):
    """(columns, dictionaries) of one table: numpy arrays in stored form
    and, for each STRING column, the list its int32 codes index."""
    require_rollup()
    return _MAKERS[table](sf, seed)
