"""TPC-DS's store channel (TPC-DS specification v3.2.0), the subtotal
and ranking reports over it: Q27, Q36, Q67 and Q89 of Appendix B.

Five of the specification's 24 tables, every column at its declared
type (section 2.3, 2.4): the `store_sales` fact table (23 columns) and
the `item` (22), `date_dim` (28), `store` (29) and
`customer_demographics` (9) dimensions, at Table 3-2's row counts
(2,880,404 store_sales and 18,000 items a scale factor, 12 stores at
SF1, every day of 1900-01-02 to 2100-01-01, the 1,920,800 rows of the
demographics' cross product). Synthetic, not `dsdgen`: seeded numpy
columns with the specification's value domains where it gives them
(the demographics' domains, the category / class hierarchy, the sales
window 1998-01-02 to 2003-01-02, d_month_seq 1200 = January 2000, two
versions of every item and store id, every store in 'TN'), and
uniform draws where it does not. Money is DECIMAL(7,2) as integer
hundredths. `null_share` blanks that share of store_sales' foreign
keys and measures, as dsdgen does (what GROUPING() must tell from a
rolled-up NULL).

The four queries at the qualification substitutions, and a plain numpy
integer oracle `ref_q*` for each that computes every grouping set on
its own from the fact rows. Mirrors workload/ssb.py.
"""

from __future__ import annotations

import datetime
from fractions import Fraction

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

# Appendix B at the qualification substitutions (Q27 YEAR 2002, Q36
# 2001, Q67 DMS 1200, Q89 1999, every state 'TN')
Q27 = """
select i_item_id,
       s_state, grouping(s_state) g_state,
       avg(ss_quantity) agg1,
       avg(ss_list_price) agg2,
       avg(ss_coupon_amt) agg3,
       avg(ss_sales_price) agg4
from store_sales, customer_demographics, date_dim, store, item
where ss_sold_date_sk = d_date_sk and
      ss_item_sk = i_item_sk and
      ss_store_sk = s_store_sk and
      ss_cdemo_sk = cd_demo_sk and
      cd_gender = '{gen}' and
      cd_marital_status = '{ms}' and
      cd_education_status = '{es}' and
      d_year = {year} and
      s_state in ('TN','TN', 'TN', 'TN', 'TN', 'TN')
group by rollup (i_item_id, s_state)
order by i_item_id, s_state
limit 100"""

Q36 = """
select sum(ss_net_profit)/sum(ss_ext_sales_price) as gross_margin,
       i_category,
       i_class,
       grouping(i_category)+grouping(i_class) as lochierarchy,
       rank() over (
           partition by grouping(i_category)+grouping(i_class),
           case when grouping(i_class) = 0 then i_category end
           order by sum(ss_net_profit)/sum(ss_ext_sales_price) asc)
           as rank_within_parent
from store_sales, date_dim d1, item, store
where d1.d_year = {year}
  and d1.d_date_sk = ss_sold_date_sk
  and i_item_sk = ss_item_sk
  and s_store_sk = ss_store_sk
  and s_state in ('TN','TN','TN','TN','TN','TN','TN','TN')
group by rollup(i_category, i_class)
order by lochierarchy desc,
         case when lochierarchy = 0 then i_category end,
         rank_within_parent
limit 100"""

Q67 = """
select *
from (select i_category, i_class, i_brand, i_product_name, d_year,
             d_qoy, d_moy, s_store_id, sumsales,
             rank() over (partition by i_category
                          order by sumsales desc) rk
      from (select i_category, i_class, i_brand, i_product_name,
                   d_year, d_qoy, d_moy, s_store_id,
                   sum(coalesce(ss_sales_price*ss_quantity,0)) sumsales
            from store_sales, date_dim, store, item
            where ss_sold_date_sk=d_date_sk
              and ss_item_sk=i_item_sk
              and ss_store_sk = s_store_sk
              and d_month_seq between {dms} and {dms}+11
            group by rollup(i_category, i_class, i_brand,
                            i_product_name, d_year, d_qoy, d_moy,
                            s_store_id)) dw1) dw2
where rk <= 100
order by i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
         d_moy, s_store_id, sumsales, rk
limit 100"""

Q89 = """
select *
from (select i_category, i_class, i_brand, s_store_name,
             s_company_name, d_moy,
             sum(ss_sales_price) sum_sales,
             avg(sum(ss_sales_price)) over
                 (partition by i_category, i_brand, s_store_name,
                               s_company_name) avg_monthly_sales
      from item, store_sales, date_dim, store
      where ss_item_sk = i_item_sk and
            ss_sold_date_sk = d_date_sk and
            ss_store_sk = s_store_sk and
            d_year in ({year}) and
            ((i_category in ('Books','Electronics','Sports') and
              i_class in ('computers','stereo','football'))
             or (i_category in ('Men','Jewelry','Women') and
                 i_class in ('shirts','birdal','dresses')))
      group by i_category, i_class, i_brand, s_store_name,
               s_company_name, d_moy) tmp1
where case when (avg_monthly_sales <> 0)
           then (abs(sum_sales - avg_monthly_sales) / avg_monthly_sales)
           else null end > 0.1
order by sum_sales - avg_monthly_sales, s_store_name
limit 100"""

QUALIFICATION = {"q27": {"gen": "M", "ms": "S", "es": "College",
                         "year": 2002},
                 "q36": {"year": 2001}, "q67": {"dms": 1200},
                 "q89": {"year": 1999}}
QUERIES = {"q27": Q27, "q36": Q36, "q67": Q67, "q89": Q89}


def query(name: str, **params) -> str:
    return QUERIES[name].format(**(QUALIFICATION[name] | params))


# -- the generator -----------------------------------------------------------

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


NULLABLE_SALES = ["ss_sold_date_sk", "ss_cdemo_sk", "ss_store_sk",
                  "ss_quantity", "ss_list_price", "ss_sales_price",
                  "ss_coupon_amt", "ss_ext_sales_price", "ss_net_profit"]


def gen_store_sales(sf: float, seed: int, null_share: float = 0.0):
    """(columns, validity) of store_sales: uniform keys over the other
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
    valid = {}
    if null_share > 0:
        for c in NULLABLE_SALES:
            valid[c] = rng.random(n) >= null_share
    return cols, valid


def generate(sf: float = 0.01, seed: int = 0, null_share: float = 0.0):
    """{table: (columns, dictionaries, validity)}."""
    out = {"date_dim": gen_date_dim() + ({},),
           "store": gen_store(seed) + ({},),
           "item": gen_item(sf, seed) + ({},),
           "customer_demographics": gen_customer_demographics(sf) + ({},)}
    cols, valid = gen_store_sales(sf, seed, null_share)
    out["store_sales"] = (cols, {}, valid)
    return out


def load(engine, sf: float = 0.01, seed: int = 0, null_share: float = 0.0,
         tables=None) -> dict:
    """Create, ingest and ANALYZE the five tables; returns generate()'s
    tables."""
    data = tables if tables is not None else generate(sf, seed, null_share)
    ts = engine.clock.now()
    for t in TABLE_ORDER:
        cols, dicts, valid = data[t]
        engine.execute(DDL[t])
        for col, values in dicts.items():
            engine.store.set_dictionary(t, col, values)
        engine.store.insert_columns(t, cols, ts, valid=valid or None)
        engine.execute(f"ANALYZE {t}")
    return data


# -- the integer oracles -------------------------------------------------------
# Every grouping set is computed on its own from the fact rows. A value
# is None where SQL has NULL; a string key is its text; money is an
# integer of hundredths, an AVG a Fraction, a quotient of sums a Fraction.

def _text(data, table, col, rows):
    cols, dicts, _ = data[table]
    return np.asarray(dicts[col], dtype=object)[cols[col][rows]]


def _valid(data, table, col, n):
    v = data[table][2].get(col)
    return np.ones(n, dtype=bool) if v is None else v


def _dim_rows(data, table, key_col, fk):
    """Row of `table` each fact key joins (-1: none), by its dense
    1-based surrogate key."""
    keys = data[table][0][key_col]
    rows = np.searchsorted(keys, fk)
    rows = np.clip(rows, 0, len(keys) - 1)
    return np.where(keys[rows] == fk, rows, -1)


def _star(data, *, date=None, item=None, store=None, cdemo=None):
    """(mask over store_sales, {dimension: row per fact row}): the fact
    rows whose keys are valid and join rows that each mask keeps."""
    ss = data["store_sales"][0]
    n = len(ss["ss_item_sk"])
    m = np.ones(n, dtype=bool)
    rows = {}
    for table, key, fk, keep in (
            ("date_dim", "d_date_sk", "ss_sold_date_sk", date),
            ("item", "i_item_sk", "ss_item_sk", item),
            ("store", "s_store_sk", "ss_store_sk", store),
            ("customer_demographics", "cd_demo_sk", "ss_cdemo_sk", cdemo)):
        if keep is None:
            continue
        r = _dim_rows(data, table, key, ss[fk])
        m &= _valid(data, "store_sales", fk, n) & (r >= 0)
        r = np.where(r >= 0, r, 0)
        m &= keep[r]
        rows[table] = r
    return m, rows


def _rollup_sets(k: int) -> list:
    """ROLLUP(k1..kk): the prefixes, longest first."""
    return [tuple(range(j)) for j in range(k, -1, -1)]


def _grouped(keys: list, live: np.ndarray, aggs) -> dict:
    """{key tuple: aggs(row mask)} over the rows `live` keeps, keys
    from per-row value arrays."""
    out: dict = {}
    idx = np.flatnonzero(live)
    if not keys:
        return {(): aggs(idx)}
    tuples = list(zip(*[k[idx].tolist() for k in keys]))
    groups: dict = {}
    for i, t in zip(idx.tolist(), tuples):
        groups.setdefault(t, []).append(i)
    for t, rows in groups.items():
        out[t] = aggs(np.array(rows, dtype=np.int64))
    return out


def _sum(x, v):
    """SQL SUM over the rows' values: None where none is valid."""
    return int(x[v].sum()) if v.any() else None


def _avg(x, v, scale=1):
    """SQL AVG in the column's own units (a DECIMAL(7,2)'s scale 100)."""
    return (Fraction(int(x[v].sum()), int(v.sum()) * scale)
            if v.any() else None)


def _sort_key(v, desc=False):
    """pg order for one value: NULLS LAST ascending, FIRST descending
    (sort_batch's convention)."""
    if v is None:
        return (1, 0) if not desc else (0, 0)
    return (0, v) if not desc else (1, _Neg(v))


class _Neg:
    def __init__(self, v):
        self.v = v

    def __lt__(self, o):
        return self.v > o.v

    def __eq__(self, o):
        return self.v == o.v


def ref_q27(data, gen="M", ms="S", es="College", year=2002) -> list:
    cd, cdd, _ = data["customer_demographics"]
    dd = data["date_dim"][0]
    cmask = ((cd["cd_gender"] == cdd["cd_gender"].index(gen))
             & (cd["cd_marital_status"]
                == cdd["cd_marital_status"].index(ms))
             & (cd["cd_education_status"]
                == cdd["cd_education_status"].index(es)))
    st, std, _ = data["store"]
    smask = np.asarray(std["s_state"], dtype=object)[st["s_state"]] == "TN"
    m, rows = _star(data, date=dd["d_year"] == year,
                    item=np.ones(len(data["item"][0]["i_item_sk"]), bool),
                    store=smask, cdemo=cmask)
    ss = data["store_sales"][0]
    n = len(m)
    item_id = _text(data, "item", "i_item_id", rows["item"])
    state = _text(data, "store", "s_state", rows["store"])
    cols = [("ss_quantity", 1), ("ss_list_price", 100),
            ("ss_coupon_amt", 100), ("ss_sales_price", 100)]
    vals = [(ss[c], _valid(data, "store_sales", c, n), sc)
            for c, sc in cols]

    def aggs(r):
        return [_avg(x[r], v[r], sc) for x, v, sc in vals]
    out = []
    for gset in _rollup_sets(2):
        keys = [item_id, state][:len(gset)]
        for t, a in _grouped(keys, m, aggs).items():
            full = list(t) + [None] * (2 - len(t))
            out.append(full + [0 if len(gset) == 2 else 1] + a)
    out.sort(key=lambda r: (_sort_key(r[0]), _sort_key(r[1])))
    return out[:100]


def ref_q36(data, year=2001) -> list:
    dd = data["date_dim"][0]
    st, std, _ = data["store"]
    smask = np.asarray(std["s_state"], dtype=object)[st["s_state"]] == "TN"
    m, rows = _star(data, date=dd["d_year"] == year,
                    item=np.ones(len(data["item"][0]["i_item_sk"]), bool),
                    store=smask)
    ss = data["store_sales"][0]
    n = len(m)
    cat = _text(data, "item", "i_category", rows["item"])
    cls = _text(data, "item", "i_class", rows["item"])
    p, pv = ss["ss_net_profit"], _valid(data, "store_sales",
                                        "ss_net_profit", n)
    s, sv = ss["ss_ext_sales_price"], _valid(data, "store_sales",
                                             "ss_ext_sales_price", n)

    def aggs(r):
        num, den = _sum(p[r], pv[r]), _sum(s[r], sv[r])
        return None if num is None or den is None or den == 0 \
            else Fraction(num, den)
    rows_out = []
    for gset in _rollup_sets(2):
        keys = [cat, cls][:len(gset)]
        for t, gm in _grouped(keys, m, aggs).items():
            full = list(t) + [None] * (2 - len(t))
            loch = 2 - len(gset)
            rows_out.append([gm, full[0], full[1], loch])
    # rank() over (partition by lochierarchy, category-if-class-kept
    # order by gross margin asc, NULLS LAST)
    parts: dict = {}
    for r in rows_out:
        pk = (r[3], r[1] if len(r) and r[3] == 0 else None)
        parts.setdefault(pk, []).append(r)
    for rs in parts.values():
        rs.sort(key=lambda r: _sort_key(r[0]))
        for i, r in enumerate(rs):
            peer = i
            while peer > 0 and rs[peer - 1][0] == r[0]:
                peer -= 1
            r.append(peer + 1)
    rows_out.sort(key=lambda r: (_sort_key(r[3], desc=True),
                                 _sort_key(r[1] if r[3] == 0 else None),
                                 _sort_key(r[4])))
    return rows_out[:100]


def ref_q67(data, dms=1200) -> list:
    dd = data["date_dim"][0]
    m, rows = _star(data, date=(dd["d_month_seq"] >= dms)
                    & (dd["d_month_seq"] <= dms + 11),
                    item=np.ones(len(data["item"][0]["i_item_sk"]), bool),
                    store=np.ones(12, bool))
    ss = data["store_sales"][0]
    n = len(m)
    keys = [_text(data, "item", c, rows["item"])
            for c in ("i_category", "i_class", "i_brand", "i_product_name")]
    keys += [dd[c][rows["date_dim"]] for c in ("d_year", "d_qoy", "d_moy")]
    keys.append(_text(data, "store", "s_store_id", rows["store"]))
    price = ss["ss_sales_price"]
    qty = ss["ss_quantity"]
    ok = _valid(data, "store_sales", "ss_sales_price", n) \
        & _valid(data, "store_sales", "ss_quantity", n)
    amount = np.where(ok, price * qty, 0)

    def aggs(r):
        return Fraction(int(amount[r].sum()), 100)
    out = []
    for gset in _rollup_sets(8):
        for t, total in _grouped(keys[:len(gset)], m, aggs).items():
            out.append(list(t) + [None] * (8 - len(t)) + [total])
    parts: dict = {}
    for r in out:
        parts.setdefault(r[0], []).append(r)
    for rs in parts.values():
        rs.sort(key=lambda r: -r[8])
        for i, r in enumerate(rs):
            peer = i
            while peer > 0 and rs[peer - 1][8] == r[8]:
                peer -= 1
            r.append(peer + 1)
    out = [r for r in out if r[9] <= 100]
    out.sort(key=lambda r: tuple(_sort_key(v) for v in r))
    return out[:100]


def ref_q89(data, year=1999) -> list:
    dd = data["date_dim"][0]
    it, itd, _ = data["item"]
    cat = np.asarray(itd["i_category"], dtype=object)[it["i_category"]]
    cls = np.asarray(itd["i_class"], dtype=object)[it["i_class"]]
    imask = ((np.isin(cat, ["Books", "Electronics", "Sports"])
              & np.isin(cls, ["computers", "stereo", "football"]))
             | (np.isin(cat, ["Men", "Jewelry", "Women"])
                & np.isin(cls, ["shirts", "birdal", "dresses"])))
    m, rows = _star(data, date=dd["d_year"] == year, item=imask,
                    store=np.ones(12, bool))
    ss = data["store_sales"][0]
    n = len(m)
    keys = [_text(data, "item", c, rows["item"])
            for c in ("i_category", "i_class", "i_brand")]
    keys += [_text(data, "store", c, rows["store"])
             for c in ("s_store_name", "s_company_name")]
    keys.append(dd["d_moy"][rows["date_dim"]])
    x = ss["ss_sales_price"]
    xv = _valid(data, "store_sales", "ss_sales_price", n)
    groups = _grouped(keys, m, lambda r: _sum(x[r], xv[r]))
    parts: dict = {}
    for t, s in groups.items():
        parts.setdefault((t[0], t[2], t[3], t[4]), []).append(s)
    out = []
    for t, s in groups.items():
        sums = [v for v in parts[(t[0], t[2], t[3], t[4])] if v is not None]
        avg = Fraction(sum(sums), len(sums)) if sums else None
        if s is None or not avg:
            continue
        if abs(s - avg) / avg > Fraction(1, 10):
            out.append(list(t) + [Fraction(s, 100), avg / 100])
    out.sort(key=lambda r: (r[6] - r[7], r[3]))
    return out[:100]


ORACLES = {"q27": ref_q27, "q36": ref_q36, "q67": ref_q67, "q89": ref_q89}
