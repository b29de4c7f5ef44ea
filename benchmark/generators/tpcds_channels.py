"""TPC-DS's three sales channels with their returns, made from one seed.

`store_sales`, `item`, `date_dim`, `store` (and `customer_demographics`)
are generators/tpcds.py's, array for array: this module hands them on.
It adds the other eight tables the cross-channel reports (Appendix B's
queries 5, 77 and 80) read, every column at the declared type of the
TPC-DS specification v3.2.0 (sections 2.3, 2.4), at Table 3-2's row
counts for the scale factor: `store_returns` (20 columns, 287,514 rows
at SF1), `catalog_sales` (34, 1,441,548), `catalog_returns` (27,
144,067), `web_sales` (34, 719,384), `web_returns` (24, 71,763),
`promotion` (19, 300), `catalog_page` (9, 11,718), `web_site` (26, 30)
and `web_page` (14, 60).

Synthetic, not `dsdgen`. A catalog order is ten lines and a web order
eight, with ten or eight different items, so that (item, order) is a
key of the sales table, as the specification's primary key makes it;
every line of an order shares its date, customer, call centre, catalog
page, web site and web page. A return is a sales line drawn without
replacement among the lines whose (item, ticket) or (item, order) no
earlier line holds, so that the pair is a key of the returns table
too, and every return joins the line it returns: the return's item,
ticket or order, store, page and call centre are the line's, its date
one to ninety days after the sale, its quantity one to the line's, its
amount the line's price for that quantity. Pricing follows tpcds.py's
rules (specification 3.6). Every column leaves here in the form the
store keeps it in (DECIMAL as int64 hundredths, DATE as int32 days, a
string as int32 codes into a dictionary returned beside the columns);
no value is NULL (run.py's ingest hands the store no validity masks),
and the integer references (statements/ch_q*.py, chref.py) read the
very arrays the engine ingests.

Imports nothing of the program. Found by the name a configuration
gives under `generator`; offers DDL, TABLE_ORDER and generate().

One refusal, as generators/tpcds.py has one. Every statement of the
configuration reads a UNION ALL of the three channels under a ROLLUP.
A program that answers a UNION ALL by running each branch as a
statement of its own and merging decoded rows on the host, and a WITH
by materializing temp tables on every execution, uploads and compiles
inside the measured window and joins a sales line to its return in
the while-loop hash table (about 26 s a statement where TPC-H Q9 does
it): it cannot run the configuration inside the check's time. The mark
of a program that plans a UNION ALL on the device is
`cockroach_tpu.sql.plan.UnionAll`; the program keeps that name while a
configuration names this generator, and such a program says so in its
first seconds with an exit code other than 0. The reference worker
imports no part of the program, so nothing is asked there.
"""

from __future__ import annotations

import datetime
import sys

import numpy as np

from generators import tpcds
from generators.tpcds import EPOCH, _ids, _word, date_sk

DDL = dict(tpcds.DDL)
DDL.update({
    "store_returns": """
CREATE TABLE store_returns (
    sr_returned_date_sk   INT8,
    sr_return_time_sk     INT8,
    sr_item_sk            INT8 NOT NULL,
    sr_customer_sk        INT8,
    sr_cdemo_sk           INT8,
    sr_hdemo_sk           INT8,
    sr_addr_sk            INT8,
    sr_store_sk           INT8,
    sr_reason_sk          INT8,
    sr_ticket_number      INT8 NOT NULL,
    sr_return_quantity    INT8,
    sr_return_amt         DECIMAL(7,2),
    sr_return_tax         DECIMAL(7,2),
    sr_return_amt_inc_tax DECIMAL(7,2),
    sr_fee                DECIMAL(7,2),
    sr_return_ship_cost   DECIMAL(7,2),
    sr_refunded_cash      DECIMAL(7,2),
    sr_reversed_charge    DECIMAL(7,2),
    sr_store_credit       DECIMAL(7,2),
    sr_net_loss           DECIMAL(7,2)
)""",
    "catalog_sales": """
CREATE TABLE catalog_sales (
    cs_sold_date_sk          INT8,
    cs_sold_time_sk          INT8,
    cs_ship_date_sk          INT8,
    cs_bill_customer_sk      INT8,
    cs_bill_cdemo_sk         INT8,
    cs_bill_hdemo_sk         INT8,
    cs_bill_addr_sk          INT8,
    cs_ship_customer_sk      INT8,
    cs_ship_cdemo_sk         INT8,
    cs_ship_hdemo_sk         INT8,
    cs_ship_addr_sk          INT8,
    cs_call_center_sk        INT8,
    cs_catalog_page_sk       INT8,
    cs_ship_mode_sk          INT8,
    cs_warehouse_sk          INT8,
    cs_item_sk               INT8 NOT NULL,
    cs_promo_sk              INT8,
    cs_order_number          INT8 NOT NULL,
    cs_quantity              INT8,
    cs_wholesale_cost        DECIMAL(7,2),
    cs_list_price            DECIMAL(7,2),
    cs_sales_price           DECIMAL(7,2),
    cs_ext_discount_amt      DECIMAL(7,2),
    cs_ext_sales_price       DECIMAL(7,2),
    cs_ext_wholesale_cost    DECIMAL(7,2),
    cs_ext_list_price        DECIMAL(7,2),
    cs_ext_tax               DECIMAL(7,2),
    cs_coupon_amt            DECIMAL(7,2),
    cs_ext_ship_cost         DECIMAL(7,2),
    cs_net_paid              DECIMAL(7,2),
    cs_net_paid_inc_tax      DECIMAL(7,2),
    cs_net_paid_inc_ship     DECIMAL(7,2),
    cs_net_paid_inc_ship_tax DECIMAL(7,2),
    cs_net_profit            DECIMAL(7,2)
)""",
    "catalog_returns": """
CREATE TABLE catalog_returns (
    cr_returned_date_sk      INT8,
    cr_returned_time_sk      INT8,
    cr_item_sk               INT8 NOT NULL,
    cr_refunded_customer_sk  INT8,
    cr_refunded_cdemo_sk     INT8,
    cr_refunded_hdemo_sk     INT8,
    cr_refunded_addr_sk      INT8,
    cr_returning_customer_sk INT8,
    cr_returning_cdemo_sk    INT8,
    cr_returning_hdemo_sk    INT8,
    cr_returning_addr_sk     INT8,
    cr_call_center_sk        INT8,
    cr_catalog_page_sk       INT8,
    cr_ship_mode_sk          INT8,
    cr_warehouse_sk          INT8,
    cr_reason_sk             INT8,
    cr_order_number          INT8 NOT NULL,
    cr_return_quantity       INT8,
    cr_return_amount         DECIMAL(7,2),
    cr_return_tax            DECIMAL(7,2),
    cr_return_amt_inc_tax    DECIMAL(7,2),
    cr_fee                   DECIMAL(7,2),
    cr_return_ship_cost      DECIMAL(7,2),
    cr_refunded_cash         DECIMAL(7,2),
    cr_reversed_charge       DECIMAL(7,2),
    cr_store_credit          DECIMAL(7,2),
    cr_net_loss              DECIMAL(7,2)
)""",
    "web_sales": """
CREATE TABLE web_sales (
    ws_sold_date_sk          INT8,
    ws_sold_time_sk          INT8,
    ws_ship_date_sk          INT8,
    ws_item_sk               INT8 NOT NULL,
    ws_bill_customer_sk      INT8,
    ws_bill_cdemo_sk         INT8,
    ws_bill_hdemo_sk         INT8,
    ws_bill_addr_sk          INT8,
    ws_ship_customer_sk      INT8,
    ws_ship_cdemo_sk         INT8,
    ws_ship_hdemo_sk         INT8,
    ws_ship_addr_sk          INT8,
    ws_web_page_sk           INT8,
    ws_web_site_sk           INT8,
    ws_ship_mode_sk          INT8,
    ws_warehouse_sk          INT8,
    ws_promo_sk              INT8,
    ws_order_number          INT8 NOT NULL,
    ws_quantity              INT8,
    ws_wholesale_cost        DECIMAL(7,2),
    ws_list_price            DECIMAL(7,2),
    ws_sales_price           DECIMAL(7,2),
    ws_ext_discount_amt      DECIMAL(7,2),
    ws_ext_sales_price       DECIMAL(7,2),
    ws_ext_wholesale_cost    DECIMAL(7,2),
    ws_ext_list_price        DECIMAL(7,2),
    ws_ext_tax               DECIMAL(7,2),
    ws_coupon_amt            DECIMAL(7,2),
    ws_ext_ship_cost         DECIMAL(7,2),
    ws_net_paid              DECIMAL(7,2),
    ws_net_paid_inc_tax      DECIMAL(7,2),
    ws_net_paid_inc_ship     DECIMAL(7,2),
    ws_net_paid_inc_ship_tax DECIMAL(7,2),
    ws_net_profit            DECIMAL(7,2)
)""",
    "web_returns": """
CREATE TABLE web_returns (
    wr_returned_date_sk      INT8,
    wr_returned_time_sk      INT8,
    wr_item_sk               INT8 NOT NULL,
    wr_refunded_customer_sk  INT8,
    wr_refunded_cdemo_sk     INT8,
    wr_refunded_hdemo_sk     INT8,
    wr_refunded_addr_sk      INT8,
    wr_returning_customer_sk INT8,
    wr_returning_cdemo_sk    INT8,
    wr_returning_hdemo_sk    INT8,
    wr_returning_addr_sk     INT8,
    wr_web_page_sk           INT8,
    wr_reason_sk             INT8,
    wr_order_number          INT8 NOT NULL,
    wr_return_quantity       INT8,
    wr_return_amt            DECIMAL(7,2),
    wr_return_tax            DECIMAL(7,2),
    wr_return_amt_inc_tax    DECIMAL(7,2),
    wr_fee                   DECIMAL(7,2),
    wr_return_ship_cost      DECIMAL(7,2),
    wr_refunded_cash         DECIMAL(7,2),
    wr_reversed_charge       DECIMAL(7,2),
    wr_account_credit        DECIMAL(7,2),
    wr_net_loss              DECIMAL(7,2)
)""",
    "promotion": """
CREATE TABLE promotion (
    p_promo_sk        INT8 NOT NULL PRIMARY KEY,
    p_promo_id        CHAR(16) NOT NULL,
    p_start_date_sk   INT8,
    p_end_date_sk     INT8,
    p_item_sk         INT8,
    p_cost            DECIMAL(15,2),
    p_response_target INT8,
    p_promo_name      CHAR(50),
    p_channel_dmail   CHAR(1),
    p_channel_email   CHAR(1),
    p_channel_catalog CHAR(1),
    p_channel_tv      CHAR(1),
    p_channel_radio   CHAR(1),
    p_channel_press   CHAR(1),
    p_channel_event   CHAR(1),
    p_channel_demo    CHAR(1),
    p_channel_details VARCHAR(100),
    p_purpose         CHAR(15),
    p_discount_active CHAR(1)
)""",
    "catalog_page": """
CREATE TABLE catalog_page (
    cp_catalog_page_sk     INT8 NOT NULL PRIMARY KEY,
    cp_catalog_page_id     CHAR(16) NOT NULL,
    cp_start_date_sk       INT8,
    cp_end_date_sk         INT8,
    cp_department          VARCHAR(50),
    cp_catalog_number      INT8,
    cp_catalog_page_number INT8,
    cp_description         VARCHAR(100),
    cp_type                VARCHAR(100)
)""",
    "web_site": """
CREATE TABLE web_site (
    web_site_sk        INT8 NOT NULL PRIMARY KEY,
    web_site_id        CHAR(16) NOT NULL,
    web_rec_start_date DATE,
    web_rec_end_date   DATE,
    web_name           VARCHAR(50),
    web_open_date_sk   INT8,
    web_close_date_sk  INT8,
    web_class          VARCHAR(50),
    web_manager        VARCHAR(40),
    web_mkt_id         INT8,
    web_mkt_class      VARCHAR(50),
    web_mkt_desc       VARCHAR(100),
    web_market_manager VARCHAR(40),
    web_company_id     INT8,
    web_company_name   CHAR(50),
    web_street_number  CHAR(10),
    web_street_name    VARCHAR(60),
    web_street_type    CHAR(15),
    web_suite_number   CHAR(10),
    web_city           VARCHAR(60),
    web_county         VARCHAR(30),
    web_state          CHAR(2),
    web_zip            CHAR(10),
    web_country        VARCHAR(20),
    web_gmt_offset     DECIMAL(5,2),
    web_tax_percentage DECIMAL(5,2)
)""",
    "web_page": """
CREATE TABLE web_page (
    wp_web_page_sk      INT8 NOT NULL PRIMARY KEY,
    wp_web_page_id      CHAR(16) NOT NULL,
    wp_rec_start_date   DATE,
    wp_rec_end_date     DATE,
    wp_creation_date_sk INT8,
    wp_access_date_sk   INT8,
    wp_autogen_flag     CHAR(1),
    wp_customer_sk      INT8,
    wp_url              VARCHAR(100),
    wp_type             CHAR(50),
    wp_char_count       INT8,
    wp_link_count       INT8,
    wp_image_count      INT8,
    wp_max_ad_count     INT8
)""",
})

TABLE_ORDER = tpcds.TABLE_ORDER + [
    "promotion", "catalog_page", "web_site", "web_page", "store_returns",
    "catalog_sales", "catalog_returns", "web_sales", "web_returns"]

# Table 3-2's rows a scale factor
PER_SF = {"store_returns": 287_514, "catalog_sales": 1_441_548,
          "catalog_returns": 144_067, "web_sales": 719_384,
          "web_returns": 71_763, "catalog_page": 11_718}
# fixed at SF1, the rows a test's small scale factor keeps too
FIXED = {"promotion": 300, "web_site": 30, "web_page": 60}
CALL_CENTERS = 6                     # call_center's rows at SF1
LINES = {"catalog_sales": 10, "web_sales": 8}
RETURNS_OF = {"store_returns": "store_sales",
              "catalog_returns": "catalog_sales",
              "web_returns": "web_sales"}
# (item column, ticket or order column) of each sales table
LINE_KEY = {"store_sales": ("ss_item_sk", "ss_ticket_number"),
            "catalog_sales": ("cs_item_sk", "cs_order_number"),
            "web_sales": ("ws_item_sk", "ws_order_number")}
RETURN_DAYS = 90


def n_rows(table: str, sf: float) -> int:
    if table in FIXED:
        return FIXED[table]
    if table in PER_SF:
        floor = 200 if table == "catalog_page" else 100
        return max(int(round(PER_SF[table] * sf)), floor)
    return tpcds.n_rows(table, sf)


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), 40 + TABLE_ORDER.index(table)])


def _yn(rng, n: int, p_yes: float = 0.5) -> np.ndarray:
    return (rng.random(n) < p_yes).astype(np.int32)


YN = ["N", "Y"]


def gen_promotion(seed: int) -> tuple:
    rng = _rng(seed, "promotion")
    n = FIXED["promotion"]
    start = rng.integers(date_sk(tpcds.SALES_LO), date_sk(tpcds.SALES_HI),
                         n)
    cols = {
        "p_promo_sk": np.arange(1, n + 1, dtype=np.int64),
        "p_promo_id": np.arange(n, dtype=np.int32),
        "p_start_date_sk": start,
        "p_end_date_sk": start + rng.integers(1, 61, n),
        "p_item_sk": rng.integers(1, tpcds.ITEM_PER_SF + 1, n),
        "p_cost": np.full(n, 100_000, dtype=np.int64),
        "p_response_target": np.ones(n, dtype=np.int64),
        "p_promo_name": rng.integers(0, 10, n).astype(np.int32),
    }
    for ch in ("dmail", "email", "catalog", "tv", "radio", "press",
               "event", "demo"):
        cols[f"p_channel_{ch}"] = _yn(rng, n)
    cols["p_channel_details"] = np.arange(n, dtype=np.int32)
    cols["p_purpose"] = np.zeros(n, dtype=np.int32)
    cols["p_discount_active"] = _yn(rng, n)
    dicts = {"p_promo_id": _ids(np.arange(1, n + 1)),
             "p_promo_name": tpcds.SYLLABLES,
             "p_channel_details": [f"promotion details {i}"
                                   for i in range(n)],
             "p_purpose": ["Unknown"], "p_discount_active": YN}
    for ch in ("dmail", "email", "catalog", "tv", "radio", "press",
               "event", "demo"):
        dicts[f"p_channel_{ch}"] = YN
    return cols, dicts


def gen_catalog_page(sf: float, seed: int) -> tuple:
    rng = _rng(seed, "catalog_page")
    n = n_rows("catalog_page", sf)
    sk = np.arange(1, n + 1, dtype=np.int64)
    per_catalog = 108
    start = date_sk(datetime.date(1998, 1, 1)) \
        + ((sk - 1) // per_catalog) * 30
    cols = {
        "cp_catalog_page_sk": sk,
        "cp_catalog_page_id": np.arange(n, dtype=np.int32),
        "cp_start_date_sk": start,
        "cp_end_date_sk": start + rng.integers(30, 181, n),
        "cp_department": np.zeros(n, dtype=np.int32),
        "cp_catalog_number": (sk - 1) // per_catalog + 1,
        "cp_catalog_page_number": (sk - 1) % per_catalog + 1,
        "cp_description": np.arange(n, dtype=np.int32),
        "cp_type": rng.integers(0, 3, n).astype(np.int32),
    }
    dicts = {"cp_catalog_page_id": _ids(sk),
             "cp_department": ["DEPARTMENT"],
             "cp_description": [f"catalog page description {i}"
                                for i in range(n)],
             "cp_type": ["bi-annual", "quarterly", "monthly"]}
    return cols, dicts


def gen_web_site(seed: int) -> tuple:
    rng = _rng(seed, "web_site")
    n = FIXED["web_site"]
    sk = np.arange(1, n + 1, dtype=np.int64)
    bkey = (sk - 1) // 2                 # two versions a site id
    start = np.where(sk % 2 == 1, (datetime.date(1997, 8, 16) - EPOCH).days,
                     (datetime.date(2000, 8, 16) - EPOCH).days)
    cols = {
        "web_site_sk": sk,
        "web_site_id": bkey.astype(np.int32),
        "web_rec_start_date": start.astype(np.int32),
        "web_rec_end_date": (start + 3 * 365).astype(np.int32),
        "web_name": bkey.astype(np.int32),
        "web_open_date_sk": np.full(n, date_sk(datetime.date(1997, 8, 16)),
                                    dtype=np.int64),
        "web_close_date_sk": np.full(n, date_sk(datetime.date(2003, 1, 2)),
                                     dtype=np.int64),
        "web_class": np.zeros(n, dtype=np.int32),
        "web_manager": np.arange(n, dtype=np.int32),
        "web_mkt_id": rng.integers(1, 7, n),
        "web_mkt_class": np.arange(n, dtype=np.int32),
        "web_mkt_desc": np.arange(n, dtype=np.int32),
        "web_market_manager": np.arange(n, dtype=np.int32),
        "web_company_id": rng.integers(1, 7, n),
        "web_company_name": rng.integers(0, 6, n).astype(np.int32),
        "web_street_number": np.arange(n, dtype=np.int32),
        "web_street_name": np.arange(n, dtype=np.int32),
        "web_street_type": rng.integers(0, 4, n).astype(np.int32),
        "web_suite_number": np.arange(n, dtype=np.int32),
        "web_city": rng.integers(0, 2, n).astype(np.int32),
        "web_county": np.zeros(n, dtype=np.int32),
        "web_state": np.zeros(n, dtype=np.int32),
        "web_zip": rng.integers(0, 2, n).astype(np.int32),
        "web_country": np.zeros(n, dtype=np.int32),
        "web_gmt_offset": np.full(n, -500, dtype=np.int64),
        "web_tax_percentage": rng.integers(0, 12, n),
    }
    dicts = {
        "web_site_id": _ids(np.arange(1, n // 2 + 1)),
        "web_name": [f"site_{i}" for i in range(n // 2)],
        "web_class": ["Unknown"],
        "web_manager": [f"manager {i}" for i in range(n)],
        "web_mkt_class": [f"market class {i}" for i in range(n)],
        "web_mkt_desc": [f"market description {i}" for i in range(n)],
        "web_market_manager": [f"market manager {i}" for i in range(n)],
        "web_company_name": tpcds.SYLLABLES[:6],
        "web_street_number": [str(100 + 37 * i) for i in range(n)],
        "web_street_name": [f"{w} " for w in _word(range(10, 10 + n))],
        "web_street_type": ["Street", "Avenue", "Boulevard", "Lane"],
        "web_suite_number": [f"Suite {10 * i}" for i in range(n)],
        "web_city": ["Midway", "Fairview"],
        "web_county": ["Williamson County"],
        "web_state": ["TN"],
        "web_zip": ["35709", "31904"],
        "web_country": ["United States"],
    }
    return cols, dicts


def gen_web_page(seed: int) -> tuple:
    rng = _rng(seed, "web_page")
    n = FIXED["web_page"]
    sk = np.arange(1, n + 1, dtype=np.int64)
    bkey = (sk - 1) // 2                 # two versions a page id
    start = np.where(sk % 2 == 1, (datetime.date(1997, 9, 3) - EPOCH).days,
                     (datetime.date(2000, 9, 3) - EPOCH).days)
    cols = {
        "wp_web_page_sk": sk,
        "wp_web_page_id": bkey.astype(np.int32),
        "wp_rec_start_date": start.astype(np.int32),
        "wp_rec_end_date": (start + 3 * 365).astype(np.int32),
        "wp_creation_date_sk": rng.integers(
            date_sk(datetime.date(1997, 9, 3)),
            date_sk(datetime.date(2000, 9, 3)), n),
        "wp_access_date_sk": rng.integers(
            date_sk(datetime.date(2000, 9, 3)),
            date_sk(datetime.date(2003, 1, 2)), n),
        "wp_autogen_flag": _yn(rng, n),
        "wp_customer_sk": rng.integers(1, 100_001, n),
        "wp_url": np.zeros(n, dtype=np.int32),
        "wp_type": rng.integers(0, 7, n).astype(np.int32),
        "wp_char_count": rng.integers(100, 8_001, n),
        "wp_link_count": rng.integers(2, 26, n),
        "wp_image_count": rng.integers(1, 8, n),
        "wp_max_ad_count": rng.integers(0, 5, n),
    }
    dicts = {"wp_web_page_id": _ids(np.arange(1, n // 2 + 1)),
             "wp_autogen_flag": YN, "wp_url": ["http://www.foo.com"],
             "wp_type": ["ad", "bio", "dynamic", "feedback", "general",
                         "order", "protected"]}
    return cols, dicts


def _order_lines(rng, n: int, lines: int, n_items: int) -> tuple:
    """(order number, item) of n sales lines in orders of `lines` lines,
    the items of an order all different: r, r + s, r + 2 s, ... modulo
    the item count, with r uniform and s in [1, items / lines)."""
    order = np.arange(n, dtype=np.int64) // lines + 1
    n_orders = int(order[-1])
    r = rng.integers(0, n_items, n_orders)
    s = rng.integers(1, max(n_items // lines, 2), n_orders)
    k = np.arange(n, dtype=np.int64) % lines
    item = (r[order - 1] + k * s[order - 1]) % n_items + 1
    return order, item


def _pricing(rng, n: int) -> dict:
    """tpcds.py's pricing rules (specification 3.6) for n lines."""
    qty = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)
    markup = rng.integers(0, 201, n)
    listp = wholesale * (100 + markup) // 100
    discount = rng.integers(0, 101, n)
    sales = listp * (100 - discount) // 100
    ext_sales = sales * qty
    tax = ext_sales * rng.integers(0, 10, n) // 100
    coupon = np.where(rng.random(n) < 0.2,
                      ext_sales * rng.integers(0, 101, n) // 100, 0)
    ship = wholesale * qty * rng.integers(0, 51, n) // 100
    return {"quantity": qty, "wholesale_cost": wholesale,
            "list_price": listp, "sales_price": sales,
            "ext_discount_amt": (listp - sales) * qty,
            "ext_sales_price": ext_sales,
            "ext_wholesale_cost": wholesale * qty,
            "ext_list_price": listp * qty, "ext_tax": tax,
            "coupon_amt": coupon, "ext_ship_cost": ship}


def _channel_sales(table: str, sf: float, seed: int) -> tuple:
    """catalog_sales or web_sales: orders of LINES lines, every line of
    an order sharing its date, customer and place."""
    rng = _rng(seed, table)
    p = "cs_" if table == "catalog_sales" else "ws_"
    n = n_rows(table, sf)
    n_items = tpcds.n_rows("item", sf)
    order, item = _order_lines(rng, n, LINES[table], n_items)
    n_orders = int(order[-1])

    def per_order(lo, hi):
        return rng.integers(lo, hi, n_orders)[order - 1]

    sold = per_order(date_sk(tpcds.SALES_LO), date_sk(tpcds.SALES_HI) + 1)
    cust = per_order(1, max(int(100_000 * sf), 100) + 1)
    addr = per_order(1, max(int(50_000 * sf), 100) + 1)
    cdemo = per_order(1, 1_920_801)
    hdemo = per_order(1, 7_201)
    m = _pricing(rng, n)
    net_paid = m["ext_sales_price"] - m["coupon_amt"]
    cols = {
        p + "sold_date_sk": sold,
        p + "sold_time_sk": per_order(0, 86_400),
        p + "ship_date_sk": sold + rng.integers(2, 91, n),
    }
    if table == "catalog_sales":
        cols.update({
            "cs_bill_customer_sk": cust, "cs_bill_cdemo_sk": cdemo,
            "cs_bill_hdemo_sk": hdemo, "cs_bill_addr_sk": addr,
            "cs_ship_customer_sk": cust, "cs_ship_cdemo_sk": cdemo,
            "cs_ship_hdemo_sk": hdemo, "cs_ship_addr_sk": addr,
            "cs_call_center_sk": per_order(1, CALL_CENTERS + 1),
            "cs_catalog_page_sk": per_order(
                1, n_rows("catalog_page", sf) + 1),
            "cs_ship_mode_sk": rng.integers(1, 21, n),
            "cs_warehouse_sk": rng.integers(1, 6, n),
            "cs_item_sk": item,
        })
    else:
        cols.update({
            "ws_item_sk": item,
            "ws_bill_customer_sk": cust, "ws_bill_cdemo_sk": cdemo,
            "ws_bill_hdemo_sk": hdemo, "ws_bill_addr_sk": addr,
            "ws_ship_customer_sk": cust, "ws_ship_cdemo_sk": cdemo,
            "ws_ship_hdemo_sk": hdemo, "ws_ship_addr_sk": addr,
            "ws_web_page_sk": per_order(1, FIXED["web_page"] + 1),
            "ws_web_site_sk": per_order(1, FIXED["web_site"] + 1),
            "ws_ship_mode_sk": rng.integers(1, 21, n),
            "ws_warehouse_sk": rng.integers(1, 6, n),
        })
    cols.update({
        p + "promo_sk": rng.integers(1, FIXED["promotion"] + 1, n),
        p + "order_number": order,
    })
    for k in ("quantity", "wholesale_cost", "list_price", "sales_price",
              "ext_discount_amt", "ext_sales_price", "ext_wholesale_cost",
              "ext_list_price", "ext_tax", "coupon_amt", "ext_ship_cost"):
        cols[p + k] = m[k]
    cols.update({
        p + "net_paid": net_paid,
        p + "net_paid_inc_tax": net_paid + m["ext_tax"],
        p + "net_paid_inc_ship": net_paid + m["ext_ship_cost"],
        p + "net_paid_inc_ship_tax": net_paid + m["ext_ship_cost"]
        + m["ext_tax"],
        p + "net_profit": net_paid - m["ext_wholesale_cost"],
    })
    order_cols = [ln.split()[0] for ln in DDL[table].splitlines()
                  if ln.startswith("    ")]
    return {c: cols[c] for c in order_cols}, {}


def returned_lines(table: str, sales: dict, sf: float, seed: int):
    """Rows of the sales table that are returned: drawn without
    replacement among the lines whose (item, ticket or order) pair no
    earlier line holds, in ascending row order."""
    rng = _rng(seed, table)
    item, ticket = (sales[c] for c in LINE_KEY[RETURNS_OF[table]])
    pair = np.unique(np.stack([ticket, item]), axis=1, return_index=True)[1]
    n = min(n_rows(table, sf), len(pair))
    return np.sort(rng.choice(pair, n, replace=False))


_SALES: dict = {}


def _sales_of(table: str, sf: float, seed: int) -> dict:
    """The sales table a returns table draws from, made once a run."""
    key = (table, sf, seed)
    if key not in _SALES:
        _SALES.clear()
        _SALES[key] = generate(table, sf, seed)[0]
    return _SALES[key]


def _returns(table: str, sf: float, seed: int) -> tuple:
    base = RETURNS_OF[table]
    sales = _sales_of(base, sf, seed)
    rows = returned_lines(table, sales, sf, seed)
    rng = np.random.default_rng([int(seed), 40 + TABLE_ORDER.index(table),
                                 1])
    n = len(rows)
    s = {"store_sales": "ss_", "catalog_sales": "cs_",
         "web_sales": "ws_"}[base]

    def line(col):
        return sales[s + col][rows]

    qty = rng.integers(1, line("quantity") + 1)
    amt = line("sales_price") * qty
    tax = amt * rng.integers(0, 10, n) // 100
    fee = rng.integers(50, 10_001, n)
    ship = line("wholesale_cost") * qty * rng.integers(0, 51, n) // 100
    cash = amt * rng.integers(0, 101, n) // 100
    charge = (amt - cash) * rng.integers(0, 101, n) // 100
    credit = amt - cash - charge
    loss = tax + fee + ship
    returned = line("sold_date_sk") + rng.integers(1, RETURN_DAYS + 1, n)
    time = rng.integers(0, 86_400, n)
    cust = rng.integers(1, max(int(100_000 * sf), 100) + 1, n)
    addr = rng.integers(1, max(int(50_000 * sf), 100) + 1, n)
    cdemo = rng.integers(1, 1_920_801, n)
    hdemo = rng.integers(1, 7_201, n)
    reason = rng.integers(1, 36, n)
    money = [amt, tax, amt + tax, fee, ship, cash, charge, credit, loss]
    if table == "store_returns":
        cols = dict(zip(
            ["sr_returned_date_sk", "sr_return_time_sk", "sr_item_sk",
             "sr_customer_sk", "sr_cdemo_sk", "sr_hdemo_sk", "sr_addr_sk",
             "sr_store_sk", "sr_reason_sk", "sr_ticket_number",
             "sr_return_quantity", "sr_return_amt", "sr_return_tax",
             "sr_return_amt_inc_tax", "sr_fee", "sr_return_ship_cost",
             "sr_refunded_cash", "sr_reversed_charge", "sr_store_credit",
             "sr_net_loss"],
            [returned, time, line("item_sk"), line("customer_sk"),
             line("cdemo_sk"), line("hdemo_sk"), line("addr_sk"),
             line("store_sk"), reason, line("ticket_number"), qty]
            + money))
    elif table == "catalog_returns":
        cols = dict(zip(
            ["cr_returned_date_sk", "cr_returned_time_sk", "cr_item_sk",
             "cr_refunded_customer_sk", "cr_refunded_cdemo_sk",
             "cr_refunded_hdemo_sk", "cr_refunded_addr_sk",
             "cr_returning_customer_sk", "cr_returning_cdemo_sk",
             "cr_returning_hdemo_sk", "cr_returning_addr_sk",
             "cr_call_center_sk", "cr_catalog_page_sk", "cr_ship_mode_sk",
             "cr_warehouse_sk", "cr_reason_sk", "cr_order_number",
             "cr_return_quantity", "cr_return_amount", "cr_return_tax",
             "cr_return_amt_inc_tax", "cr_fee", "cr_return_ship_cost",
             "cr_refunded_cash", "cr_reversed_charge", "cr_store_credit",
             "cr_net_loss"],
            [returned, time, line("item_sk"), line("bill_customer_sk"),
             line("bill_cdemo_sk"), line("bill_hdemo_sk"),
             line("bill_addr_sk"), cust, cdemo, hdemo, addr,
             line("call_center_sk"), line("catalog_page_sk"),
             line("ship_mode_sk"), line("warehouse_sk"), reason,
             line("order_number"), qty] + money))
    else:
        cols = dict(zip(
            ["wr_returned_date_sk", "wr_returned_time_sk", "wr_item_sk",
             "wr_refunded_customer_sk", "wr_refunded_cdemo_sk",
             "wr_refunded_hdemo_sk", "wr_refunded_addr_sk",
             "wr_returning_customer_sk", "wr_returning_cdemo_sk",
             "wr_returning_hdemo_sk", "wr_returning_addr_sk",
             "wr_web_page_sk", "wr_reason_sk", "wr_order_number",
             "wr_return_quantity", "wr_return_amt", "wr_return_tax",
             "wr_return_amt_inc_tax", "wr_fee", "wr_return_ship_cost",
             "wr_refunded_cash", "wr_reversed_charge", "wr_account_credit",
             "wr_net_loss"],
            [returned, time, line("item_sk"), line("bill_customer_sk"),
             line("bill_cdemo_sk"), line("bill_hdemo_sk"),
             line("bill_addr_sk"), cust, cdemo, hdemo, addr,
             line("web_page_sk"), reason, line("order_number"), qty]
            + money))
    return {k: np.asarray(v, dtype=np.int64) for k, v in cols.items()}, {}


_MAKERS = {"promotion": lambda sf, seed: gen_promotion(seed),
           "catalog_page": gen_catalog_page,
           "web_site": lambda sf, seed: gen_web_site(seed),
           "web_page": lambda sf, seed: gen_web_page(seed),
           "catalog_sales": lambda sf, seed: _channel_sales(
               "catalog_sales", sf, seed),
           "web_sales": lambda sf, seed: _channel_sales("web_sales", sf,
                                                        seed),
           "store_returns": lambda sf, seed: _returns("store_returns", sf,
                                                      seed),
           "catalog_returns": lambda sf, seed: _returns("catalog_returns",
                                                        sf, seed),
           "web_returns": lambda sf, seed: _returns("web_returns", sf,
                                                    seed)}

PLAN = "cockroach_tpu.sql.plan"


def require_union_all() -> None:
    plan = sys.modules.get(PLAN)
    if plan is not None and not hasattr(plan, "UnionAll"):
        raise SystemExit(
            "generators/tpcds_channels.py: this program runs a UNION ALL "
            "as statements of its own merged on the host and a WITH "
            f"through temp tables (no {PLAN}.UnionAll): TPC-DS Q5, Q77 "
            "and Q80 would upload and compile inside the window and join "
            "a sale to its return in the while-loop hash table. Refusing "
            "to start it.")


def generate(table: str, sf: float, seed: int):
    """(columns, dictionaries) of one table: numpy arrays in stored form
    and, for each STRING column, the list its int32 codes index."""
    require_union_all()
    if table in _MAKERS:
        return _MAKERS[table](sf, seed)
    return tpcds.generate(table, sf, seed)
