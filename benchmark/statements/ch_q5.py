"""Integer reference of ch_q5.sql (TPC-DS Q5: each channel's sales,
returns, profit and loss over 14 days by store, catalog page or web
site, sales and returns as one UNION ALL of rows before the date and
place joins, a web return's site found through its sale by (item,
order); ROLLUP (channel, id)). The text keeps every constant of the
specification's query; a rolled-up key is written 'ALL' (COALESCE)
because the comparison (verify.py) holds no NULL."""

import numpy as np

import chref

COLUMNS = ["text", "text", "dec2", "dec2", "dec2"]
TABLES = ("store_sales", "store_returns", "catalog_sales",
          "catalog_returns", "web_sales", "web_returns", "date_dim",
          "store", "catalog_page", "web_site")
DAYS = 14


def _branch(tables, p, table, place_fk: tuple, date_col, measures,
            place, place_key, place_id):
    """{place id code: [sales, profit, returns, loss]} of one branch of
    a channel's UNION ALL: `measures` are the four columns' (data,
    validity), a zero where the branch has none."""
    live = chref.in_window(tables, chref.col(tables, table, date_col),
                           p["date"], DAYS)
    prow, pok = chref.find(tables, place, place_key, place_fk)
    groups = chref.codes(tables, place, place_id, prow, pok)
    return chref.sums(groups, live & pok, measures)


def _zero(n):
    return (np.zeros(n, dtype=np.int64), np.ones(n, dtype=bool))


def channel_rows(tables, p) -> list:
    out = []
    ss = "store_sales"
    ws_row, ws_ok = chref.returns_of(
        tables, "web_returns", "web_sales",
        ("wr_item_sk", "wr_order_number"), ("ws_item_sk", "ws_order_number"))
    site, site_v = chref.col(tables, "web_sales", "ws_web_site_sk")
    for name, prefix, place, place_key, place_id, branches in (
            ("store channel", "store", "store", "s_store_sk", "s_store_id",
             [(ss, chref.col(tables, ss, "ss_store_sk"), "ss_sold_date_sk",
               ["ss_ext_sales_price", "ss_net_profit", None, None]),
              ("store_returns",
               chref.col(tables, "store_returns", "sr_store_sk"),
               "sr_returned_date_sk",
               [None, None, "sr_return_amt", "sr_net_loss"])]),
            ("catalog channel", "catalog_page", "catalog_page",
             "cp_catalog_page_sk", "cp_catalog_page_id",
             [("catalog_sales",
               chref.col(tables, "catalog_sales", "cs_catalog_page_sk"),
               "cs_sold_date_sk",
               ["cs_ext_sales_price", "cs_net_profit", None, None]),
              ("catalog_returns",
               chref.col(tables, "catalog_returns", "cr_catalog_page_sk"),
               "cr_returned_date_sk",
               [None, None, "cr_return_amount", "cr_net_loss"])]),
            ("web channel", "web_site", "web_site", "web_site_sk",
             "web_site_id",
             [("web_sales", (site, site_v), "ws_sold_date_sk",
               ["ws_ext_sales_price", "ws_net_profit", None, None]),
              ("web_returns", (np.where(ws_ok, site[ws_row], 0),
                               ws_ok & site_v[ws_row]),
               "wr_returned_date_sk",
               [None, None, "wr_return_amt", "wr_net_loss"])])):
        total: dict = {}
        for table, fk, date_col, cols in branches:
            n = len(chref.col(tables, table, date_col)[0])
            measures = [chref.col(tables, table, c) if c else _zero(n)
                        for c in cols]
            for g, vals in _branch(tables, p, table, fk, date_col,
                                   measures, place, place_key,
                                   place_id).items():
                acc = total.setdefault(g, [None] * 4)
                for m, v in enumerate(vals):
                    if v is not None:
                        acc[m] = v + (acc[m] or 0)
        for g, (sales, profit, rets, loss) in total.items():
            out.append((name, chref.label(tables, place, place_id, g,
                                          prefix),
                        [sales, rets, chref.sub(profit, loss)]))
    return out


def reference(tables, p):
    return chref.rollup(channel_rows(tables, p), True, "ALL")
