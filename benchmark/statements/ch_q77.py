"""Integer reference of ch_q77.sql (TPC-DS Q77: each channel's sales
and returns over 30 days by store, call centre or web page, sales and
returns aggregated apart and joined (store and web by a LEFT JOIN on
the key, catalog by the specification's cross join of the two
aggregates); ROLLUP (channel, id)). The text keeps every constant of
the specification's query; a rolled-up key is written 'ALL' or 0
(COALESCE) because the comparison (verify.py) holds no NULL."""

import numpy as np

import chref

COLUMNS = ["text", "int", "dec2", "dec2", "dec2"]
TABLES = ("store_sales", "store_returns", "catalog_sales",
          "catalog_returns", "web_sales", "web_returns", "date_dim",
          "store", "web_page")
DAYS = 30


def _agg(tables, p, fact, date_col, key_col, measures, place=None,
         place_key=None):
    """{key: [sums]} of a fact table's rows in the window: by its own
    key column, or by the key of the dimension it joins (`place`)."""
    live = chref.in_window(tables, chref.col(tables, fact, date_col),
                           p["date"], DAYS)
    data, valid = chref.col(tables, fact, key_col)
    if place is not None:
        prow, pok = chref.find(tables, place, place_key, (data, valid))
        live = live & pok
        groups = np.where(pok, chref.col(tables, place, place_key)[0][prow],
                          -1)
    else:
        groups = np.where(valid, data, -1)
    return chref.sums(groups, live, [chref.col(tables, fact, m)
                                     for m in measures])


def _left(name, sales: dict, rets: dict) -> list:
    out = []
    for k, (s, pr) in sales.items():
        r, loss = rets.get(k, (None, None))
        out.append((name, None if k == -1 else k,
                    [s, chref.coalesce0(r),
                     chref.sub(pr, chref.coalesce0(loss))]))
    return out


def channel_rows(tables, p) -> list:
    ss = _agg(tables, p, "store_sales", "ss_sold_date_sk", "ss_store_sk",
              ["ss_ext_sales_price", "ss_net_profit"], "store",
              "s_store_sk")
    sr = _agg(tables, p, "store_returns", "sr_returned_date_sk",
              "sr_store_sk", ["sr_return_amt", "sr_net_loss"], "store",
              "s_store_sk")
    cs = _agg(tables, p, "catalog_sales", "cs_sold_date_sk",
              "cs_call_center_sk", ["cs_ext_sales_price", "cs_net_profit"])
    cr = _agg(tables, p, "catalog_returns", "cr_returned_date_sk",
              "cr_call_center_sk", ["cr_return_amount", "cr_net_loss"])
    ws = _agg(tables, p, "web_sales", "ws_sold_date_sk", "ws_web_page_sk",
              ["ws_ext_sales_price", "ws_net_profit"], "web_page",
              "wp_web_page_sk")
    wr = _agg(tables, p, "web_returns", "wr_returned_date_sk",
              "wr_web_page_sk", ["wr_return_amt", "wr_net_loss"],
              "web_page", "wp_web_page_sk")
    out = _left("store channel", ss, sr)
    for k, (s, pr) in cs.items():
        for r, loss in cr.values():
            out.append(("catalog channel", None if k == -1 else k,
                        [s, r, chref.sub(pr, loss)]))
    return out + _left("web channel", ws, wr)


def reference(tables, p):
    return chref.rollup(channel_rows(tables, p), True, 0)
