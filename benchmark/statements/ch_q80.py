"""Integer reference of ch_q80.sql (TPC-DS Q80: each channel's sales,
returns and profit by store, catalog page or web site over 30 days, for
items over 50.00 under promotions not on TV, a sale joined to its
return by (item, ticket or order); ROLLUP (channel, id)). The text
keeps every constant of the specification's query; a rolled-up key is
written 'ALL' (COALESCE) because the comparison (verify.py) holds no
NULL."""

import numpy as np

import chref

COLUMNS = ["text", "text", "dec2", "dec2", "dec2"]
TABLES = ("store_sales", "store_returns", "catalog_sales",
          "catalog_returns", "web_sales", "web_returns", "date_dim",
          "store", "catalog_page", "web_site", "item", "promotion")
DAYS = 30
# (channel, sales, returns, line key, return key, place key, place
# table, its key, its id, the id's prefix, sale's and return's columns)
CHANNELS = [
    ("store channel", "store_sales", "store_returns",
     ("ss_item_sk", "ss_ticket_number"), ("sr_item_sk", "sr_ticket_number"),
     "ss_store_sk", "store", "s_store_sk", "s_store_id", "store", "ss_",
     ("sr_return_amt", "sr_net_loss")),
    ("catalog channel", "catalog_sales", "catalog_returns",
     ("cs_item_sk", "cs_order_number"), ("cr_item_sk", "cr_order_number"),
     "cs_catalog_page_sk", "catalog_page", "cp_catalog_page_sk",
     "cp_catalog_page_id", "catalog_page", "cs_",
     ("cr_return_amount", "cr_net_loss")),
    ("web channel", "web_sales", "web_returns",
     ("ws_item_sk", "ws_order_number"), ("wr_item_sk", "wr_order_number"),
     "ws_web_site_sk", "web_site", "web_site_sk", "web_site_id",
     "web_site", "ws_", ("wr_return_amt", "wr_net_loss")),
]


def channel_rows(tables, p) -> list:
    """[(channel, id, [sales, returns, profit])]: each channel's CTE."""
    out = []
    for (name, sales, returns, skey, rkey, place_fk, place, place_key,
         place_id, prefix, s, (ramt, rloss)) in CHANNELS:
        c = lambda n: chref.col(tables, sales, s + n)  # noqa: E731
        live = chref.in_window(tables, c("sold_date_sk"), p["date"], DAYS)
        prow, pok = chref.find(tables, place, place_key,
                               chref.col(tables, sales, place_fk))
        irow, iok = chref.find(tables, "item", "i_item_sk", c("item_sk"))
        price, pv = chref.col(tables, "item", "i_current_price")
        mrow, mok = chref.find(tables, "promotion", "p_promo_sk",
                               c("promo_sk"))
        tv, tvv = chref.col(tables, "promotion", "p_channel_tv")
        no = tables["promotion"][1]["p_channel_tv"].index("N")
        live = live & pok & iok & pv[irow] & (price[irow] > 5000) \
            & mok & tvv[mrow] & (tv[mrow] == no)
        rrow, rok = chref.returns_of(tables, sales, returns, skey, rkey)
        amt, amtv = chref.col(tables, returns, ramt)
        loss, lossv = chref.col(tables, returns, rloss)
        ret = (amt[rrow] * (rok & amtv[rrow]), np.ones(len(rok), bool))
        profit, prv = c("net_profit")
        lost = loss[rrow] * (rok & lossv[rrow])
        groups = chref.codes(tables, place, place_id, prow, pok)
        for g, vals in chref.sums(groups, live,
                                  [c("ext_sales_price"), ret,
                                   (profit - lost, prv)]).items():
            ident = chref.label(tables, place, place_id, g, prefix)
            out.append((name, ident, vals))
    return out


def reference(tables, p):
    return chref.rollup(channel_rows(tables, p), True, "ALL")
