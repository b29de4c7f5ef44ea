"""Integer reference of ssb_q1_3.sql (SSB Q1.3: a week)."""

import ssbref

COLUMNS = ["int"]
TABLES = ("lineorder", "date")


def reference(tables, p):
    lo, _ = tables["lineorder"]
    m = (ssbref.star(tables, date=(
        ssbref.equal(tables, "date", "d_weeknuminyear", p["week"])
        & ssbref.equal(tables, "date", "d_year", p["year"])))
         & (lo["lo_discount"] >= p["discount_lo"])
         & (lo["lo_discount"] <= p["discount_hi"])
         & (lo["lo_quantity"] >= p["quantity_lo"])
         & (lo["lo_quantity"] <= p["quantity_hi"]))
    revenue = lo["lo_extendedprice"][m] * lo["lo_discount"][m]
    return [[int(revenue.sum())]]
