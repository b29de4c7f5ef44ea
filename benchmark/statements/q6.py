"""Integer reference of q6.sql (TPC-H Q6, forecasting revenue change)."""

from generators.tpch import days

COLUMNS = ["dec4"]
TABLES = ("lineitem",)


def reference(tables, p):
    li, _ = tables["lineitem"]
    year = int(p["year"])
    disc = round(float(p["discount"]) * 100)
    m = ((li["l_shipdate"] >= days(f"{year}-01-01"))
         & (li["l_shipdate"] < days(f"{year + 1}-01-01"))
         & (li["l_discount"] >= disc - 1) & (li["l_discount"] <= disc + 1)
         & (li["l_quantity"] < int(p["quantity"]) * 100))
    return [(int((li["l_extendedprice"][m] * li["l_discount"][m]).sum()),)]
