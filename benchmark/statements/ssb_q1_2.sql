select sum(lo_extendedprice*lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
and d_yearmonthnum = {yearmonthnum}
and lo_discount between {discount_lo} and {discount_hi}
and lo_quantity between {quantity_lo} and {quantity_hi}
