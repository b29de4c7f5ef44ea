select c_city, s_city, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
and lo_suppkey = s_suppkey
and lo_orderdate = d_datekey
and (c_city='{city1}' or c_city='{city2}')
and (s_city='{city1}' or s_city='{city2}')
and d_year >= {year_lo} and d_year <= {year_hi}
group by c_city, s_city, d_year
order by d_year asc, revenue desc
