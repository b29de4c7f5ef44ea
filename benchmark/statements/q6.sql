SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= date '{year}-01-01'
  AND l_shipdate < date '{year}-01-01' + interval '1 year'
  AND l_discount BETWEEN {discount} - 0.01 AND {discount} + 0.01
  AND l_quantity < {quantity}
