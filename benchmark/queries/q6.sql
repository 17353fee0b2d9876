select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '{date_lo}'
  and l_shipdate < date '{date_hi}'
  and l_discount between {disc_lo} and {disc_hi}
  and l_quantity < {quantity}
