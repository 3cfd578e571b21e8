"""TPC-H Q3 (2.4.3) by sqlite over the generated rows: decimals are
unscaled ints (1 - l_discount is 100 - l_discount at scale 2, revenue
at scale 4), dates epoch days. The engine's text carries the same
l_orderkey tiebreak."""

from benchmarks.harness.reference import days

KIND = "sqlite"
TABLES = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate",
               "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"),
}
INDEXES = ("orders(o_custkey)", "lineitem(l_orderkey)")
# the control: one of 32 grace partitions of the probe side dropped
# (order keys are sparse, 8 used of every 32)
DROPPED_PARTITION = "AND (l_orderkey / 32) % 32 <> 0"


def oracle_sql(params, control=False):
    date = days(params["date"])
    return f"""
        SELECT l_orderkey,
               SUM(l_extendedprice * (100 - l_discount)), o_orderdate,
               o_shippriority
        FROM customer, orders, lineitem
        WHERE c_mktsegment = '{params["segment"]}'
          AND c_custkey = o_custkey AND l_orderkey = o_orderkey
          AND o_orderdate < {date} AND l_shipdate > {date}
          {DROPPED_PARTITION if control else ""}
        GROUP BY l_orderkey, o_orderdate, o_shippriority
        ORDER BY 2 DESC, o_orderdate, l_orderkey LIMIT 10
    """
