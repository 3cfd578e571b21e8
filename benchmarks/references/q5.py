"""TPC-H Q5 (2.4.5) by sqlite over the generated rows: decimals are
unscaled ints (revenue at scale 4), dates epoch days."""

import datetime

from benchmarks.harness.reference import days

KIND = "sqlite"
TABLES = {
    "customer": ("c_custkey", "c_nationkey"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
    "lineitem": ("l_orderkey", "l_suppkey", "l_extendedprice",
                 "l_discount"),
    "supplier": ("s_suppkey", "s_nationkey"),
    "nation": ("n_nationkey", "n_name", "n_regionkey"),
    "region": ("r_regionkey", "r_name"),
}
INDEXES = ("customer(c_nationkey)", "orders(o_custkey)",
           "lineitem(l_orderkey)", "supplier(s_suppkey)")
# the control: one of 32 grace partitions of the probe side dropped
# (order keys are sparse, 8 used of every 32)
DROPPED_PARTITION = "AND (l_orderkey / 32) % 32 <> 0"


def oracle_sql(params, control=False):
    lo = datetime.date.fromisoformat(params["date"])
    hi = lo.replace(year=lo.year + 1)
    return f"""
        SELECT n_name, SUM(l_extendedprice * (100 - l_discount))
        FROM customer, orders, lineitem, supplier, nation, region
        WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
          AND l_suppkey = s_suppkey AND c_nationkey = s_nationkey
          AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
          AND r_name = '{params["region"]}'
          AND o_orderdate >= {days(lo.isoformat())}
          AND o_orderdate < {days(hi.isoformat())}
          {DROPPED_PARTITION if control else ""}
        GROUP BY n_name ORDER BY 2 DESC
    """
