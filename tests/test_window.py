"""Window function correctness vs the sqlite3 oracle (sqlite >= 3.25 has
full window support).

Reference test analog: presto-main operator/TestWindowOperator +
AbstractTestQueries window cases (SURVEY §3.2 WindowOperator -> segmented
scans)."""

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner
from tests.oracle import load_sqlite

SF = 0.005


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def runner(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13)


@pytest.fixture(scope="module")
def db(conn):
    return load_sqlite(conn, ["nation", "orders", "customer"])


CASES = [
    # ranking trio with partitions and ordering
    """select n_regionkey, n_name,
              row_number() over (partition by n_regionkey order by n_name),
              rank() over (partition by n_regionkey order by n_nationkey),
              dense_rank() over (partition by n_regionkey order by n_nationkey)
       from nation order by n_regionkey, n_name""",
    # rank with ties (duplicate order keys)
    """select o_custkey, o_orderkey,
              rank() over (partition by o_custkey order by o_orderdate),
              dense_rank() over (partition by o_custkey order by o_orderdate),
              row_number() over (partition by o_custkey order by o_orderdate, o_orderkey)
       from orders order by o_custkey, o_orderkey limit 200""",
    # whole-partition aggregates (no order by in the frame)
    """select n_regionkey, n_nationkey,
              count(*) over (partition by n_regionkey),
              sum(n_nationkey) over (partition by n_regionkey),
              min(n_name) over (partition by n_regionkey),
              max(n_name) over (partition by n_regionkey)
       from nation order by n_nationkey""",
    # running aggregates (range frame with peers)
    """select o_custkey, o_orderkey,
              sum(o_totalprice) over (partition by o_custkey order by o_orderdate),
              count(*) over (partition by o_custkey order by o_orderdate),
              min(o_totalprice) over (partition by o_custkey order by o_orderdate),
              max(o_totalprice) over (partition by o_custkey order by o_orderdate)
       from orders order by o_custkey, o_orderkey limit 200""",
    # navigation functions
    """select o_custkey, o_orderkey,
              lag(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey),
              lead(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey),
              lag(o_orderkey, 2) over (partition by o_custkey order by o_orderdate, o_orderkey),
              first_value(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey)
       from orders order by o_custkey, o_orderkey limit 200""",
    # global window (no partition)
    """select n_name, rank() over (order by n_regionkey),
              sum(n_nationkey) over (order by n_regionkey)
       from nation order by n_name""",
    # window + where + expression args
    """select o_orderkey,
              sum(o_totalprice) over (partition by o_orderpriority
                                      order by o_orderkey)
       from orders where o_custkey % 5 = 0
       order by o_orderkey limit 100""",
    # distribution + ntile (round 3)
    """select o_custkey, o_orderkey,
              ntile(4) over (partition by o_custkey order by o_orderkey),
              percent_rank() over (partition by o_custkey
                                   order by o_orderdate),
              cume_dist() over (partition by o_custkey
                                order by o_orderdate)
       from orders order by o_custkey, o_orderkey limit 200""",
    # explicit ROWS frames: prefix, sliding, empty-capable, suffix
    """select o_custkey, o_orderkey,
              sum(o_totalprice) over (partition by o_custkey
                  order by o_orderkey
                  rows between 2 preceding and current row),
              min(o_totalprice) over (partition by o_custkey
                  order by o_orderkey
                  rows between 1 preceding and 1 following),
              max(o_totalprice) over (partition by o_custkey
                  order by o_orderkey
                  rows between 3 preceding and 1 preceding),
              count(*) over (partition by o_custkey order by o_orderkey
                  rows between current row and unbounded following)
       from orders order by o_custkey, o_orderkey limit 200""",
    # nth_value + last_value over the whole partition (RANGE frame)
    """select o_custkey, o_orderkey,
              nth_value(o_orderkey, 2) over (partition by o_custkey
                  order by o_orderkey
                  rows between unbounded preceding
                           and unbounded following),
              last_value(o_orderkey) over (partition by o_custkey
                  order by o_orderdate
                  range between unbounded preceding
                            and unbounded following)
       from orders order by o_custkey, o_orderkey limit 200""",
]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_window_vs_sqlite(case, runner, db):
    sql = CASES[case]
    got = runner.execute(sql).rows
    want = [tuple(r) for r in db.execute(sql).fetchall()]
    assert len(got) == len(want), (len(got), len(want))
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"case {case} row {i}: {g} != {w}"


def test_window_then_filter_subquery(runner, db):
    sql = """select * from (
               select n_name, n_regionkey,
                      row_number() over (partition by n_regionkey
                                         order by n_name) rn
               from nation) t
             where rn = 1 order by n_regionkey"""
    got = runner.execute(sql).rows
    want = [tuple(r) for r in db.execute(sql).fetchall()]
    assert got == want


def test_window_over_aggregate_subquery(runner, db):
    # windows over aggregated results via nesting (the supported spelling)
    sql = """select o_custkey, total,
                    rank() over (order by total desc, o_custkey)
             from (select o_custkey, sum(o_totalprice) total
                   from orders group by o_custkey) t
             order by total desc, o_custkey limit 50"""
    got = runner.execute(sql).rows
    want = [tuple(r) for r in db.execute(sql).fetchall()]
    assert got == want


def test_window_with_aggregate_same_block_raises(runner):
    from presto_tpu.sql.planner import PlanningError

    with pytest.raises(Exception):
        runner.execute(
            "select rank() over (order by sum(n_nationkey)) "
            "from nation group by n_regionkey"
        )
