"""SQL frontend regression tests beyond the TPC-H suite — subquery
scoping, set operations, ordinals, scalar-count decorrelation (cases found
by review: each was a silent wrong-answer before the fix)."""

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner
from presto_tpu.sql.planner import PlanningError


@pytest.fixture(scope="module")
def runner():
    return LocalRunner({"tpch": TpchConnector(0.002)}, page_rows=1 << 14)


class TestSetOps:
    def test_union_order_limit_applies_to_whole_union(self, runner):
        res = runner.execute(
            "select o_orderkey from orders union all "
            "select o_orderkey from orders order by o_orderkey limit 3"
        )
        assert len(res.rows) == 3
        # smallest key twice, then next — proves both branches contribute
        assert res.rows[0][0] == res.rows[1][0]

    def test_union_coerces_types(self, runner):
        # common type is decimal(scale 1); engine returns unscaled ints at
        # the python boundary, so 1 -> 10 and 2.5 -> 25
        res = runner.execute("select 1 as x union all select 2.5")
        vals = sorted(int(r[0]) for r in res.rows)
        assert vals == [10, 25]

    def test_union_distinct(self, runner):
        res = runner.execute("select 1 as x union select 1 union select 2")
        assert sorted(r[0] for r in res.rows) == [1, 2]


class TestSubqueryScoping:
    def test_scalar_subquery_agg_stays_inner(self, runner):
        res = runner.execute(
            "select (select max(o_orderkey) from orders) as m, o_orderkey "
            "from orders order by o_orderkey limit 5"
        )
        # outer query must NOT collapse into a global aggregation
        assert len(res.rows) == 5
        assert all(r[0] >= r[1] for r in res.rows)

    def test_correlated_count_zero_groups(self, runner):
        # customers with custkey % 3 == 0 place no orders (generator rule);
        # count(*) over an empty correlated set must be 0, not NULL
        res = runner.execute(
            "select count(*) from customer where 0 = "
            "(select count(*) from orders where o_custkey = c_custkey)"
        )
        assert res.rows[0][0] >= 100  # the one-third inactive customers

    def test_exists_over_aggregated_subquery_rejected(self, runner):
        with pytest.raises(PlanningError):
            runner.execute(
                "select count(*) from customer where exists "
                "(select count(*) from orders where o_custkey = c_custkey "
                "group by o_orderstatus having count(*) > 100)"
            )


class TestOrdinals:
    def test_order_by_ordinal(self, runner):
        res = runner.execute(
            "select o_orderkey, o_custkey from orders order by 1 limit 3"
        )
        keys = [r[0] for r in res.rows]
        assert keys == sorted(keys)

    def test_ordinal_out_of_range(self, runner):
        with pytest.raises(PlanningError):
            runner.execute("select o_orderkey from orders order by 0")
        with pytest.raises(PlanningError):
            runner.execute("select o_orderkey from orders order by 5")
        with pytest.raises(PlanningError):
            runner.execute(
                "select o_orderkey, count(*) from orders group by 3"
            )


class TestMisc:
    def test_limit_offset(self, runner):
        all_rows = runner.execute(
            "select o_orderkey from orders order by o_orderkey limit 10"
        ).rows
        page2 = runner.execute(
            "select o_orderkey from orders order by o_orderkey "
            "limit 5 offset 5"
        ).rows
        assert page2 == all_rows[5:]

    def test_distinct(self, runner):
        res = runner.execute("select distinct o_orderstatus from orders")
        assert sorted(r[0] for r in res.rows) == ["F", "O", "P"]

    def test_select_star(self, runner):
        res = runner.execute("select * from region order by r_regionkey")
        assert len(res.rows) == 5
        assert res.column_names[:2] == ["r_regionkey", "r_name"]

    def test_group_by_expression(self, runner):
        res = runner.execute(
            "select o_orderkey % 2 as parity, count(*) from orders "
            "group by o_orderkey % 2 order by parity"
        )
        assert len(res.rows) == 2
        assert sum(r[1] for r in res.rows) == 3000  # n_orders at SF0.002


class TestAdviceRound1Regressions:
    """Regressions for the round-1 advisor findings."""

    def test_case_mixing_two_dictionary_columns(self, runner):
        # CASE selecting between two differently-coded string columns must
        # decode each branch through its own values, not one branch's dict
        res = runner.execute(
            "select c_custkey, case when c_custkey % 2 = 0 then c_mktsegment "
            "else c_name end from customer order by c_custkey limit 6"
        )
        for key, v in res.rows:
            if key % 2 == 0:
                assert v in {"AUTOMOBILE", "BUILDING", "FURNITURE",
                             "HOUSEHOLD", "MACHINERY"}, v
            else:
                assert v.startswith("Customer#"), v

    def test_case_string_literal_vs_column(self, runner):
        res = runner.execute(
            "select case when c_custkey % 2 = 0 then 'even' "
            "else c_mktsegment end from customer limit 50"
        )
        vals = {r[0] for r in res.rows}
        assert "even" in vals
        assert any(v != "even" for v in vals)

    def test_coalesce_string_literal_default(self, runner):
        res = runner.execute(
            "select coalesce(c_mktsegment, 'missing') from customer limit 5"
        )
        assert all(r[0] != "missing" for r in res.rows)

    def test_semi_join_on_transformed_dictionary(self, runner):
        # substr-produced dictionaries carry duplicate values; the join path
        # must canonicalize codes by value (advisor high #2)
        direct = runner.execute(
            "select count(*) from customer where substr(c_phone, 1, 2) = "
            "(select substr(c_phone, 1, 2) from customer where c_custkey = 1)"
        ).rows[0][0]
        via_in = runner.execute(
            "select count(*) from customer where substr(c_phone, 1, 2) in "
            "(select substr(c_phone, 1, 2) from customer where c_custkey = 1)"
        ).rows[0][0]
        assert direct == via_in and direct >= 1

    def test_power_negative_base_fractional_exponent_nan(self, runner):
        import math
        res = runner.execute("select power(-8.0, 0.5), power(-8.0, 2.0), "
                             "power(-2.0, 3.0)")
        assert math.isnan(res.rows[0][0])
        assert res.rows[0][1] == 64.0
        assert res.rows[0][2] == -8.0

    def test_uncorrelated_subquery_error_not_misrouted(self, runner):
        # a typo'd column inside an uncorrelated scalar subquery must raise
        # "column not found", not a decorrelator shape error
        with pytest.raises(PlanningError, match="column not found"):
            runner.execute(
                "select count(*) from customer where c_custkey = "
                "(select max(no_such_col) from orders)"
            )


def test_memory_budget_enforced(runner):
    from presto_tpu.exec.executor import MemoryBudgetExceeded

    runner.execute("set session query_max_memory_bytes = 1024")
    try:
        import pytest

        with pytest.raises(MemoryBudgetExceeded):
            runner.execute("select count(*) from lineitem")
        r = runner.execute("set session query_max_memory_bytes = 0")
        assert runner.execute(
            "select count(*) from region"
        ).rows == [(5,)]
    finally:
        runner.execute("set session query_max_memory_bytes = 0")


class TestVarianceFamily:
    """stddev/variance aggregates (reference: operator/aggregation/
    VarianceAggregation — Welford state; ours is moment sums, see
    exec/agg_states.py)."""

    def test_grouped_vs_numpy(self, runner):
        import collections

        import numpy as np

        rows = runner.execute(
            "select l_returnflag, l_quantity, l_extendedprice "
            "from lineitem"
        ).rows
        by = collections.defaultdict(list)
        for f, q, e in rows:
            by[f].append((q / 100.0, e / 100.0))
        got = runner.execute(
            "select l_returnflag, stddev(l_quantity), "
            "var_samp(l_quantity), stddev_pop(l_extendedprice), "
            "var_pop(l_extendedprice), variance(l_orderkey) "
            "from lineitem group by l_returnflag"
        ).rows
        assert len(got) == 3
        for f, sd, vs, sp, vp, vk in got:
            a = np.array(by[f])
            np.testing.assert_allclose(sd, np.std(a[:, 0], ddof=1),
                                       rtol=1e-9)
            np.testing.assert_allclose(vs, np.var(a[:, 0], ddof=1),
                                       rtol=1e-9)
            np.testing.assert_allclose(sp, np.std(a[:, 1], ddof=0),
                                       rtol=1e-9)
            np.testing.assert_allclose(vp, np.var(a[:, 1], ddof=0),
                                       rtol=1e-9)

    def test_global_and_edge_counts(self, runner):
        # global (ungrouped) path + n<2 null semantics
        r = runner.execute(
            "select stddev(l_quantity), var_pop(l_quantity) "
            "from lineitem where l_orderkey < 0"
        ).rows
        assert r[0][0] is None and r[0][1] is None
        one = runner.execute(
            "select var_samp(x), var_pop(x), stddev_pop(x) from "
            "(select 5 as x) t"
        ).rows[0]
        assert one[0] is None and one[1] == 0.0 and one[2] == 0.0


class TestDistinctAggregates:
    """MarkDistinct-backed DISTINCT aggregates (reference:
    MarkDistinctOperator + AggregationNode mask symbols): mixed
    DISTINCT/plain and multiple distinct argument columns."""

    def test_multiple_distinct_columns(self, runner):
        # regression: this returned (25, 25) when the dedup ran over the
        # combined (a, b) space instead of per-argument marks
        got = runner.execute(
            "select count(distinct n_regionkey), count(distinct n_name) "
            "from nation"
        ).rows
        assert got == [(5, 25)]

    def test_mixed_distinct_and_plain(self, runner):
        got = runner.execute(
            "select count(distinct o_custkey), count(*), "
            "sum(o_totalprice) from orders"
        ).rows[0]
        plain = runner.execute(
            "select count(*), sum(o_totalprice) from orders"
        ).rows[0]
        dcust = runner.execute(
            "select count(*) from "
            "(select distinct o_custkey from orders) t"
        ).rows[0]
        assert got == (dcust[0], plain[0], plain[1])

    def test_grouped_mixed_vs_manual(self, runner):
        got = runner.execute(
            "select l_returnflag, count(distinct l_suppkey), "
            "count(distinct l_partkey), sum(l_quantity) "
            "from lineitem group by l_returnflag order by 1"
        ).rows
        for flag, dsupp, dpart, qty in got:
            m = runner.execute(
                f"select count(distinct l_suppkey) from lineitem "
                f"where l_returnflag = '{flag}'"
            ).rows[0][0]
            m2 = runner.execute(
                f"select count(distinct l_partkey) from lineitem "
                f"where l_returnflag = '{flag}'"
            ).rows[0][0]
            m3 = runner.execute(
                f"select sum(l_quantity) from lineitem "
                f"where l_returnflag = '{flag}'"
            ).rows[0][0]
            assert (dsupp, dpart, qty) == (m, m2, m3)

    def test_sum_distinct(self, runner):
        got = runner.execute(
            "select sum(distinct n_regionkey), count(*) from nation"
        ).rows
        assert got == [(0 + 1 + 2 + 3 + 4, 25)]


class TestUsingJoins:
    """JOIN ... USING (reference: StatementAnalyzer's USING scope
    rules): one unqualified copy of each using column, coalesced for
    FULL joins, then the remaining columns of both sides."""

    def test_inner_using_matches_on(self, runner):
        a = runner.execute(
            "select k, count(*), sum(l_extendedprice) from "
            "(select o_orderkey k, o_totalprice from orders) "
            "join (select l_orderkey k, l_extendedprice from lineitem) "
            "using (k) group by k order by k limit 5"
        ).rows
        b = runner.execute(
            "select a.k, count(*), sum(l_extendedprice) from "
            "(select o_orderkey k, o_totalprice from orders) a "
            "join (select l_orderkey k, l_extendedprice from lineitem) b "
            "on a.k = b.k group by a.k order by a.k limit 5"
        ).rows
        assert a == b and len(a) == 5

    def test_using_output_shape(self, runner):
        res = runner.execute(
            "select * from (select n_nationkey k, n_name from nation) "
            "join (select r_regionkey k, r_name from region) using (k) "
            "order by k limit 2"
        )
        # one k column, then n_name, then r_name
        assert res.column_names == ["k", "n_name", "r_name"]
        assert res.rows[0][0] == 0

    def test_left_and_full_using_coalesce(self, runner):
        left = runner.execute(
            "select k, r_name from "
            "(select n_nationkey k, n_name from nation) "
            "left join (select r_regionkey k, r_name from region) "
            "using (k) order by k"
        ).rows
        assert len(left) == 25
        # keys 0..4 match regions; 5..24 null-extended
        assert left[0][1] is not None and left[10][1] is None
        full = runner.execute(
            "select k from "
            "(select r_regionkey k from region) "
            "full join (select n_nationkey k from nation where "
            "n_nationkey >= 3) using (k) order by k"
        ).rows
        # coalesced key: 0..2 from left only, 3,4 both, 5..24 right only
        assert [r[0] for r in full] == list(range(25))

    def test_using_missing_column_errors(self, runner):
        with pytest.raises(PlanningError):
            runner.execute(
                "select * from nation join region using (nope)"
            )
