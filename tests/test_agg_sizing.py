"""First-attempt sizing of the blocking grouped aggregation (ISSUE 26,
Executor._agg_sizing): ONE rule decides the partition count, the
compaction accumulator and the single path's group capacity, and
membudget.audit reports it from the same function.

On a CPU ``_fault_rows()`` is None, so without forcing it no tier-1 test
takes the branch the chip takes: before this rule Q3 at SF1 ran 32
hash-partition passes over 4M-slot pages for 11k groups out of 30k
joined rows, while the audit of the same plan printed one 262,144-row
state. Every test here forces ``fault_rows``.
"""

import os

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import membudget as MB
from presto_tpu.exec import plan as P
from presto_tpu.exec import shapes as SH
from presto_tpu.runner import LocalRunner
from tests.oracle import load_sqlite
from tests.test_sql_tpch import ENGINE_SQL, ORACLE, compare

STATEMENTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "statements")
V5E_BUDGET = (16 << 30) * 7 // 8  # one v5e chip, governor headroom off
OPT = 1 << 18                     # agg_optimistic_rows' default


def _statement(name, **params):
    with open(os.path.join(STATEMENTS, f"{name}.sql")) as f:
        return f.read().format(**params)


BENCH = {
    "q1": _statement("q1", delta=90),
    "q3": _statement("q3", segment="BUILDING", date="1995-03-15"),
    "q5": _statement("q5", region="ASIA", date="1994-01-01"),
}


def _grouped_agg(plan):
    found = []

    def walk(n):
        if isinstance(n, P.Aggregation) and n.group_channels:
            found.append(n)
        for c in n.children():
            walk(c)

    walk(plan)
    assert len(found) == 1, found
    return found[0]


def _chip_runner(sf):
    """A runner that sizes as the chip does: the v5e's budget and the
    fault line forced. Static use only — nothing is generated."""
    r = LocalRunner({"tpch": TpchConnector(sf)}, page_rows=1 << 18)
    r.apply_session()
    r.executor.device_memory_budget = V5E_BUDGET
    r.executor.fault_rows = SH.SAFE_BUFFER_ROWS
    return r


# ------------------------------------------------------- (a) static
@pytest.mark.parametrize("sf", [1.0, 10.0], ids=["sf1", "sf10"])
def test_q3_first_attempt_is_sized_by_the_optimistic_rows(sf):
    """The benchmark's Q3 under TPU assumptions: one pass, a 262,144
    group state and a 262,144-row compaction buffer, whatever the
    planner's 4M-slot bound says; the audit reports the same numbers
    from the same function and every buffer stays under the line."""
    r = _chip_runner(sf)
    ex = r.executor
    plan = r.plan(BENCH["q3"])
    agg = _grouped_agg(plan)
    assert agg.capacity == 1 << 22  # the bound the 32 passes came from
    sz = ex._agg_sizing(agg)
    assert (sz.parts, sz.cap, sz.compact_rows) == (1, OPT, OPT)
    assert (sz.sized_by, sz.governed) == ("optimistic", False)
    report = MB.audit(ex, plan)
    by_label = {b.label: b for b in report.buffers}
    assert by_label["agg state"].rows == sz.cap
    assert not by_label["agg state"].chunked
    assert by_label["agg compaction"].rows == sz.compact_rows
    assert report.chunked_count == 0 and report.ok, MB.render(report)
    assert all(b.rows < SH.DEVICE_FAULT_ROWS for b in report.buffers)


def test_q3_boosted_retry_sizes_from_the_planners_bounds():
    """A boosted attempt is evidence the optimistic size was wrong: it
    decides as every attempt did before the rule — boost-scaled slots
    against the governed fold cap, so the partitioned path engages and
    the compaction buffer (16M > 2M) switches itself off — and the
    audit of that attempt says so too."""
    r = _chip_runner(1.0)
    ex = r.executor
    plan = r.plan(BENCH["q3"])
    agg = _grouped_agg(plan)
    ex._capacity_boost = SH.BOOST_STEP
    sz = ex._agg_sizing(agg)
    assert (sz.parts, sz.compact_rows) == (32, 0)
    assert (sz.sized_by, sz.governed) == ("rows_cap", True)
    assert sz.cap == OPT * SH.BOOST_STEP
    labels = [b.label for b in MB.audit(ex, plan).buffers]
    assert "agg state (1/32 pass)" in labels
    assert "agg compaction" not in labels


@pytest.mark.parametrize("name", ["q1", "q5"])
def test_dictionary_keys_get_no_compaction_buffer(name):
    """Q1 and Q5 group by dictionary-coded strings: dense group ids
    cost next to nothing per sparse page, so they keep their one pass
    at the optimistic capacity and get no compaction buffer, although
    the planner bounds them at 4M slots as it does Q3 (and Q5's source
    is a join)."""
    r = _chip_runner(1.0)
    plan = r.plan(BENCH[name])
    sz = r.executor._agg_sizing(_grouped_agg(plan))
    assert (sz.parts, sz.cap, sz.compact_rows) == (1, OPT, 0)
    assert "agg compaction" not in [
        b.label for b in MB.audit(r.executor, plan).buffers]


def test_sizing_reports_which_bound_decided():
    """agg_sized_by: the planner's estimate where it is under the
    optimistic rows, then spill_bytes, the row ceiling or the byte
    share once one of them asks for passes."""
    r = LocalRunner({"tpch": TpchConnector(0.01)}, page_rows=1 << 13)
    r.apply_session()
    ex = r.executor
    agg = _grouped_agg(r.plan(
        "select l_orderkey, count(*) from lineitem group by l_orderkey"))
    assert ex._agg_sizing(agg)[2:] == (1, "estimate")
    ex.spill_bytes = 1 << 13
    assert ex._agg_sizing(agg).sized_by == "spill_bytes"
    ex.spill_bytes = None
    ex.fault_rows = 1 << 12
    sz = ex._agg_sizing(agg)
    assert (sz.sized_by, sz.governed) == ("rows_cap", True)
    ex.fault_rows = None
    ex.device_memory_budget = 1 << 16
    sz = ex._agg_sizing(agg)
    assert (sz.sized_by, sz.governed) == ("bytes_cap", True)


# ----------------------------------------------------- (b) executed
SF = 0.01
LOW_OPT = 1 << 13    # agg_optimistic_rows forced low, above the matmul limit
LOW_FAULT = 1 << 16  # governed fold cap = fault_rows >> 2 = 16,384

# Q3 without its date and segment filters: 15,000 groups from 60,175
# joined rows, more than LOW_OPT holds
WIDE_Q3 = (
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,"
    " o_orderdate, o_shippriority from customer, orders, lineitem"
    " where c_custkey = o_custkey and l_orderkey = o_orderkey"
    " group by l_orderkey, o_orderdate, o_shippriority"
    " order by revenue desc, o_orderdate, l_orderkey limit 10")
WIDE_Q3_ORACLE = (
    "SELECT l_orderkey, SUM(l_extendedprice * (100 - l_discount)),"
    " o_orderdate, o_shippriority FROM customer, orders, lineitem"
    " WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey"
    " GROUP BY l_orderkey, o_orderdate, o_shippriority"
    " ORDER BY 2 DESC, o_orderdate, l_orderkey LIMIT 10")


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def db(conn):
    return load_sqlite(conn, conn.tables())


def _forced_runner(conn):
    """Sizes forced low, and split batching on as on the chip: eight
    4,096-row splits a launch make 32,768-slot pages, wider than the
    compaction buffer as the chip's 4M-slot pages are."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 12)
    r.session.set("query_trace_enabled", True)
    r.session.set("agg_optimistic_rows", LOW_OPT)
    r.session.set("split_batch_size", 8)
    r.apply_session()
    r.executor.fault_rows = LOW_FAULT
    return r


def _attempts(runner):
    return [sp.attrs for sp in runner.last_trace.spans()
            if sp.kind == "attempt"]


def test_groups_that_fit_run_one_compacted_pass(conn, db):
    """Q3's 11 groups at SF0.01 fit LOW_OPT: the single path with the
    compaction buffer, no partition pass, no retry, exact."""
    r = _forced_runner(conn)
    rows = r.execute(ENGINE_SQL[3]).rows
    compare(3, rows, db.execute(ORACLE[3][0]).fetchall(), ORACLE[3][1])
    ex = r.executor
    assert ex.spill_partitions_used == 0
    assert ex.capacity_boost_retries == 0
    (attempt,) = _attempts(r)
    assert attempt["outcome"] == "ok"
    assert (attempt["agg_parts"], attempt["agg_cap"],
            attempt["agg_compact_rows"], attempt["agg_sized_by"]) == (
        1, LOW_OPT, LOW_OPT, "optimistic")
    # a wide page compacts alone, then merges as 2 x C slots; the tail
    # batch's page is no wider than C and merges as it is
    pages = attempt["launches"]["fused_batch"]
    assert pages >= 3
    assert attempt["launches"]["stream_compact1"] == pages - 1
    assert attempt["launches"]["stream_compact2"] == pages - 1
    assert attempt["launches"]["agg_partial"] == 1
    assert "partfilter" not in attempt["launches"]


def test_groups_that_do_not_fit_overflow_into_the_partitioned_path(conn, db):
    """15,000 groups overflow LOW_OPT: the first attempt fails cheaply
    (one pass, flagged), the boosted one sizes from the planner's
    bounds, lands in the partitioned path and is still exact."""
    r = _forced_runner(conn)
    rows = r.execute(WIDE_Q3).rows
    compare("3-wide", rows, db.execute(WIDE_Q3_ORACLE).fetchall(), {})
    ex = r.executor
    assert ex.capacity_boost_retries >= 1
    assert ex.spill_partitions_used > 1
    first, last = _attempts(r)[0], _attempts(r)[-1]
    assert (first["outcome"], first["agg_parts"],
            first["agg_sized_by"]) == ("overflow", 1, "optimistic")
    assert (last["outcome"], last["agg_sized_by"]) == ("ok", "rows_cap")
    assert last["agg_parts"] == ex.spill_partitions_used
    assert last["launches"]["partfilter"] >= last["agg_parts"]


# ------------------------------------------- (c) programs launched
@pytest.mark.parametrize("qnum,compacts", [(1, False), (5, False),
                                           (3, True)],
                         ids=["q1", "q5", "q3"])
def test_only_sorted_grouping_over_a_join_launches_stream_compact(
        conn, db, qnum, compacts):
    """With the paths the chip takes forced on (fused partial
    aggregation, split batching, the fault line): Q1 (no join) and Q5
    (a join, dictionary keys) launch no stream_compact program; Q3 (a
    join, bigint/date/integer keys) does. All exact."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("query_trace_enabled", True)
    r.session.set("fused_partial_agg_enabled", "true")
    r.session.set("split_batch_size", 8)
    r.apply_session()
    r.executor.fault_rows = SH.SAFE_BUFFER_ROWS
    rows = r.execute(ENGINE_SQL[qnum]).rows
    compare(qnum, rows, db.execute(ORACLE[qnum][0]).fetchall(),
            ORACLE[qnum][1])
    (attempt,) = _attempts(r)
    launched = {lab for lab in attempt["launches"]
                if lab.startswith("stream_compact")}
    assert bool(launched) == compacts, attempt["launches"]
    assert bool(attempt["agg_compact_rows"]) == compacts
    assert attempt["agg_parts"] == 1
