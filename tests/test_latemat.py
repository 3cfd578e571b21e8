"""Late materialization for join chains + fused partial aggregation.

Reference: spi/block/DictionaryBlock.java (joins emit indirections over
the build PagesIndex; values materialize at the first consumer) and
operator/ScanFilterAndProjectOperator.java (pipeline fusion), extended
further: carry build ROW IDS through the chain and gather
each carried column exactly once; compile scan→filter→project→partial
aggregation to one XLA program per split.

The counter tests use hand-built physical plans over the memory
connector so join order, build sides, and channel sets are pinned —
the assertions are exact, not directional."""

import collections
import dataclasses

import pytest

from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import plan as P
from presto_tpu.exec.executor import Executor
from presto_tpu.runner import LocalRunner


def _rows_equal(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b)
    )


def _chain_rig():
    """t1 ⋈ t2 ⋈ t3 on a shared key — the Q5-shaped probe spine."""
    mem = MemoryConnector()
    mem.create_table(
        "t1", ["k1", "a"], [T.BIGINT, T.BIGINT],
        [(i, i * 10) for i in range(100)],
    )
    mem.create_table(
        "t2", ["k2", "b", "c"], [T.BIGINT, T.BIGINT, T.BIGINT],
        [(i, i + 1, i + 2) for i in range(100)],
    )
    mem.create_table(
        "t3", ["k3", "d"], [T.BIGINT, T.BIGINT],
        [(i, -i) for i in range(100)],
    )
    scan1 = P.TableScan("mem", "t1", ("k1", "a"))
    scan2 = P.TableScan("mem", "t2", ("k2", "b", "c"))
    scan3 = P.TableScan("mem", "t3", ("k3", "d"))
    j1 = P.HashJoin(scan1, scan2, (0,), (0,), "inner")
    j2 = P.HashJoin(j1, scan3, (0,), (0,), "inner")
    return mem, j2


def test_chain_single_gather_per_carried_build_column():
    """The acceptance contract: on a multi-join chain, every carried
    build column is VALUE-gathered exactly once (at the chain
    boundary), however many joins it rides through."""
    mem, j2 = _chain_rig()
    ex = Executor({"mem": mem})
    _names, rows = ex.execute(j2)
    want = [(i, i * 10, i, i + 1, i + 2, i, -i) for i in range(100)]
    assert _rows_equal(rows, want)
    # join1 defers t2's 3 columns; join2 defers t3's 2 and carries
    # t2's 3 — one page per stream, so:
    #   deferred  = 3 (at j1) + 5 (at j2)        = 8
    #   gathered  = 3 (t2) + 2 (t3), ONCE each   = 5
    assert ex.gathers_materialized == 5
    assert ex.gathers_deferred == 8


def test_chain_disabled_matches_and_defers_nothing():
    mem, j2 = _chain_rig()
    ex_on = Executor({"mem": mem})
    ex_off = Executor({"mem": mem})
    ex_off.late_mat = False
    _n, rows_on = ex_on.execute(j2)
    _n, rows_off = ex_off.execute(j2)
    assert _rows_equal(rows_on, rows_off)
    assert ex_off.gathers_deferred == 0
    assert ex_off.gathers_materialized == 0


def test_left_join_null_build_side_survives_deferral():
    """LEFT-join pad rows (unmatched probe, null build side) must stay
    NULL through the indirection AND through a downstream join's
    composition: the id column's null mask gathers with probe_idx and
    ORs over the build nulls at materialization."""
    mem = MemoryConnector()
    mem.create_table(
        "p", ["k", "a"], [T.BIGINT, T.BIGINT],
        [(i, i) for i in range(20)],
    )
    mem.create_table(
        "b", ["bk", "v"], [T.BIGINT, T.BIGINT],
        [(i, 100 + i) for i in range(0, 20, 2)],  # evens only
    )
    mem.create_table(
        "t3", ["k3", "d"], [T.BIGINT, T.BIGINT],
        [(i, -i) for i in range(20)],
    )
    left = P.HashJoin(
        P.TableScan("mem", "p", ("k", "a")),
        P.TableScan("mem", "b", ("bk", "v")),
        (0,), (0,), "left",
    )
    top = P.HashJoin(
        left, P.TableScan("mem", "t3", ("k3", "d")),
        (0,), (0,), "inner",
    )
    ex = Executor({"mem": mem})
    _n, rows = ex.execute(top)
    want = [
        (i, i, i, 100 + i, i, -i) if i % 2 == 0
        else (i, i, None, None, i, -i)
        for i in range(20)
    ]
    assert _rows_equal(rows, want)
    # the interior left join defers b's 2 columns; the top join (chain
    # boundary, lazy probe) defers t3's 2 for free; every carried
    # column gathers once at the boundary
    assert ex.gathers_materialized == 4
    assert ex.gathers_deferred == 6


def test_single_boundary_join_stays_eager():
    """A lone (un-chained) join's consumer materializes immediately —
    deferring would only add a launch, so the boundary join runs the
    eager path and the counters stay zero."""
    mem = MemoryConnector()
    mem.create_table(
        "p", ["k", "a"], [T.BIGINT, T.BIGINT],
        [(i, i) for i in range(10)],
    )
    mem.create_table(
        "b", ["bk", "v"], [T.BIGINT, T.BIGINT],
        [(i, 100 + i) for i in range(10)],
    )
    join = P.HashJoin(
        P.TableScan("mem", "p", ("k", "a")),
        P.TableScan("mem", "b", ("bk", "v")),
        (0,), (0,), "inner",
    )
    ex = Executor({"mem": mem})
    _n, rows = ex.execute(join)
    assert _rows_equal(rows, [(i, i, i, 100 + i) for i in range(10)])
    assert ex.gathers_deferred == 0
    assert ex.gathers_materialized == 0


def test_lazy_filter_lifts_only_referenced_channels():
    """A filter between chained joins lifts exactly the deferred
    channels its predicate reads (prune.expr_channels liveness); the
    rest stay deferred to the boundary — total value gathers stay at
    one per carried column."""
    from presto_tpu.expr import ir

    mem, j2 = _chain_rig()
    j1 = j2.left
    scan3 = j2.right
    # filter on t2's `b` (logical channel 3 of j1's output) between
    # the joins: b > 10
    pred = ir.Call(
        "gt", (ir.InputRef(3, T.BIGINT), ir.Constant(10, T.BIGINT)),
        T.BOOLEAN,
    )
    filtered = P.Filter(j1, pred)
    top = P.HashJoin(filtered, scan3, (0,), (0,), "inner")
    ex = Executor({"mem": mem})
    _n, rows = ex.execute(top)
    want = [
        (i, i * 10, i, i + 1, i + 2, i, -i)
        for i in range(100) if i + 1 > 10
    ]
    assert _rows_equal(rows, want)
    # lift of `b` (1) + boundary gathers of k2, c, k3, d (4): still
    # exactly one value gather per carried column
    assert ex.gathers_materialized == 5


@pytest.fixture(scope="module")
def tpch_rig():
    conn = TpchConnector(0.01)
    runner = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    return runner


Q5ISH = (
    "select n_name, sum(l_extendedprice * (1 - l_discount)) as rev "
    "from customer, orders, lineitem, supplier, nation "
    "where c_custkey = o_custkey and l_orderkey = o_orderkey "
    "and l_suppkey = s_suppkey and c_nationkey = s_nationkey "
    "and s_nationkey = n_nationkey "
    "group by n_name order by rev desc"
)


def test_q5_shaped_sql_parity_general_join_path(tpch_rig):
    """SQL-level parity on the Q5-shaped join chain through the GENERAL
    (materialized-build) path — generated joins off so the sort join +
    late materialization actually run."""
    r = tpch_rig
    r.session.set("generated_join_enabled", False)
    # late materialization is auto = TPU-only; the CPU test forces it
    r.session.set("late_materialization_enabled", "true")
    try:
        on = r.execute(Q5ISH).rows
        deferred = r.executor.gathers_deferred
        materialized = r.executor.gathers_materialized
        r.session.set("late_materialization_enabled", "false")
        off = r.execute(Q5ISH).rows
    finally:
        r.session.unset("generated_join_enabled")
        r.session.unset("late_materialization_enabled")
    assert deferred > 0 and materialized > 0
    # the chain composes: strictly fewer value gathers than the eager
    # engine's per-join gathers of the same carried columns
    assert materialized < deferred
    assert _rows_equal(on, off)


def test_q5ish_oracle_parity(tpch_rig):
    from tests.oracle import load_sqlite

    r = tpch_rig
    db = load_sqlite(
        r.catalogs["tpch"],
        ["customer", "orders", "lineitem", "supplier", "nation"],
    )
    r.session.set("generated_join_enabled", False)
    r.session.set("late_materialization_enabled", "true")
    try:
        got = r.execute(Q5ISH).rows
    finally:
        r.session.unset("generated_join_enabled")
        r.session.unset("late_materialization_enabled")
    # sqlite holds decimals as UNSCALED ints (cents); the engine's
    # decimal output is the matching unscaled int, so the comparison is
    # exact integer equality
    want = db.execute(
        "select n_name, sum(l_extendedprice * (100 - l_discount)) "
        "from customer, orders, lineitem, supplier, nation "
        "where c_custkey = o_custkey and l_orderkey = o_orderkey "
        "and l_suppkey = s_suppkey and c_nationkey = s_nationkey "
        "and s_nationkey = n_nationkey "
        "group by n_name order by 2 desc"
    ).fetchall()
    assert [(g[0], int(g[1])) for g in got] == [
        (w[0], int(w[1])) for w in want
    ]


# ---------------------------------------------------------------- fusion


Q1ISH = (
    "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
    "from lineitem where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus order by 1, 2"
)
Q6ISH = (
    "select sum(l_extendedprice * l_discount) from lineitem "
    "where l_discount between 0.05 and 0.07 and l_quantity < 24"
)


def test_fused_partial_agg_grouped(tpch_rig):
    """Q1-shaped scan→filter→project→partial-agg compiles through the
    fused pipeline (counter mirrors generated_joins_used) with exact
    parity against the unfused driver loop. Fusion is auto = TPU-only
    (the win is launch overhead), so the CPU test forces it on — same
    pattern as the Pallas-join interpret-mode tests."""
    r = tpch_rig
    r.session.set("fused_partial_agg_enabled", "true")
    try:
        on = r.execute(Q1ISH).rows
        assert r.executor.fused_partial_aggs >= 1
        r.session.set("fused_partial_agg_enabled", "false")
        off = r.execute(Q1ISH).rows
        assert r.executor.fused_partial_aggs == 0
    finally:
        r.session.unset("fused_partial_agg_enabled")
    assert on == off


def test_fused_partial_agg_global(tpch_rig):
    r = tpch_rig
    r.session.set("fused_partial_agg_enabled", "true")
    try:
        on = r.execute(Q6ISH).rows
        assert r.executor.fused_partial_aggs >= 1
        r.session.set("fused_partial_agg_enabled", "false")
        off = r.execute(Q6ISH).rows
    finally:
        r.session.unset("fused_partial_agg_enabled")
    assert on == off


def test_fused_partial_agg_shipped_plan_worker_path():
    """The distributed shape: a coordinator-planned PARTIAL fragment,
    serialized through plan_serde and executed over a round-robin
    SplitFilterConnector — exactly server/worker.py's shipped-plan
    path — must engage the fused pipeline too."""
    from presto_tpu.connectors.split_filter import SplitFilterConnector
    from presto_tpu.dist import plan_serde
    from presto_tpu.server.worker import find_partial_cut

    conn = TpchConnector(0.01)
    planner_runner = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    plan = planner_runner.plan(Q1ISH)
    cut = find_partial_cut(plan)
    assert cut is not None
    partial = dataclasses.replace(cut, step="partial")
    fragment = plan_serde.loads(plan_serde.dumps(partial))

    worker_runner = LocalRunner(
        {"tpch": SplitFilterConnector(conn, "lineitem", 0, 2)},
        page_rows=1 << 13,
    )
    # the worker applies shipped session properties the same way
    # (server/worker.py _run_task); fusion is auto=TPU-only, so the
    # CPU test ships it force-enabled
    worker_runner.session.set("fused_partial_agg_enabled", "true")
    worker_runner.apply_session()
    ex = worker_runner.executor
    pages = list(ex.pages(fragment))
    assert pages, "worker fragment produced no state pages"
    assert ex.fused_partial_aggs >= 1, (
        "shipped-plan worker path did not fuse the partial aggregation"
    )
