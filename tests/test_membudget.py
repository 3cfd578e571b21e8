"""Device-memory governor (exec/membudget.py): plan-time HBM budget
accounting + chunked pipeline rewrites.

Reference: presto-main memory/MemoryPool + the spill decisions made
under memory pressure — except the TPU translation decides BEFORE
compile: every buffer capacity rides the shapes.py ladder, so a
pipeline's footprint is static. These tests force tiny artificial
budgets (and fault lines) at SF0.01 so the chunked rewrites engage on
CPU, and pin (a) sqlite-oracle / default-budget parity — chunked
execution must be exactly the same answer — and (b) the
memory_chunked_pipelines / peak_device_bytes observability contract.
"""

import collections

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import membudget as MB
from presto_tpu.exec import shapes as SH
from presto_tpu.runner import LocalRunner


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(0.01)


@pytest.fixture(scope="module")
def base(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13)


def _rows_equal(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b)
    )


JOIN_Q = (
    "select o_orderkey, sum(l_extendedprice), count(*) "
    "from orders, lineitem where o_orderkey = l_orderkey "
    "group by o_orderkey order by 2 desc, 1 limit 7"
)
SCAN_AGG_Q = (
    "select l_returnflag, l_linestatus, sum(l_quantity), "
    "sum(l_extendedprice), count(*) from lineitem "
    "where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus order by 1, 2"
)


# ------------------------------------------------------------- model
def test_resolve_budget_cpu_is_generous():
    # auto on CPU: tier-1 behavior must not change without a forced
    # tiny budget
    assert MB.resolve_budget(0, "cpu") == MB.CPU_BUDGET
    assert MB.resolve_budget(12345, "cpu") == 12345
    assert MB.resolve_budget(12345, "tpu") == 12345


def test_rows_cap_on_ladder():
    cap = MB.rows_cap(100, 1 << 20, None, 4)  # 256 KiB share / 100 B
    assert cap is not None
    assert cap & (cap - 1) == 0  # power of two (rounded DOWN)
    assert cap * 100 <= (1 << 20) // 4
    # fault line wins when tighter
    assert MB.rows_cap(1, 1 << 40, 4096, 4) == 4096
    assert MB.rows_cap(100, 0, None, 4) is None


def test_parts_for_fits_both_caps():
    # 64M rows at 32 B against a 2M-row line: 32 passes
    assert SH.parts_for(60_000_000, 32, rows_cap=1 << 21,
                        bytes_cap=None) == 32
    # byte cap binds harder than the row cap (but never past the
    # 256-pass ceiling the legacy _spill_partitions shares)
    p = SH.parts_for(1 << 20, 1024, rows_cap=1 << 21,
                     bytes_cap=1 << 22)
    assert p == 256  # 1 GiB / 4 MiB
    assert SH.parts_for(100, 8, rows_cap=None, bytes_cap=None) == 1
    assert SH.parts_for(1 << 30, 64, rows_cap=8, bytes_cap=8) == 256


def test_buffer_bytes_is_the_allocation():
    # the model predicts LADDER allocations, not raw row counts
    assert SH.buffer_bytes(1000, 10) == 1024 * 10


# ------------------------------------- forced chunked rewrites (CPU)
def test_tiny_budget_chunks_join_oracle_exact(conn, base):
    """A budget small enough that the Q3-shaped join cannot hold its
    build in one pass: the governor grace-partitions it, probe pages
    position-chunk, and the answer is bit-identical."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("device_memory_budget", 1 << 21)  # 2 MiB
    r.session.set("generated_join_enabled", False)  # force real builds
    want = base.execute(JOIN_Q).rows
    got = r.execute(JOIN_Q).rows
    assert r.executor.memory_chunked_pipelines > 0, (
        "tiny budget should have forced a chunked rewrite"
    )
    assert _rows_equal(want, got), (want[:3], got[:3])


def test_tiny_budget_chunks_scan_agg_oracle_exact(conn, base):
    """Generation-chunked scan: page size shrinks to fit the budget
    share, the Q1-shaped pipeline streams through smaller resident
    buffers, same answer (the SF100 mechanism at SF0.01)."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("device_memory_budget", 1 << 20)  # 1 MiB
    want = base.execute(SCAN_AGG_Q).rows
    got = r.execute(SCAN_AGG_Q).rows
    ex = r.executor
    assert ex.memory_chunked_pipelines > 0
    schema = conn.table_schema("lineitem")
    types = [schema.column_type(c) for c in schema.column_names()]
    assert ex._governed_target_rows(types, count=False) < (1 << 13)
    assert _rows_equal(want, got), (want[:3], got[:3])


def test_fault_rows_ceiling_chunks_everything(conn, base):
    """Forcing the device fault line down to 4k rows (the CPU stand-in
    for the >=4M-row fault) bounds every governed buffer — scan
    pages, join builds, join outputs — and execution stays exact."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.apply_session()
    r.executor.fault_rows = 1 << 12
    for q in (JOIN_Q, SCAN_AGG_Q):
        want = base.execute(q).rows
        got = r.execute(q).rows
        assert _rows_equal(want, got), (q, want[:3], got[:3])
    assert r.executor.memory_chunked_pipelines > 0


def test_sqlite_oracle_parity_under_tiny_budget(conn):
    """The sqlite correctness gate against the forced-chunked
    engine: sqlite computes the same join-aggregate."""
    from tests.oracle import load_sqlite

    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("device_memory_budget", 1 << 21)
    r.session.set("generated_join_enabled", False)
    got = r.execute(JOIN_Q).rows
    assert r.executor.memory_chunked_pipelines > 0
    db = load_sqlite(conn, ["orders", "lineitem"])
    want = db.execute(
        "select o_orderkey, sum(l_extendedprice), count(*) "
        "from orders join lineitem on o_orderkey = l_orderkey "
        "group by o_orderkey order by 2 desc, 1 limit 7"
    ).fetchall()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[2] == w[2]
        assert abs(g[1] - w[1]) < 1e-4 * max(abs(w[1]), 1)


# --------------------------------------------------- observability
def test_explain_analyze_exposes_governor_counters(conn):
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("device_memory_budget", 1 << 20)
    r.apply_session()
    plan = r.plan(SCAN_AGG_Q)
    _names, _rows, stats = r.executor.execute_with_stats(plan)
    ctr = stats["counters"]
    assert ctr["peak_device_bytes"] > 0
    assert ctr["memory_chunked_pipelines"] > 0
    # and they render into the EXPLAIN ANALYZE text
    from presto_tpu.runner import explain_text

    text = explain_text(plan, stats=stats)
    assert "peak_device_bytes" in text
    assert "memory_chunked_pipelines" in text


def test_static_audit_matches_execution_decisions(conn):
    """membudget.audit predicts chunked rewrites from the plan alone —
    same sizing functions, no execution."""
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("device_memory_budget", 1 << 21)
    r.session.set("generated_join_enabled", False)
    r.apply_session()
    plan = r.plan(JOIN_Q)
    report = MB.audit(r.executor, plan)
    assert report.budget == 1 << 21
    assert report.chunked_count > 0
    assert report.buffers  # scans + build + output recorded
    assert report.max_buffer_bytes > 0
    # rendering never touches the device
    assert "governed rewrites" in MB.render(report)


def test_stats_driven_broadcast_flips_with_size(conn, base):
    """Satellite: the broadcast-vs-partitioned decision follows the
    build side's BYTE footprint against the per-chip share (exact
    generator row counts x row width), not a fixed row threshold — the
    same plan flips as the budget share moves across the build size."""
    from presto_tpu.exec import plan as P
    from presto_tpu.dist.fragmenter import add_exchanges

    plan = base.plan(
        "select o_orderkey, c_custkey from customer, orders "
        "where c_custkey = o_custkey"
    )

    def kinds(n, out):
        if isinstance(n, P.Exchange):
            out.append(n.kind)
        for c in n.children():
            kinds(c, out)
        return out

    roomy, _ = add_exchanges(
        plan, base.catalogs,
        broadcast_bytes=1 << 40, row_bytes_of=lambda n: 64,
    )
    tight, _ = add_exchanges(
        plan, base.catalogs,
        broadcast_bytes=64, row_bytes_of=lambda n: 64,
    )
    assert "broadcast" in kinds(roomy, [])
    assert "broadcast" not in kinds(tight, [])
    assert "repartition" in kinds(tight, [])


def test_dist_budget_is_mesh_share(conn):
    from presto_tpu.dist.executor import DistExecutor, make_mesh

    mesh = make_mesh(2)
    ex = DistExecutor({"tpch": conn}, mesh)
    ex.device_memory_budget = 1 << 30
    from presto_tpu.exec.executor import Executor

    solo = Executor({"tpch": conn})
    solo.device_memory_budget = 1 << 30
    assert ex._budget() == 2 * solo._budget()


def test_etc_key_seeds_session_default(tmp_path):
    from presto_tpu.config import server_from_etc

    (tmp_path / "catalog").mkdir()
    (tmp_path / "config.properties").write_text(
        "http-server.http.port=0\n"
        "device-memory.budget=123456789\n"
    )
    (tmp_path / "catalog" / "tiny.properties").write_text(
        "connector.name=tpch\ntpch.scale-factor=0.001\n"
    )
    server = server_from_etc(str(tmp_path))
    from presto_tpu.session import Session

    session = Session()
    runner = server.manager._runner_factory(session)
    assert session.get("device_memory_budget") == 123456789
    runner.apply_session()
    assert runner.executor.device_memory_budget == 123456789


# -------------------------------------------- SF10/SF100 dry audits
@pytest.mark.slow
def test_sf10_join_plans_stay_under_fault_line():
    """The acceptance criterion behind deleting BENCH_INCLUDE_SF10_JOINS:
    under TPU assumptions (default HBM budget, the fault line),
    every buffer the governor plans for the Q3/Q5 SF10 join pipelines
    stays under the >=4M-row line BY CONSTRUCTION. Static — no pages
    are generated; the SF10 connector is just metadata here."""
    from tests.tpch_queries import QUERIES

    conn10 = TpchConnector(10.0)
    r = LocalRunner({"tpch": conn10}, page_rows=1 << 18)
    r.apply_session()
    ex = r.executor
    ex.device_memory_budget = (16 << 30) * 7 // 8  # one v5e chip
    ex.fault_rows = SH.SAFE_BUFFER_ROWS
    for qid in (3, 5):
        report = MB.audit(ex, r.plan(QUERIES[qid]))
        over = [b for b in report.buffers
                if b.rows >= SH.DEVICE_FAULT_ROWS]
        assert not over, (qid, [(b.label, b.rows) for b in over])
        assert not report.over_budget(), (
            qid, [(b.label, b.bytes) for b in report.over_budget()])


@pytest.mark.slow
def test_sf100_scan_agg_plans_fixed_resident_buffers():
    """The q1_sf100 on-ramp: 600M rows stream through governed
    fixed-size generation buffers — the plan's footprint is independent
    of the table size."""
    from tests.tpch_queries import QUERIES

    conn100 = TpchConnector(100.0)
    r = LocalRunner({"tpch": conn100}, page_rows=1 << 20)
    r.apply_session()
    ex = r.executor
    ex.device_memory_budget = (16 << 30) * 7 // 8  # one v5e chip
    ex.fault_rows = SH.SAFE_BUFFER_ROWS
    for qid in (1, 6):
        report = MB.audit(ex, r.plan(QUERIES[qid]))
        assert report.ok, (qid, MB.render(report))
        assert report.max_buffer_bytes < report.budget
