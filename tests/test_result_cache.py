"""ISSUE 10: the two-level result cache (presto_tpu/cache/).

Covers the subsystem contract by contract:
  - the acceptance pin: a second identical cacheable execution
    completes with result_cache_hits >= 1 and program_launches == 0
    (fragment replay skips compile+launch);
  - hit/miss/evict/TTL counter contracts at the executor and store
    levels (demotion to the disk tier still serves hits);
  - sqlite-oracle parity on cache hits;
  - snapshot invalidation: DML to the writable memory connector bumps
    snapshot_version() and forces a miss with correct fresh rows —
    including the UPDATE case where the ROW COUNT does not change
    (the write counter, not cardinality, moves the token);
  - cacheability rules (system scans, volatile calls, remote sources,
    snapshot-less connectors never cache);
  - the process-shared store under concurrency: the same statement
    from 8 client threads executes at least once, the rest hit, all
    rows identical;
  - the resident store's key (connectors/cached.py): one copy whatever
    the constraint, snapshot versioning, the invalidation registration.
"""

import collections
import re
import threading
import time

import pytest

from presto_tpu import types as T
from presto_tpu.cache import (
    ResultCache,
    shared_cache_if_exists,
    uncacheable_reason,
)
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import plan as P
from presto_tpu.expr.ir import Call
from presto_tpu.runner import LocalRunner

SF = 0.01
PAGE_ROWS = 1 << 13

AGG_Q = ("select l_returnflag, l_linestatus, count(*), "
         "sum(l_quantity), sum(l_extendedprice) from lineitem "
         "group by l_returnflag, l_linestatus "
         "order by l_returnflag, l_linestatus")
JOIN_Q = ("select o_orderpriority, count(*) c from orders join "
          "lineitem on o_orderkey = l_orderkey where l_quantity < 10 "
          "group by o_orderpriority order by o_orderpriority")


def _rows_equal(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b))


@pytest.fixture(autouse=True)
def _clean_shared_cache():
    """The store is process-shared by design; tests must not leak
    entries (or tallies another test asserts deltas over) into each
    other through it."""
    rc = shared_cache_if_exists()
    if rc is not None:
        rc.clear()
    yield
    rc = shared_cache_if_exists()
    if rc is not None:
        rc.clear()


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture()
def runner(conn):
    return LocalRunner({"tpch": conn}, page_rows=PAGE_ROWS)


# ----------------------------------------------------- acceptance pin
def test_second_run_hits_and_launches_zero(runner):
    """THE acceptance contract: identical cacheable plan, second
    execution serves from the fragment cache — >=1 hit, ZERO program
    launches (compile+launch skipped), identical rows."""
    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(AGG_Q)
    _n1, rows1 = ex.execute(plan)
    assert ex.result_cache_misses >= 1
    assert ex.result_cache_hits == 0
    _n2, rows2 = ex.execute(plan)
    assert ex.result_cache_hits >= 1
    assert ex.program_launches == 0, (
        "a cache hit must not launch fused-scan programs")
    assert rows1 == rows2


def test_replan_same_sql_still_hits(runner):
    """A fresh plan object of the same SQL lands on the same key (the
    fingerprint is structural, not identity) — the dashboard repeat
    case where every request re-plans."""
    ex = runner.executor
    ex.result_cache = ResultCache()
    _1, rows1 = ex.execute(runner.plan(AGG_Q))
    _2, rows2 = ex.execute(runner.plan(AGG_Q))
    assert ex.result_cache_hits >= 1
    assert rows1 == rows2


def test_statement_cache_skips_executor(runner):
    """Level 2: the runner returns the finished row set for an
    identical statement without executing; per-attempt gauges read 0
    for the replayed query."""
    runner.session.set("result_cache_enabled", True)
    res1 = runner.execute(AGG_Q)
    ex = runner.executor
    hits_before = ex.result_cache_hits
    res2 = runner.execute(AGG_Q)
    assert ex.result_cache_hits > hits_before
    assert ex.program_launches == 0
    assert res1.rows == res2.rows
    assert res1.column_names == res2.column_names
    assert res1.column_types == res2.column_types


def test_statement_cache_hit_zero_transfers(runner):
    """ISSUE 12 acceptance pin: a statement-cache hit crosses the
    host<->device boundary ZERO times — no page replay, no decode
    pull; the transfer gauges read 0 for the replayed query."""
    from presto_tpu.exec import xfer as XFER

    runner.session.set("result_cache_enabled", True)
    runner.execute(AGG_Q)
    ex = runner.executor
    hits_before = ex.result_cache_hits
    base = XFER.process_totals()
    runner.execute(AGG_Q)
    assert ex.result_cache_hits > hits_before
    assert ex.d2h_bytes == 0 and ex.h2d_bytes == 0, (
        "a replayed statement must not touch the device")
    assert ex.d2h_transfers == 0 and ex.h2d_transfers == 0
    assert ex.transfer_wall_s == 0
    # the per-query gauges are RESET on the hit path, so the
    # falsifiable half of the pin is the process totals: nothing
    # anywhere in the process crossed during the replay
    after = XFER.process_totals()
    assert after["h2d_bytes"] == base["h2d_bytes"]
    assert after["d2h_bytes"] == base["d2h_bytes"]
    assert after["d2h_transfers"] == base["d2h_transfers"]
    assert after["h2d_transfers"] == base["h2d_transfers"]


def test_fragment_hit_serves_host_pages_zero_transfers(runner):
    """The first redundant crossing the transfer auditor surfaced
    (ISSUE 12 satellite): a fragment-cache hit whose pages feed only
    result serialization used to device_put every stored host page
    and pull it straight back at decode. The host-serve sink now
    replays host pages directly — a full-plan hit executes with zero
    crossings either way."""
    from presto_tpu.exec import xfer as XFER

    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(AGG_Q)
    _n1, rows1 = ex.execute(plan)
    assert ex.result_cache_misses >= 1
    base = XFER.process_totals()
    _n2, rows2 = ex.execute(plan)
    assert ex.result_cache_hits >= 1
    assert rows1 == rows2
    assert ex.h2d_bytes == 0 and ex.d2h_bytes == 0, (
        "a host-served fragment replay must not round-trip the device")
    # and nothing leaked around the per-query gauges: the process
    # totals did not move either
    after = XFER.process_totals()
    assert after["h2d_bytes"] == base["h2d_bytes"]
    assert after["d2h_bytes"] == base["d2h_bytes"]


# ------------------------------------------------- counter contracts
def test_hit_miss_counters_explain_analyze(runner):
    """The four registry counters surface through execute_with_stats
    (and therefore EXPLAIN ANALYZE, /metrics, system.metrics — the
    exec/counters.py contract)."""
    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(AGG_Q)
    _n, _r, stats = ex.execute_with_stats(plan)
    ctr = stats["counters"]
    for name in ("result_cache_hits", "result_cache_misses",
                 "result_cache_evictions",
                 "result_cache_invalidations"):
        assert name in ctr, name
    assert ctr["result_cache_misses"] >= 1
    _n, _r, stats = ex.execute_with_stats(plan)
    assert stats["counters"]["result_cache_hits"] >= 1


def test_store_eviction_under_budget():
    """LRU eviction: rows entries past the resident budget evict
    oldest-first and are counted."""
    rc = ResultCache(budget_bytes=1 << 14)
    big = [("x" * 64, i) for i in range(20)]
    ev = 0
    for i in range(8):
        ev += rc.put_rows(f"k{i}", ["a", "b"], big, ["varchar", "bigint"],
                          {("m", "t")})
    assert ev > 0
    assert rc.evictions == ev
    assert rc.resident_bytes() <= 1 << 14
    # oldest keys evicted, newest still present
    assert rc.get_rows("k7") is not None
    assert rc.get_rows("k0") is None


def test_pages_demote_to_disk_still_hit(runner):
    """Host budget pressure demotes LRU page entries to the disk-tier
    PageStore; a demoted entry still serves hits (loaded back under
    the store lock)."""
    ex = runner.executor
    ex.result_cache = ResultCache()
    p1 = runner.plan(AGG_Q)
    p2 = runner.plan(JOIN_Q)
    ex.execute(p1)
    ex.execute(p2)
    rc = ex.result_cache
    assert rc.entry_count >= 2
    total = rc.total_bytes()
    # shrink the budget below the resident set: page entries demote
    # (not evict — total stays), resident drops under the new budget
    rc.configure(budget_bytes=max(total // 2, 1024))
    assert rc.resident_bytes() <= rc.budget_bytes
    assert rc.total_bytes() == total
    _n, rows1 = ex.execute(p1)
    assert ex.result_cache_hits >= 1
    # the demoted replay is still exact
    base = LocalRunner({"tpch": runner.catalogs["tpch"]},
                       page_rows=PAGE_ROWS)
    assert _rows_equal(rows1, base.execute(AGG_Q).rows)


def test_oversized_entry_never_admitted(runner):
    ex = runner.executor
    ex.result_cache = ResultCache(budget_bytes=64)  # smaller than any
    ex.execute(runner.plan(AGG_Q))                  # result set
    assert ex.result_cache.entry_count == 0
    # and the run is simply a miss, not an error
    assert ex.result_cache_misses >= 1


def test_ttl_expiry(runner):
    """An entry older than result_cache_ttl_ms reads as a miss and is
    reclaimed (counted as an eviction — age-based reclaim)."""
    ex = runner.executor
    ex.result_cache = ResultCache(ttl_ms=80)
    plan = runner.plan(AGG_Q)
    ex.execute(plan)
    ex.execute(plan)
    assert ex.result_cache_hits == 1  # inside the TTL window: hit
    time.sleep(0.12)
    ex.execute(plan)
    assert ex.result_cache_hits == 1  # aged out: no new hit
    assert ex.result_cache_misses >= 2
    assert ex.result_cache.evictions >= 1


# ------------------------------------------------------ oracle parity
def test_oracle_parity_on_hits(runner, conn):
    """The sqlite correctness gate applied to REPLAYED results: the
    hit rows match sqlite over the same generated data."""
    from tests.oracle import load_sqlite

    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(JOIN_Q)
    ex.execute(plan)
    _n, got = ex.execute(plan)   # served from cache
    assert ex.result_cache_hits >= 1
    db = load_sqlite(conn, ["orders", "lineitem"])
    want = db.execute(
        "select o_orderpriority, count(*) from orders join lineitem "
        "on o_orderkey = l_orderkey where l_quantity < 1000 "
        "group by o_orderpriority order by o_orderpriority"
    ).fetchall()
    # l_quantity is decimal(12,2): engine-internal unscaled ints in
    # sqlite, so < 10 in SQL is < 1000 unscaled on the oracle side
    assert [tuple(r) for r in want] == [tuple(r) for r in got]


# ------------------------------------------- snapshot invalidation
@pytest.fixture()
def mem_runner():
    return LocalRunner(
        {"mem": MemoryConnector(), "tpch": TpchConnector(SF)},
        default_catalog="mem",
    )


def test_memory_dml_bumps_snapshot_and_misses(mem_runner):
    """INSERT moves snapshot_version -> the repeated statement misses
    and returns fresh (ground-truth-verified) rows."""
    r = mem_runner
    r.session.set("result_cache_enabled", True)
    r.execute("create table t as select 1 x, 10 y")
    r.execute("insert into t select 2, 20")
    conn = r.catalogs["mem"]
    v0 = conn.snapshot_version("t")
    q = "select count(*), sum(y) from t"
    res1 = r.execute(q)
    assert res1.rows == [(2, 30)]
    ex = r.executor
    hits0 = ex.result_cache_hits
    res2 = r.execute(q)
    assert ex.result_cache_hits > hits0          # unchanged data: hit
    assert res2.rows == [(2, 30)]
    r.execute("insert into t select 3, 300")
    assert conn.snapshot_version("t") != v0      # the token moved
    assert ex.result_cache_invalidations >= 1    # eager reclaim ran
    hits1 = ex.result_cache_hits
    res3 = r.execute(q)
    assert ex.result_cache_hits == hits1         # stale key: no hit
    assert res3.rows == [(3, 330)]               # fresh, correct


def test_update_same_cardinality_invalidates(mem_runner):
    """THE write-counter case: UPDATE preserves the row count, so a
    row-count-derived token would falsely serve the stale sum — the
    memory connector's explicit write version must force the miss."""
    r = mem_runner
    r.session.set("result_cache_enabled", True)
    r.execute("create table u as select 1 k, 100 v")
    r.execute("insert into u select 2, 200")
    q = "select sum(v) from u"
    assert r.execute(q).rows == [(300,)]
    assert r.execute(q).rows == [(300,)]         # cached
    rc0 = r.catalogs["mem"].row_count("u")
    v0 = r.catalogs["mem"].snapshot_version("u")
    r.execute("update u set v = 999 where k = 2")
    assert r.catalogs["mem"].row_count("u") == rc0   # same cardinality
    assert r.catalogs["mem"].snapshot_version("u") != v0
    assert r.execute(q).rows == [(1099,)]        # fresh rows, not 300


def test_view_replacement_moves_statement_key(mem_runner):
    """CREATE OR REPLACE VIEW must not serve the OLD view's cached
    rows: the statement key fingerprints the view-EXPANDED plan, so
    redefinition moves it."""
    r = mem_runner
    r.session.set("result_cache_enabled", True)
    r.execute("create table base as select 1 a, 2 b")
    r.execute("create view v as select a from base")
    assert r.execute("select * from v").rows == [(1,)]
    assert r.execute("select * from v").rows == [(1,)]  # cached
    r.execute("create or replace view v as select b from base")
    assert r.execute("select * from v").rows == [(2,)], (
        "stale pre-replacement view rows served from the cache")


def test_fragment_key_salted_by_session_config(runner):
    """Two sessions with different collect_k / page_rows must never
    address one fragment entry (the store is process-shared)."""
    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(AGG_Q)
    ex._select_cache_points(plan)
    keys1 = {e[0] for e in ex._cache_points.values()}
    ex.collect_k = ex.collect_k * 2
    ex._select_cache_points(plan)
    keys2 = {e[0] for e in ex._cache_points.values()}
    ex.page_rows = ex.page_rows * 2
    ex._select_cache_points(plan)
    keys3 = {e[0] for e in ex._cache_points.values()}
    ex._cache_points = {}
    assert keys1 and keys1.isdisjoint(keys2)
    assert keys2.isdisjoint(keys3)


def test_memory_limit_enforced_on_replay(runner):
    """A cache hit still passes the per-query memory accounting: a
    limit that rejects the pages cold rejects them replayed."""
    from presto_tpu.exec.executor import MemoryBudgetExceeded

    ex = runner.executor
    ex.result_cache = ResultCache()
    plan = runner.plan(AGG_Q)
    ex.execute(plan)  # populate
    ex.max_memory_bytes = 8
    try:
        with pytest.raises(MemoryBudgetExceeded):
            ex.execute(plan)
    finally:
        ex.max_memory_bytes = None


def test_delete_and_drop_invalidate(mem_runner):
    r = mem_runner
    r.session.set("result_cache_enabled", True)
    r.execute("create table d as select 1 a union all select 2 a")
    q = "select count(*) from d"
    assert r.execute(q).rows == [(2,)]
    assert r.execute(q).rows == [(2,)]
    r.execute("delete from d where a = 2")
    assert r.execute(q).rows == [(1,)]


# --------------------------------------------------- cacheability rules
def test_system_scans_never_cache(runner):
    plan = runner.plan("select * from system.catalogs")
    reason = uncacheable_reason(plan, runner.catalogs)
    assert reason is not None and "system" in reason


def test_volatile_function_never_caches(runner):
    scan = P.TableScan("tpch", "nation", ("n_nationkey",))
    vol = P.Project(scan, (Call("random", (), T.DOUBLE),))
    reason = uncacheable_reason(P.Output(vol, ("r",)), runner.catalogs)
    assert reason is not None and "random" in reason


def test_remote_source_never_caches(runner):
    rs = P.RemoteSource((T.BIGINT,), key="stage1")
    assert uncacheable_reason(P.Output(rs, ("x",)),
                              runner.catalogs) is not None


def test_snapshotless_connector_never_caches(runner):
    class NoCount:
        def row_count(self, t):
            raise NotImplementedError

    from presto_tpu.connectors.base import Connector

    class NoSnap(Connector):
        pass

    cats = dict(runner.catalogs)
    cats["weird"] = NoSnap()
    plan = P.Output(
        P.Aggregation(P.TableScan("weird", "t", ("a",)), (), ()),
        ("c",))
    assert uncacheable_reason(plan, cats) is not None


def test_split_filter_token_carries_split_identity(conn):
    """Two tasks of one fragment on different split shares must never
    share a cache key: the SplitFilterConnector's snapshot token
    carries (index, count) for the filtered table — and only for it."""
    from presto_tpu.connectors.split_filter import (
        HashSplitConnector,
        SplitFilterConnector,
    )

    w0 = SplitFilterConnector(conn, "lineitem", 0, 2)
    w1 = SplitFilterConnector(conn, "lineitem", 1, 2)
    assert w0.snapshot_version("lineitem") != \
        w1.snapshot_version("lineitem")
    # unfiltered tables share the inner token (whole-table scans on
    # every worker ARE the same content)
    assert w0.snapshot_version("orders") == \
        w1.snapshot_version("orders")
    assert w0.snapshot_version("orders") == \
        conn.snapshot_version("orders")
    h0 = HashSplitConnector(conn, {"lineitem": "l_orderkey"}, 0, 2)
    h1 = HashSplitConnector(conn, {"lineitem": "l_orderkey"}, 1, 2)
    assert h0.snapshot_version("lineitem") != \
        h1.snapshot_version("lineitem")
    assert h0.snapshot_version("nation") == \
        conn.snapshot_version("nation")


# ------------------------------------------------ concurrent clients
def test_concurrent_clients_share_one_execution(conn):
    """Same statement from 8 concurrent protocol clients against one
    server: >= 1 real execution, the rest hit the process-shared
    store, every client gets identical rows."""
    from presto_tpu.client import StatementClient
    from presto_tpu.server.http_server import PrestoTpuServer
    import urllib.request

    srv = PrestoTpuServer({"tpch": conn}, port=0,
                          default_catalog="tpch")
    port = srv.start()
    try:
        results = [None] * 8
        errors = []

        def go(i):
            try:
                cl = StatementClient(f"http://127.0.0.1:{port}",
                                     user=f"u{i}", catalog="tpch")
                cl.session_properties["result_cache_enabled"] = "true"
                res = cl.execute(AGG_Q)
                assert res.error is None, res.error
                results[i] = res.rows
            except Exception as e:  # noqa: BLE001 - surfaced in the
                errors.append(e)    # main thread's assert below

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert all(r is not None for r in results)
        for r in results[1:]:
            assert r == results[0]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as f:
            metrics = f.read().decode()

        def metric(name):
            m = re.search(rf"^{name} (\d+)", metrics, re.M)
            return int(m.group(1)) if m else 0

        hits = metric("presto_tpu_result_cache_hits_total")
        misses = metric("presto_tpu_result_cache_misses_total")
        assert misses >= 1, "at least one real execution"
        assert hits >= 7, (
            f"8 identical statements should mostly hit (hits={hits}, "
            f"misses={misses})")
    finally:
        srv.stop()


# --------------------------- the resident store's key (connectors/cached.py)
class _CountingConnector(MemoryConnector):
    """Counts loads: a table is loaded by one walk of its splits."""

    def __init__(self):
        super().__init__()
        self.loads = 0

    def splits(self, table, target_rows):
        self.loads += 1
        return super().splits(table, target_rows)


def test_caching_connector_canonical_constraint_key():
    """Structurally equal constraints built as distinct objects must
    share one stored copy. (The page cache this wrapper once was keyed
    its page lists by the constraint, canonically encoded; the store
    holds a table once and the constraint is no part of its key, ISSUE
    33: any constraint, equal or not, is served from the one copy.)"""
    inner = _CountingConnector()
    inner.create_table("t", ["a", "b"], [T.BIGINT, T.BIGINT],
                       [(i, i * 2) for i in range(10)])
    cc = ResidentConnector(inner)
    c1 = (("a", 2, None),)
    c2 = tuple([("a", 2, None)])  # distinct object, same structure
    r1 = [p for p in cc.pages("t", constraint=c1)]
    assert cc.resident_loads == 1
    loads = inner.loads
    r2 = [p for p in cc.pages("t", constraint=c2)]
    r3 = [p for p in cc.pages("t", constraint=(("b", 4, 8),))]
    assert cc.resident_loads == 1, "second scan must hit the store"
    # (each pages() call asks the inner connector for its splits, once)
    assert inner.loads == loads + 2
    assert len(r1) == len(r2) == len(r3)


def test_caching_connector_snapshot_and_invalidate():
    """Wrapping a WRITABLE connector is safe: the inner snapshot
    version is the stored copy's key, and the invalidation path
    (runner._invalidate_caches -> invalidate()) reclaims bytes."""
    inner = _CountingConnector()
    inner.create_table("t", ["a"], [T.BIGINT], [(1,), (2,)])
    cc = ResidentConnector(inner)
    rows = [r for p in cc.pages("t") for r in p.to_pylist()]
    assert len(rows) == 2
    assert cc.resident_loads == 1
    inner.insert("t", [(3,)])  # write THROUGH the wrapper's inner
    rows = [r for p in cc.pages("t") for r in p.to_pylist()]
    assert len(rows) == 3, "stale copy served after a write"
    assert cc.resident_loads == 2
    assert cc.resident_table_bytes > 0
    assert cc.invalidate("t") > 0
    assert cc.resident_table_bytes == 0


def test_runner_invalidation_reaches_wrapped_connector():
    """The runner's write path drops a wrapping page cache's stale
    lists through the registered invalidation hook."""
    inner = MemoryConnector()
    cc = ResidentConnector(inner)
    r = LocalRunner({"mem": cc}, default_catalog="mem")
    r.execute("create table t as select 1 x")
    assert r.execute("select * from t").rows == [(1,)]
    r.execute("insert into t select 2")
    assert sorted(r.execute("select * from t").rows) == [(1,), (2,)]


# ---------------------------------------------- mesh-path residency
def _mesh_runner(conn, n=2):
    """A DistExecutor runner over an n-device CPU mesh (conftest
    forces the host platform device count)."""
    from presto_tpu.dist.executor import make_mesh
    from presto_tpu.session import Session

    return LocalRunner(
        {"tpch": conn}, default_catalog="tpch", page_rows=PAGE_ROWS,
        mesh=make_mesh(n),
        session=Session(catalog="tpch",
                        properties={"result_cache_enabled": True}),
    )


def test_mesh_root_hit_zero_crossings(conn):
    """Transfer-ledger pin (ISSUE 15 satellite): a fragment hit at
    the mesh root serves host pages straight through the extended
    sink chain (Output + gather-over-replicated pass-throughs) —
    ZERO h2d/d2h crossings on the replay."""
    from presto_tpu.exec import xfer as XF

    r = _mesh_runner(conn)
    r.apply_session()
    ex = r.executor
    plan = r.plan(AGG_Q)
    _, rows1 = ex.execute(plan)
    assert ex.result_cache_hits == 0
    base = XF.process_totals()
    _, rows2 = ex.execute(plan)
    assert rows1 == rows2
    assert ex.result_cache_hits >= 1
    assert ex.h2d_bytes == 0 and ex.d2h_bytes == 0
    # falsifiable process-totals delta, not just the per-query gauges
    now = XF.process_totals()
    assert now["h2d_bytes"] == base["h2d_bytes"]
    assert now["d2h_bytes"] == base["d2h_bytes"]


def test_mesh_midplan_replicated_point_hits(conn):
    """Mesh-path cache residency (ROADMAP item 6 remainder): a mesh
    query whose ROOT is uncacheable still caches its REPLICATED
    interior — the hit replays host pages (staged as mesh-replicated
    arrays only for the device consumer above) and SKIPS the
    gathered subtree's collectives entirely."""
    from presto_tpu.exec import plan as PP

    r = _mesh_runner(conn)
    r.apply_session()
    ex = r.executor
    base = r.plan("select l_returnflag rf, sum(l_quantity) s "
                  "from lineitem group by l_returnflag")
    # UniqueId above the interior makes the root uncacheable; the
    # replicated aggregated interior below is the mesh cache point
    plan = PP.Output(source=PP.UniqueId(source=base.source),
                     names=("rf", "s", "uid"))
    _, rows1 = ex.execute(plan)
    assert ex.result_cache_misses >= 1
    m0 = ex.mesh_local_exchanges
    _, rows2 = ex.execute(plan)
    assert rows1 == rows2
    assert ex.result_cache_hits >= 1, (
        "no mid-plan cache point selected on the mesh (replicated "
        "subtrees must be eligible)")
    # the replayed subtree's compiled collectives never ran again
    assert ex.mesh_local_exchanges == m0
