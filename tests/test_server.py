"""Coordinator protocol tests: /v1/statement paging, session headers,
DDL via the wire, error surfaces, cancel, CLI client round trip.

Reference test analog: TestingPrestoServer + client protocol tests
(presto-main server/testing, presto-client)."""

import json
import urllib.request

import pytest

from presto_tpu.client import StatementClient
from presto_tpu.connectors.blackhole import BlackholeConnector
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.server import PrestoTpuServer


@pytest.fixture(scope="module")
def server():
    srv = PrestoTpuServer(
        {
            "tpch": TpchConnector(scale=0.001),
            "memory": MemoryConnector(),
            "blackhole": BlackholeConnector(),
        },
        port=0,  # ephemeral
        page_rows=1 << 12,
    )
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def client(server):
    return StatementClient(server=f"http://127.0.0.1:{server.port}")


def test_simple_query(client):
    res = client.execute("select 1 + 1 as two")
    assert res.error is None
    assert [c["name"] for c in res.columns] == ["two"]
    assert res.rows == [[2]]
    assert res.state == "FINISHED"


def test_scan_aggregate(client):
    res = client.execute(
        "select count(*), sum(n_nationkey) from nation"
    )
    assert res.error is None
    assert res.rows == [[25, 300]]
    assert res.columns[0]["type"] == "bigint"


def test_paged_results(client):
    # more rows than one protocol page (4096) forces nextUri paging
    res = client.execute(
        "select l_orderkey from lineitem"
    )
    assert res.error is None
    assert len(res.rows) > 4096


def test_ddl_roundtrip(client):
    res = client.execute(
        "create table memory.n2 as select n_name, n_regionkey from nation"
    )
    assert res.update_type == "CREATE TABLE AS"
    res = client.execute(
        "select count(*) from memory.n2"
    )
    assert res.rows == [[25]]
    res = client.execute("show tables from memory")
    assert ["n2"] in res.rows
    client.execute("drop table memory.n2")
    res = client.execute("show tables from memory")
    assert ["n2"] not in res.rows


def test_set_session_roundtrip(client):
    res = client.execute("set session tpu_offload_enabled = false")
    assert res.update_type == "SET SESSION"
    # client carries the property forward (X-Presto-Set-Session echo)
    assert client.session_properties["tpu_offload_enabled"] == "false"
    res = client.execute("select count(*) from region")
    assert res.rows == [[5]]
    client.execute("set session tpu_offload_enabled = true")
    assert client.session_properties["tpu_offload_enabled"] == "true"


def test_show_session(client):
    res = client.execute("show session")
    names = [r[0] for r in res.rows]
    assert "tpu_offload_enabled" in names
    assert "join_distribution_type" in names


def test_session_catalog(server):
    """X-Presto-Catalog steers unqualified names and write targets."""
    c = StatementClient(
        server=f"http://127.0.0.1:{server.port}", catalog="memory"
    )
    res = c.execute("create table t3 as select 42 as x")
    assert res.error is None, res.error
    assert res.update_type == "CREATE TABLE AS"
    res = c.execute("select x from t3")
    assert res.rows == [[42]]
    res = c.execute("show tables")
    assert ["t3"] in res.rows
    c.execute("drop table t3")


def test_error_surface(client):
    res = client.execute("select bogus_column from nation")
    assert res.error is not None
    assert res.state == "FAILED"
    assert "bogus_column" in res.error["message"]


def test_syntax_error(client):
    res = client.execute("selec 1")
    assert res.error is not None


def test_info_endpoints(server, client):
    base = f"http://127.0.0.1:{server.port}"
    with urllib.request.urlopen(f"{base}/v1/info") as r:
        info = json.loads(r.read())
    assert info["coordinator"] is True
    res = client.execute("select 1 as x")
    with urllib.request.urlopen(
        f"{base}/v1/query/{res.query_id}"
    ) as r:
        qinfo = json.loads(r.read())
    assert qinfo["state"] == "FINISHED"
    assert qinfo["rowCount"] == 1


def test_cli_execute(server, capsys):
    from presto_tpu.cli import main

    rc = main([
        "--server", f"http://127.0.0.1:{server.port}",
        "--execute", "select r_name from region order by r_name limit 2",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "r_name" in out and "(2 rows)" in out


def test_metrics_endpoint(server, client):
    # run one query so counters are non-zero, then scrape
    client.execute("select 1")
    with urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics"
    ) as resp:
        assert resp.status == 200
        assert "text/plain" in resp.headers["Content-Type"]
        body = resp.read().decode()
    assert "presto_tpu_uptime_seconds" in body
    assert 'presto_tpu_queries_total{state="FINISHED"}' in body
    assert "presto_tpu_rows_returned_total" in body


def test_event_listener_spi():
    """Reference: spi/eventlistener — created/completed events fire with
    final state; a throwing listener never fails the query."""
    from presto_tpu.events import EventListener

    seen = {"created": [], "completed": []}

    class Recorder(EventListener):
        def query_created(self, e):
            seen["created"].append(e)

        def query_completed(self, e):
            seen["completed"].append(e)

    class Thrower(EventListener):
        def query_created(self, e):
            raise RuntimeError("listener bug")

    srv = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.001)}, port=0,
        event_listeners=[Thrower(), Recorder()],
    )
    srv.start()
    try:
        c = StatementClient(server=f"http://127.0.0.1:{srv.port}")
        res = c.execute("select count(*) from nation")
        assert res.error is None
        bad = c.execute("select nope from nowhere")
        assert bad.error is not None
    finally:
        srv.stop()
    assert len(seen["created"]) == 2
    states = sorted(e.state for e in seen["completed"])
    assert states == ["FAILED", "FINISHED"]
    done = [e for e in seen["completed"] if e.state == "FINISHED"][0]
    assert done.row_count == 1 and done.wall_ms >= 0
    failed = [e for e in seen["completed"] if e.state == "FAILED"][0]
    assert failed.error_name


def test_heartbeat_failure_detector():
    """Reference: failureDetector/HeartbeatFailureDetector — a peer goes
    FAILED after consecutive missed pings and recovers on success."""
    from presto_tpu.server.heartbeat import HeartbeatFailureDetector

    peer = PrestoTpuServer({"tpch": TpchConnector(scale=0.001)}, port=0)
    peer.start()
    uri = f"http://127.0.0.1:{peer.port}"
    det = HeartbeatFailureDetector([uri], fail_after=2, timeout_s=0.5)
    det.check_once()
    assert det.is_alive(uri)
    assert det.snapshot()[0]["state"] == "ALIVE"
    peer.stop()
    det.check_once()
    assert det.is_alive(uri)  # one miss is not failure
    det.check_once()
    assert not det.is_alive(uri)
    assert det.snapshot()[0]["state"] == "FAILED"
    # node comes back: first success revives it (reference: rejoin
    # between queries)
    peer2 = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.001)}, port=peer.port
    )
    try:
        peer2.start()
        det.check_once()
        assert det.is_alive(uri)
    finally:
        peer2.stop()


def test_monitored_server_exposes_node_view():
    peer = PrestoTpuServer({"tpch": TpchConnector(scale=0.001)}, port=0)
    peer.start()
    mon = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.001)}, port=0,
        peer_uris=[f"http://127.0.0.1:{peer.port}"],
    )
    mon.start()
    try:
        mon.failure_detector.check_once()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mon.port}/v1/node"
        ) as resp:
            nodes = json.loads(resp.read())
        assert len(nodes) == 1 and nodes[0]["state"] == "ALIVE"
    finally:
        mon.stop()
        peer.stop()


def test_resource_group_admission():
    """Reference: resourceGroups/* — queue-full rejection (429 /
    QUERY_QUEUE_FULL) and per-group running/queued accounting."""
    import json as _json
    import threading
    import urllib.error

    from presto_tpu.server.resource_groups import (
        ResourceGroupManager,
        ResourceGroupSpec,
    )

    rg = ResourceGroupManager([
        ResourceGroupSpec("tiny", ".*", hard_concurrency=1, max_queued=1),
    ])
    srv = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.001)}, port=0, resource_groups=rg,
    )
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # hold the device with a slowish query, then flood the queue
        slow_sql = ("select count(*) from lineitem l1, lineitem l2 "
                    "where l1.l_orderkey = l2.l_orderkey")
        results = []

        def run_slow():
            c = StatementClient(server=base)
            results.append(c.execute(slow_sql))

        threads = [threading.Thread(target=run_slow) for _ in range(3)]
        for t in threads:
            t.start()
        # with concurrency 1 + queue 1, at least one of three concurrent
        # submissions must be rejected with 429
        rejected = 0
        for t in threads:
            t.join()
        rejected = sum(
            1 for r in results
            if r.error and r.error.get("errorName") == "QUERY_QUEUE_FULL"
        )
        finished = sum(1 for r in results if r.error is None)
        assert finished >= 1 and rejected >= 1, [
            (r.state, r.error) for r in results
        ]
        with urllib.request.urlopen(base + "/v1/resourceGroup") as resp:
            snap = _json.loads(resp.read())
        assert snap[0]["name"] == "tiny"
        assert snap[0]["running"] == 0 and snap[0]["queued"] == 0
    finally:
        srv.stop()


# ------------------------------------------- concurrent query execution

def test_concurrent_queries_under_memory_budget():
    """With a memory budget configured, the global device lock is
    replaced by footprint admission (reference: ClusterMemoryManager):
    the same three clients submitting at once execute ONE AT A TIME on
    the default server and CONCURRENTLY (overlapping execution
    intervals) under the budget, with identical rows. Judged on the
    event spy's records of when each query executed, never on how long
    a round took: this box's clock says nothing about admission."""
    import threading

    queries = [
        "select count(*), sum(o_totalprice) from orders",
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority",
        "select count(*) from lineitem where l_quantity < 25",
    ]

    class _Spy:
        """(query, execution start, execution end) per completed
        query, on the coordinator's clock: the executor's run begins
        after admission and ends with the last page."""

        def __init__(self):
            self.executed = []
            self.arrived = threading.Condition()

        def query_completed(self, e):
            # the synthetic `local` stage spans the executor's run;
            # its offsets count from the trace's own wall anchor
            info = e.query_info
            (stage,) = info["stages"]
            with self.arrived:
                self.executed.append((
                    e.sql,
                    info["createTime"] + stage["startMs"] / 1000.0,
                    info["createTime"] + stage["endMs"] / 1000.0,
                ))
                self.arrived.notify_all()

    def run_round(srv, spy):
        """All three statements submitted at once; returns their rows
        and the longest pairwise overlap of execution intervals."""
        base = f"http://127.0.0.1:{srv.port}"
        results = [None] * len(queries)
        go = threading.Barrier(len(queries))

        def one(i):
            c = StatementClient(server=base)
            # every round must EXECUTE: the budgeted server would
            # otherwise answer repeats from its result cache, which
            # bypasses admission (and leaves no execution record)
            c.session_properties["result_cache_enabled"] = "false"
            go.wait()
            results[i] = c.execute(queries[i]).rows

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(queries))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # completion events are delivered after the client has its
        # rows: wait for all three records, then take them
        with spy.arrived:
            assert spy.arrived.wait_for(
                lambda: len(spy.executed) == len(queries), timeout=60)
            spans = sorted((s, e) for _, s, e in spy.executed)
            spy.executed.clear()
        overlap = max(
            min(e1, e2) - max(s1, s2)
            for i, (s1, e1) in enumerate(spans)
            for s2, e2 in spans[i + 1:]
        )
        return results, overlap

    def rounds(**server_kw):
        spy = _Spy()
        srv = PrestoTpuServer({"tpch": conn}, port=0, page_rows=1 << 13,
                              event_listeners=[spy], **server_kw)
        srv.start()
        try:
            run_round(srv, spy)  # warm compile caches / runners
            return [run_round(srv, spy) for _ in range(3)]
        finally:
            srv.stop()

    conn = TpchConnector(0.01)
    # startMs/endMs are whole milliseconds: back-to-back executions
    # may appear to touch by a rounding step, never by more
    tick = 0.002
    serial = rounds()
    concurrent = rounds(memory_budget_bytes=1 << 32)
    for rows, _ in serial + concurrent:
        assert rows == serial[0][0], "results diverged"
    # the device lock: no two executions ever overlap
    assert all(ov <= tick for _, ov in serial), [
        ov for _, ov in serial]
    # footprint admission: the lock is gone — queries that fit the
    # budget execute at the same time
    assert any(ov > tick for _, ov in concurrent), (
        f"queries never overlapped: {[ov for _, ov in concurrent]}")


def test_memory_arbiter_serializes_oversized():
    """A query whose estimate exceeds the budget runs only when alone
    (progress guarantee), so results stay correct under a tiny
    budget."""
    conn = TpchConnector(0.01)
    srv = PrestoTpuServer(
        {"tpch": conn}, port=0, page_rows=1 << 13,
        memory_budget_bytes=1 << 16,  # far below any query's estimate
    )
    srv.start()
    try:
        c = StatementClient(server=f"http://127.0.0.1:{srv.port}")
        rows = c.execute(
            "select count(*) from orders, lineitem "
            "where o_orderkey = l_orderkey"
        ).rows
        assert rows[0][0] > 0
    finally:
        srv.stop()
