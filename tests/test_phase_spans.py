"""One span tree per served statement (ISSUE 25): the coordinator owns
the trace from submission, and the phases queue, parse, plan, execute
and encode tile ``elapsedTimeMillis`` on the serial and the concurrent
server."""

import json
import threading
import time
import urllib.request

import pytest

from presto_tpu.client import StatementClient
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.obs.trace import PHASE_KINDS, QueryTrace
from presto_tpu.server.http_server import PrestoTpuServer

SQL = ("select l_returnflag, count(*), sum(l_quantity) from lineitem "
       "group by l_returnflag")
SCALAR = ("select count(*) from orders where o_totalprice > "
          "(select avg(o_totalprice) from orders)")


def _info(srv, query_id):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/query/{query_id}") as resp:
        return json.loads(resp.read())


def _assert_tiles(info):
    """The phases are in order, each begins where the last one ended,
    the first at submission, and together they are the elapsed time."""
    phases = info["phases"]
    kinds = [p["kind"] for p in phases]
    assert kinds[0] == "queue" and set(kinds) <= set(PHASE_KINDS), kinds
    assert [k for k in PHASE_KINDS if k in kinds] == sorted(
        set(kinds), key=PHASE_KINDS.index)
    assert phases[0]["startUs"] == 0
    for prev, nxt in zip(phases, phases[1:]):
        assert nxt["startUs"] == prev["endUs"], (prev, nxt)
    total_ms = sum(p["endUs"] - p["startUs"] for p in phases) / 1e3
    assert abs(total_ms - info["elapsedTimeMillis"]) <= 1.0, (
        total_ms, info["elapsedTimeMillis"], phases)
    assert isinstance(info["anchorMonotonicS"], float)
    return {k: sum(p["endUs"] - p["startUs"] for p in phases
                   if p["kind"] == k) for k in kinds}


@pytest.fixture(scope="module", params=["serial", "concurrent"])
def server(request):
    kw = ({"memory_budget_bytes": 1 << 32}
          if request.param == "concurrent" else {})
    srv = PrestoTpuServer({"tpch": TpchConnector(0.01)}, port=0,
                          page_rows=1 << 13, **kw)
    srv.start()
    yield srv
    srv.stop()


def _client(srv):
    c = StatementClient(server=f"http://127.0.0.1:{srv.port}")
    c.session_properties["result_cache_enabled"] = "false"
    return c


def test_phases_tile_the_elapsed_time(server):
    c = _client(server)
    c.execute(SQL)  # compiled
    for sql in (SQL, SCALAR):
        res = c.execute(sql)
        assert res.error is None
        info = _info(server, res.query_id)
        by_kind = _assert_tiles(info)
        assert set(by_kind) == set(PHASE_KINDS), by_kind
        # the tree of stages keeps its own origin: the instant the
        # runner begins to plan
        (stage,) = info["stages"]
        plan_start = next(p["startUs"] for p in info["phases"]
                          if p["kind"] == "plan")
        first_exec = min(t["startMs"] for t in stage["tasks"])
        assert stage["startMs"] == first_exec >= 0
        exec_start = next(p["startUs"] for p in info["phases"]
                          if p["kind"] == "execute")
        if sql is SQL:
            assert abs(stage["startMs"]
                       - (exec_start - plan_start) / 1e3) <= 1.0
        else:
            # the scalar subquery ran on the executor while planning:
            # its execute span nests under plan and is no phase
            assert len(stage["tasks"]) == 2
            assert stage["startMs"] * 1e3 < exec_start - plan_start


def test_eight_statements_at_once_all_tile(server):
    c0 = _client(server)
    c0.execute(SQL)
    results = [None] * 8

    def one(i):
        results[i] = _client(server).execute(SQL if i % 2 else SCALAR)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for res in results:
        assert res.error is None
        _assert_tiles(_info(server, res.query_id))


def test_control_statement_and_failure_tile_too(server):
    c = _client(server)
    ok = c.execute("set session page_rows = 8192")
    assert ok.error is None
    by_kind = _assert_tiles(_info(server, ok.query_id))
    assert "execute" not in by_kind and "encode" in by_kind
    bad = c.execute("select no_such_column from orders")
    assert bad.error is not None
    _assert_tiles(_info(server, bad.query_id))


def test_wait_behind_the_execution_lock_is_queue_time():
    """Serial path: a statement held behind the execution lock shows
    the wait in ``queue`` (with the gate that held it), not in
    ``execute``."""
    srv = PrestoTpuServer({"tpch": TpchConnector(0.01)}, port=0,
                          page_rows=1 << 13)
    srv.start()
    try:
        c = _client(srv)
        c.execute(SQL)
        free = _assert_tiles(_info(srv, c.execute(SQL).query_id))
        held_s = 0.4
        res = {}
        with srv.manager._exec_lock:
            t = threading.Thread(
                target=lambda: res.setdefault("r", c.execute(SQL)))
            t.start()
            time.sleep(held_s)
        t.join()
        info = _info(srv, res["r"].query_id)
        held = _assert_tiles(info)
        queue = next(p for p in info["phases"] if p["kind"] == "queue")
        assert held["queue"] >= (held_s - 0.05) * 1e6
        assert queue["attrs"]["gate"] == "execution_lock"
        assert queue["attrs"]["execution_lock_us"] >= (held_s - 0.05) * 1e6
        assert held["execute"] < free["execute"] + held_s * 1e6 / 2
        # the old reading still counts from where the runner begins
        assert info["stages"][0]["endMs"] < held_s * 1e3 + \
            free["execute"] / 1e3
    finally:
        srv.stop()


def test_phase_opens_where_the_last_one_ended():
    tr = QueryTrace("q", anchor_mono=time.monotonic() - 1.0)
    queue = tr.phase("queue", at=0.0)
    assert queue.t0 == 0.0
    parse = tr.phase("parse")
    assert queue.t1 == parse.t0 >= 1.0
    tr.end(parse)
    time.sleep(0.01)
    plan = tr.phase("plan")          # a closed phase hands over its end
    assert plan.t0 == parse.t1
    nested = tr.begin("execute", "Scalar", parent=plan)
    tr.end(nested)
    run = tr.phase("execute", "Output")
    assert plan.t1 == run.t0
    tr.finish(at_mono=time.monotonic())
    assert run.t1 == tr.root.t1
    kinds = [p["kind"] for p in tr.phases()]
    assert kinds == ["queue", "parse", "plan", "execute"]
    # a trace nobody gave a phase opens its first one now, not at 0
    late = QueryTrace("q2", anchor_mono=time.monotonic() - 1.0)
    assert late.phase("execute", "Output").t0 >= 1.0


@pytest.mark.parametrize("cell_name", [
    "scan_sf10_solo", "join_sf1_solo", "mixed_sf1_sf10_c8"])
def test_every_statement_of_a_rehearsal_tiles(cell_name, tmp_path):
    """The benchmark's own traffic at SF0.01 against the coordinator
    its configuration describes: every statement's phases tile its
    elapsed time, and the counters a traced run reads are there."""
    from benchmarks.harness import manifest, serve, traffic

    cell = manifest.load_cell(cell_name)
    etc = str(tmp_path / "etc")
    serve.write_etc(etc, cell.config, rehearse=True)
    served = serve.Served(etc, cell.chips)
    try:
        plans = traffic.plan_clients(cell, 3000000007)
        for st in traffic.statements_used(plans):
            assert served.client(st.catalog).execute(st.sql).error is None
        _t0, samples = traffic.run_window(
            served, plans, 1.5, cell.traffic["stop"], scrape=True)
        assert samples and all(s.error is None for s in samples)
        for s in samples:
            by_kind = _assert_tiles(served.query_info(s.query_id))
            assert set(by_kind) == set(PHASE_KINDS)
            assert s.metrics_after["device_launches"] >= \
                s.metrics_after["program_launches"]
        last = samples[-1].metrics_after
        assert last["device_launches"] >= 1
        assert last["dispatch_wall_us"] > 0 and last["device_wait_us"] > 0
        assert last["program_trace_wall_s"] > 0
    finally:
        served.stop()
