"""Ring-3.5: multi-process (DCN) distributed execution on localhost.

Reference: presto-tests tests/DistributedQueryRunner.java boots real
servers with real HTTP shuffle in one JVM; our DCN analog goes one
step further and uses real OS processes (separate JAX runtimes), per
SURVEY §6.3/§6.8 — the host page proxy is also where faults inject
(delay/drop/kill), since compiled ICI collectives cannot be faulted.

Process workers are expensive to boot (fresh XLA compiles), so most
tests share two in-process WorkerServers (threads — same HTTP protocol,
same serde boundary) and two tests pay for real subprocesses: the
end-to-end parity run and the kill-a-worker failure path.
"""

import collections
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.dist.dcn import DcnQueryFailed, DcnRunner
from presto_tpu.runner import LocalRunner
from presto_tpu.server.worker import WorkerServer
from tests.tpch_queries import QUERIES

SF = 0.01
PAGE_ROWS = 1 << 13


@pytest.fixture(scope="module")
def single():
    return LocalRunner({"tpch": TpchConnector(SF)}, page_rows=PAGE_ROWS)


@pytest.fixture(scope="module")
def workers():
    w1 = WorkerServer({"tpch": TpchConnector(SF)}, node_id="w1",
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    w2 = WorkerServer({"tpch": TpchConnector(SF)}, node_id="w2",
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    uris = [f"http://127.0.0.1:{w1.start()}",
            f"http://127.0.0.1:{w2.start()}"]
    yield uris
    w1.stop()
    w2.stop()


@pytest.fixture(scope="module")
def coord(workers):
    c = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                  default_catalog="tpch", page_rows=PAGE_ROWS)
    yield c
    c.close()


def rows_equal(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b)
    )


def _post_fault(uri, **cfg):
    """Set a worker's runtime fault overlay via the HTTP surface the
    chaos harness uses (no kwargs = restore env-ruled mode)."""
    req = urllib.request.Request(
        f"{uri}/v1/fault", data=json.dumps(cfg).encode(),
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=5).close()


@pytest.mark.parametrize("qid", [1, 6, 3])
def test_dcn_matches_single(qid, single, coord):
    want = single.execute(QUERIES[qid]).rows
    got = coord.execute(QUERIES[qid])
    assert rows_equal(want, got), f"Q{qid} diverged"


def test_dcn_approx_distinct(single, coord):
    q = ("select o_orderpriority, approx_distinct(o_custkey), "
         "sum(o_totalprice) from orders group by o_orderpriority")
    assert rows_equal(single.execute(q).rows, coord.execute(q))


def test_heartbeat_sees_workers(coord):
    coord.heartbeat.check_once()
    assert len(coord.heartbeat.alive_nodes()) == 2


def test_fault_delay_and_drop_recovered(workers, single, monkeypatch):
    """Injected page-proxy faults (delay + periodic HTTP 500) must be
    absorbed by the token-acked retry protocol — same rows, no error."""
    monkeypatch.setenv("FAULT_DELAY_MS", "20")
    monkeypatch.setenv("FAULT_DROP_EVERY", "3")
    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    q = ("select l_returnflag, count(*), sum(l_quantity) "
         "from lineitem group by l_returnflag")
    want = single.execute(q).rows
    got = coord.execute(q)
    assert rows_equal(want, got)


def _boot_subprocess_worker(port_env, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("FAULT_DELAY_MS", "FAULT_DROP_EVERY",
              "FAULT_KILL_AFTER_FETCHES", "FAULT_SUBMIT_DROP_EVERY"):
        env.pop(k, None)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "presto_tpu.server.worker",
         "--port", "0", "--suite", "tpch", "--scale", str(SF),
         "--page-rows", str(PAGE_ROWS)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        text=True,
    )
    line = proc.stdout.readline()
    info = json.loads(line)
    return proc, f"http://127.0.0.1:{info['port']}"


@pytest.mark.slow
def test_two_real_processes_and_kill(single):
    """The ring-3.5 gate, upgraded for fault-tolerant
    execution: Q3 across 2 real OS processes matches single-process;
    a worker that hard-exits MID-QUERY (FAULT_KILL_AFTER_FETCHES) is
    recovered by task re-dispatch — the query COMPLETES with
    single-process-identical rows and task_retries >= 1 — while
    task_retry_attempts=0 pins the old fail-query-cleanly contract."""
    p1, u1 = _boot_subprocess_worker(0)
    # w2 hard-exits after serving one results fetch: worker death in
    # the middle of the fetch loop, not before the query
    p2, u2 = _boot_subprocess_worker(
        0, extra_env={"FAULT_KILL_AFTER_FETCHES": "1"})
    coord = coord0 = None
    try:
        coord = DcnRunner({"tpch": TpchConnector(SF)}, [u1, u2],
                          default_catalog="tpch", page_rows=PAGE_ROWS,
                          fetch_retries=2,
                          session_props={"retry_backoff_ms": 20})
        want = single.execute(QUERIES[3]).rows
        got = coord.execute(QUERIES[3])
        assert rows_equal(want, got), \
            "Q3 with a mid-query worker kill diverged"
        ex = coord.runner.executor
        assert ex.task_retries >= 1, "recovery did not re-dispatch"
        assert ex.workers_excluded >= 1
        p2.wait(timeout=10)  # the fault hook really killed the process
        assert p2.poll() is not None

        # the killed worker stays excluded; a second query sails
        # through on the survivor alone
        got2 = coord.execute(QUERIES[3])
        assert rows_equal(want, got2)

        # pinned mode (task_retry_attempts=0): the classic contract —
        # a dead worker fails the QUERY cleanly, no task recovery
        coord0 = DcnRunner({"tpch": TpchConnector(SF)}, [u1, u2],
                           default_catalog="tpch", page_rows=PAGE_ROWS,
                           fetch_retries=2,
                           session_props={"task_retry_attempts": 0})
        with pytest.raises(DcnQueryFailed):
            coord0.execute(QUERIES[3])
    finally:
        for c in (coord, coord0):
            if c is not None:
                c.close()
        for p in (p1, p2):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)


def test_submit_drop_recovers_to_other_worker(workers, single):
    """FAULT_SUBMIT_DROP_EVERY=1 makes one worker 500 every task
    submit; the coordinator's submit retry re-dispatches that split
    share to the other ALIVE worker and the query completes."""
    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      session_props={"retry_backoff_ms": 10})
    _post_fault(workers[1], FAULT_SUBMIT_DROP_EVERY=1)
    try:
        q = ("select l_returnflag, count(*), sum(l_quantity) "
             "from lineitem group by l_returnflag")
        want = single.execute(q).rows
        got = coord.execute(q)
        assert rows_equal(want, got)
        assert coord.runner.executor.task_retries >= 1
        assert coord.runner.executor.workers_excluded >= 1
    finally:
        _post_fault(workers[1])
        coord.close()


def test_heartbeat_failed_node_never_picked(workers, single):
    """A node the heartbeat marks FAILED is excluded from the submit
    pool up front — the query completes on the survivors with ZERO
    recovery actions (no retries, no exclusions: it was never
    picked)."""
    dead_uri = "http://127.0.0.1:1"  # nothing listens there
    coord = DcnRunner({"tpch": TpchConnector(SF)},
                      list(workers) + [dead_uri],
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    try:
        for _ in range(3):  # fail_after=3 consecutive misses
            coord.heartbeat.check_once()
        assert not coord.heartbeat.is_alive(dead_uri)
        q = ("select o_orderpriority, count(*) from orders "
             "group by o_orderpriority")
        want = single.execute(q).rows
        got = coord.execute(q)
        assert rows_equal(want, got)
        assert coord.last_pool == list(workers)  # FAILED never picked
        assert coord.runner.executor.task_retries == 0
        assert coord.runner.executor.workers_excluded == 0
    finally:
        coord.close()


def test_dcn_query_deadline_expires(workers):
    """query_max_run_time is a real deadline: with a per-fetch injected
    delay longer than the deadline the query surfaces
    QueryDeadlineExceeded instead of hanging (the delay makes expiry
    deterministic even when the compile cache is warm)."""
    from presto_tpu.exec.executor import QueryDeadlineExceeded

    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      session_props={"query_max_run_time": 400})
    _post_fault(workers[0], FAULT_DELAY_MS=600)
    try:
        with pytest.raises(QueryDeadlineExceeded):
            coord.execute(QUERIES[1])
    finally:
        _post_fault(workers[0])
        coord.close()


def test_runtime_fault_config_overlays_env(monkeypatch):
    """The /v1/fault config is an OVERLAY: posted keys win (explicit 0
    disables an env-seeded fault), absent keys fall back to the
    environment, `{}` restores env-ruled mode — never one-way."""
    from presto_tpu.server import worker as W

    ws = W.WorkerServer.__new__(W.WorkerServer)
    ws.fault_config = {}
    monkeypatch.setenv("FAULT_DELAY_MS", "500")
    assert ws._fault("FAULT_DELAY_MS") == 500  # env rules with no post
    ws.fault_config = {"FAULT_DELAY_MS": 0}  # explicit 0 disables env
    assert ws._fault("FAULT_DELAY_MS") == 0
    ws.fault_config = {"FAULT_DELAY_MS": 7}
    assert ws._fault("FAULT_DELAY_MS") == 7
    ws.fault_config = {}  # {} = back to env-ruled mode
    assert ws._fault("FAULT_DELAY_MS") == 500


def test_nondistributable_runs_locally_with_all_workers_down(single):
    """An empty ALIVE pool only fails queries that NEED workers: a bare
    scan (nothing distributable) still falls back to local execution —
    the pre-FTE contract, kept."""
    dead = ["http://127.0.0.1:1", "http://127.0.0.1:2"]
    coord = DcnRunner({"tpch": TpchConnector(SF)}, dead,
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    try:
        for _ in range(3):
            coord.heartbeat.check_once()
        q = "select r_name from region"
        got = coord.execute(q)
        assert rows_equal(got, single.execute(q).rows)
        assert coord.last_distribution == "local"
        # but a distributable aggregation with no workers fails loudly
        with pytest.raises(DcnQueryFailed, match="no ALIVE workers"):
            coord.execute("select count(*) from region")
    finally:
        coord.close()


def test_task_retry_event_dispatched(workers, single):
    """TaskRetryEvent reaches registered EventListeners on every
    re-dispatch (the events.py half of the observability contract)."""
    from presto_tpu import events as E

    seen = []

    class Listener(E.EventListener):
        def task_retried(self, event):
            seen.append(event)

    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      session_props={"retry_backoff_ms": 10},
                      listeners=[Listener()])
    _post_fault(workers[0], FAULT_SUBMIT_DROP_EVERY=1)
    try:
        q = "select count(*), sum(l_quantity) from lineitem"
        got = coord.execute(q)
        assert rows_equal(got, single.execute(q).rows)
        assert seen, "no TaskRetryEvent dispatched"
        ev = seen[0]
        assert ev.from_uri == workers[0]
        assert ev.to_uri in workers
        assert ev.attempt == 1
    finally:
        _post_fault(workers[0])
        coord.close()


def test_bare_scan_query_falls_back_local(coord, single):
    # a bare scan has no useful union cut (generation is cheaper than
    # the wire) — runs locally
    q = "select r_regionkey, r_name from region order by r_regionkey"
    assert coord.execute(q) == single.execute(q).rows
    assert coord.last_distribution == "local"


def test_union_cut_multijoin_distributes(coord, single):
    """Done-criterion: a multi-join query with NO
    aggregation distributes across 2 workers (union cut: workers run
    the row-local join subtree over their split share, shipped as a
    serialized fragment; the coordinator unions the pages)."""
    q = ("select c_name, o_orderkey, l_quantity from customer "
         "join orders on c_custkey = o_custkey "
         "join lineitem on l_orderkey = o_orderkey "
         "where l_quantity > 45")
    want = single.execute(q).rows
    got = coord.execute(q)
    assert coord.last_distribution.startswith("union")
    assert rows_equal(got, want)


def test_union_cut_under_topn(coord, single):
    # coordinator-side TopN over the unioned worker pages
    q = ("select o_orderkey, l_extendedprice from orders "
         "join lineitem on l_orderkey = o_orderkey "
         "order by l_extendedprice desc, o_orderkey limit 7")
    want = single.execute(q).rows
    got = coord.execute(q)
    assert coord.last_distribution.startswith("union")
    assert got == want


def test_union_cut_hash_partitioned(workers, single):
    # both big sides of the join hash-co-partition (union-hash):
    # worker build state is 1/N even with no aggregation in the plan
    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      partition_threshold=10_000)
    q = ("select o_orderpriority, l_shipmode from orders "
         "join lineitem on l_orderkey = o_orderkey "
         "where l_quantity > 49")
    want = single.execute(q).rows
    got = coord.execute(q)
    assert coord.last_distribution == "union-hash"
    assert rows_equal(got, want)


def test_shipped_fragment_is_executed_verbatim(workers, single):
    """Plan SHIPPING (not replay): POST a hand-edited fragment that no
    SQL replay could produce and check the worker executes exactly it."""
    import urllib.request

    from presto_tpu.dist import plan_serde, serde
    from presto_tpu.exec import plan as P
    from presto_tpu.expr import ir as E

    plan = single.plan("select o_orderkey from orders")
    # wrap the scan subtree in an extra filter the SQL never had
    scan = plan
    while not isinstance(scan, P.TableScan):
        scan = scan.children()[0]
    fragment = P.Filter(
        source=P.Project(source=scan, exprs=(
            E.input_ref(0, single.executor.output_types(scan)[0]),)),
        predicate=E.call("lt", E.input_ref(
            0, single.executor.output_types(scan)[0]),
            E.const(100, single.executor.output_types(scan)[0])),
    )
    payload = {
        "taskId": "ship-test.0",
        "fragment": plan_serde.dumps(fragment),
        "splitTable": "orders",
        "splitIndex": 0,
        "splitCount": 1,
        "session": {},
    }
    req = urllib.request.Request(
        f"{workers[0]}/v1/task", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=30).close()
    rows = []
    token = 0
    deadline = time.time() + 120
    while time.time() < deadline:
        r = urllib.request.urlopen(
            f"{workers[0]}/v1/task/ship-test.0/results/{token}",
            timeout=30)
        if r.status == 204:
            if r.headers.get("X-Done") == "1":
                break
            continue
        body = r.read()
        token = int(r.headers["X-Next-Token"])
        rows.extend(serde.deserialize_page(body).to_pylist())
    want = [r for r in single.execute(
        "select o_orderkey from orders").rows if r[0] < 100]
    assert rows_equal(rows, want)


@pytest.mark.parametrize("q", [
    # DISTINCT masks: MarkDistinct below the cut would double-count
    # values spanning workers — must fall back local, stay correct
    "select count(distinct o_custkey) from orders",
    # outer join below the cut: null-extension is not split-safe
    "select count(*) from customer left join orders "
    "on c_custkey = o_custkey",
    # NOT IN (anti join) below the cut
    "select count(*) from customer where c_custkey not in "
    "(select o_custkey from orders)",
])
def test_unsafe_shapes_fall_back_local(coord, single, q):
    assert rows_equal(coord.execute(q), single.execute(q).rows)


def test_self_join_of_fact_table_falls_back(coord, single):
    q = ("select count(*) from orders o1, orders o2 "
         "where o1.o_orderkey = o2.o_orderkey")
    assert rows_equal(coord.execute(q), single.execute(q).rows)


def test_session_props_reach_both_halves(workers, single):
    coord = DcnRunner(
        {"tpch": TpchConnector(SF)}, workers,
        default_catalog="tpch", page_rows=PAGE_ROWS,
        session_props={"spill_threshold_bytes": 1 << 15},
    )
    q = ("select o_custkey, count(*) from orders group by o_custkey "
         "order by 2 desc, 1 limit 5")
    got = coord.execute(q)
    assert rows_equal(got, single.execute(q).rows)
    # the coordinator-side final stage honored the session (spill knob
    # reached the shared executor through apply_session)
    assert coord.runner.executor.spill_bytes == 1 << 15


def test_partitioned_join_across_workers(workers, single):
    """A PARTITIONED join (both sides hash-split on the
    join key — the DCN repartition exchange) across 2 workers matches
    single-process. partition_threshold=1 forces every scanned table
    into the co-partitioned set at this tiny SF."""
    # threshold between customer (1.5k) and orders (15k) at SF0.01:
    # orders+lineitem co-partition on orderkey, customer replicates.
    # (threshold=1 would make customer "big" too — orders would then
    # need BOTH o_custkey and o_orderkey partition keys, which the
    # analyzer correctly refuses.)
    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      partition_threshold=10_000)
    want = single.execute(QUERIES[3]).rows
    got = coord.execute(QUERIES[3])
    assert coord.last_distribution == "hash"
    assert rows_equal(want, got), "partitioned Q3 diverged"


def test_partitioned_join_covers_null_keys(workers, single):
    # rows with NULL partition keys land on exactly one worker; an
    # inner join drops them either way but the partial agg below the
    # cut must not double-count them
    coord = DcnRunner({"tpch": TpchConnector(SF)}, workers,
                      default_catalog="tpch", page_rows=PAGE_ROWS,
                      partition_threshold=10_000)
    q = ("select o_orderpriority, count(*), sum(l_quantity) "
         "from orders, lineitem where o_orderkey = l_orderkey "
         "group by o_orderpriority")
    want = single.execute(q).rows
    got = coord.execute(q)
    assert coord.last_distribution == "hash"
    assert rows_equal(want, got)


def test_hash_fanout_shape_analysis(single):
    from presto_tpu.server.worker import find_partial_cut, hash_fanout_plan

    plan = single.plan(QUERIES[3])
    cut = find_partial_cut(plan)
    # threshold=1: customer+orders+lineitem all "big" — orders would
    # need both o_custkey and o_orderkey, so the analyzer must refuse
    assert hash_fanout_plan(cut, single.catalogs,
                            partition_threshold=1) is None
    # realistic threshold: orders+lineitem co-partition on orderkey
    parts = hash_fanout_plan(cut, single.catalogs,
                             partition_threshold=10_000)
    assert parts == {"tpch.orders": "o_orderkey",
                     "tpch.lineitem": "l_orderkey"}
