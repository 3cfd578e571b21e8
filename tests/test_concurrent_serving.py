"""ISSUE 11 satellite: the concurrent serving path as a gated
invariant — N protocol clients x the CONCURRENT QueryManager path
(memory arbiter on, per-query runners) x the process-shared result
cache x the armed lock sanitizer, raced deliberately in tier-1.

This is ROADMAP item 1(d)'s "result cache on by default for the
server" prerequisite turned into a test: before the cache can default
on, concurrent clients hammering the shared store must produce
IDENTICAL rows per statement and ZERO sanitizer violations (no
lock-order inversion, no unlocked shared-attr write anywhere in the
engine while the race runs).

ISSUE 17 extends the suite to the multi-tenant dispatch plane:
cross-query launch batching (batched vs solo vs sqlite-oracle row
parity, queries_per_launch > 1 actually recorded), fair scheduling
(a short interactive query overtakes a queue of long scans by
completion ORDER — wall-clock assertions don't survive a 2-core CI
box), and per-group HBM shares (peak_device_bytes governed under the
group's resolved budget).
"""

import json
import threading
import time
import urllib.request

import pytest

from presto_tpu.obs import sanitizer as SAN

CLIENTS = 8
ROUNDS = 3

# small repeated deck (dashboard shape): after each statement's first
# execution the rest should collapse onto the shared result cache —
# which is exactly the cross-thread traffic being raced
STATEMENTS = (
    "select count(*), sum(n_nationkey) from nation",
    "select r_name, count(*) from region group by r_name "
    "order by r_name",
    "select n_regionkey, count(*), max(n_name) from nation "
    "group by n_regionkey order by n_regionkey",
)


@pytest.fixture(scope="module")
def server_url():
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.server.http_server import PrestoTpuServer

    # memory arbiter on => the CONCURRENT path: every query gets its
    # own runner/executor; the result-cache store, jit cache, views,
    # and histograms are the process-shared surfaces under race
    srv = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.01)},
        port=0, memory_budget_bytes=1 << 32,
    )
    port = srv.start()
    yield f"http://127.0.0.1:{port}"
    srv.stop()


def test_concurrent_clients_cache_on_zero_sanitizer_violations(
        server_url):
    if not SAN.is_armed():
        pytest.skip("sanitizer disarmed via PRESTO_TPU_LOCK_SANITIZER")
    from presto_tpu.client import StatementClient

    SAN.reset()
    results = [[] for _ in range(CLIENTS)]
    errors = []

    def client(idx: int) -> None:
        cl = StatementClient(server_url, user=f"race{idx}",
                             catalog="tpch")
        cl.session_properties["result_cache_enabled"] = "true"
        for _ in range(ROUNDS):
            for sql in STATEMENTS:
                try:
                    res = cl.execute(sql)
                except Exception as e:  # noqa: BLE001 - the assertion
                    errors.append(repr(e))  # below reports transport
                    continue  # failures with full context
                if res.error is not None:
                    errors.append(str(res.error))
                else:
                    results[idx].append(
                        (sql, tuple(map(tuple, res.rows))))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "client hung"
    assert not errors, errors[:5]

    # every client saw every statement every round...
    for idx in range(CLIENTS):
        assert len(results[idx]) == ROUNDS * len(STATEMENTS)
    # ...and all of them identical rows (a cache serving one client a
    # torn/stale page set would diverge here)
    by_sql = {}
    for idx in range(CLIENTS):
        for sql, rows in results[idx]:
            by_sql.setdefault(sql, set()).add(rows)
    for sql, variants in by_sql.items():
        assert len(variants) == 1, \
            f"divergent rows across clients for {sql!r}"

    # the cache actually engaged across the race (the point of the
    # exercise: hits ARE the contended path)
    from presto_tpu.cache import shared_cache_if_exists

    rc = shared_cache_if_exists()
    assert rc is not None and rc.hits > 0

    # and the armed sanitizer observed ZERO violations anywhere in
    # the engine while 8 threads raced it
    assert SAN.violation_count() == 0, SAN.report()


def _race(server_url, batching: str, rounds: int = ROUNDS):
    """Run the CLIENTS x STATEMENTS deck with the result cache OFF
    (every statement executes — replays would launch nothing and
    flatter the batching numbers) and the cross_query_batching knob
    pinned. Returns {sql: {rows-variant, ...}} across every client
    and round, plus transport errors."""
    from presto_tpu.client import StatementClient

    results = [[] for _ in range(CLIENTS)]
    errors = []

    def client(idx: int) -> None:
        cl = StatementClient(server_url, user=f"xq{idx}",
                             catalog="tpch")
        cl.session_properties["result_cache_enabled"] = "false"
        cl.session_properties["cross_query_batching"] = batching
        # a wide gather window makes 8-thread overlap near-certain on
        # a 2-core box; correctness must hold at ANY window
        cl.session_properties["cross_query_batch_wait_ms"] = "50"
        for _ in range(rounds):
            for sql in STATEMENTS:
                try:
                    res = cl.execute(sql)
                except Exception as e:  # noqa: BLE001 - reported below
                    errors.append(repr(e))
                    continue
                if res.error is not None:
                    errors.append(str(res.error))
                else:
                    results[idx].append(
                        (sql, tuple(map(tuple, res.rows))))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "client hung"
    by_sql = {}
    for idx in range(CLIENTS):
        for sql, rows in results[idx]:
            by_sql.setdefault(sql, set()).add(rows)
    return by_sql, errors


def _scrape(server_url: str, name: str) -> int:
    import re
    import urllib.request

    with urllib.request.urlopen(server_url + "/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    m = re.search(rf"^{re.escape(name)} (\d+)", text, re.M)
    return int(m.group(1)) if m else 0


def test_batched_vs_solo_row_parity_and_width(server_url):
    """ISSUE 17 acceptance: under 8 concurrent clients with the cache
    off, the batched path must return rows identical to the solo path
    AND to the sqlite oracle, while actually riding shared launches
    (queries_per_launch > 1) — and the armed sanitizer must stay
    silent through both passes."""
    if SAN.is_armed():
        SAN.reset()
    solo, errs_solo = _race(server_url, "false")
    batched, errs_b = _race(server_url, "true")
    assert not errs_solo, errs_solo[:5]
    assert not errs_b, errs_b[:5]

    # each pass internally consistent, and batched == solo per
    # statement (the in-program demux never leaks another query's
    # slot or a padded lane)
    for sql in STATEMENTS:
        assert len(solo[sql]) == 1, f"solo divergence for {sql!r}"
        assert len(batched[sql]) == 1, \
            f"batched divergence for {sql!r}"
        assert solo[sql] == batched[sql], \
            f"batched rows differ from solo for {sql!r}"

    # ...and both match the sqlite oracle over the same generated data
    from presto_tpu.connectors.tpch import TpchConnector
    from tests.oracle import load_sqlite, rows_match

    db = load_sqlite(TpchConnector(scale=0.01), ["nation", "region"])
    for sql in STATEMENTS:
        engine_rows = [tuple(r) for r in next(iter(batched[sql]))]
        oracle_rows = [tuple(r) for r in db.execute(sql).fetchall()]
        rows_match(engine_rows, oracle_rows)

    # the batched pass actually shared launches: the process-wide
    # gauge (max across completed queries) recorded a width > 1
    width = _scrape(server_url, "presto_tpu_queries_per_launch")
    assert width > 1, (
        f"queries_per_launch={width}: no launch was ever shared "
        f"across queries under an 8-client race")
    assert _scrape(
        server_url, "presto_tpu_cross_query_batches_total") > 0

    if SAN.is_armed():
        assert SAN.violation_count() == 0, SAN.report()


def test_priority_scheduling_interactive_overtakes_scans():
    """Fair scheduling (ISSUE 17), asserted by completion ORDER: with
    one global concurrency slot held by a long scan and three more
    long scans queued ahead of it, a high-priority interactive query
    must finish next (position 1), not last — FIFO would starve it
    behind every scan. Aging is the converse guarantee (the scans'
    effective priority grows while queued), so the scans must all
    still complete."""
    from presto_tpu.client import StatementClient
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.server.http_server import PrestoTpuServer
    from presto_tpu.server.resource_groups import (
        ResourceGroupManager,
        ResourceGroupSpec,
    )

    if SAN.is_armed():
        SAN.reset()
    rg = ResourceGroupManager([ResourceGroupSpec(
        "global", ".*", hard_concurrency=1, max_queued=64,
        sub_groups=(
            ResourceGroupSpec("inter", "inter.*",
                              hard_concurrency=1, max_queued=64,
                              priority=100),
            ResourceGroupSpec("batch", "batch.*",
                              hard_concurrency=1, max_queued=64),
        ))])
    srv = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.003)},
        port=0, memory_budget_bytes=1 << 32, resource_groups=rg,
    )
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    scan_sql = ("select count(*) from lineitem l1, lineitem l2 "
                "where l1.l_orderkey = l2.l_orderkey")
    quick_sql = "select count(*) from nation"
    try:
        # prewarm both programs off the raced path (shared jit cache)
        for user, sql in (("batchwarm", scan_sql),
                          ("interwarm", quick_sql)):
            c = StatementClient(base, user=user, catalog="tpch")
            c.session_properties["result_cache_enabled"] = "false"
            r = c.execute(sql)
            assert r.error is None, r.error

        order = []
        olock = threading.Lock()

        def run(label: str, user: str, sql: str):
            cl = StatementClient(base, user=user, catalog="tpch")
            cl.session_properties["result_cache_enabled"] = "false"
            res = cl.execute(sql)
            with olock:
                order.append((label, res.error))

        def batch_group():
            with urllib.request.urlopen(
                    base + "/v1/resourceGroup") as resp:
                groups = {g["name"]: g for g in json.loads(resp.read())}
            return groups["global.batch"]

        threads = [
            threading.Thread(
                target=run, args=(f"scan{i}", f"batch{i}", scan_sql),
                daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        # submit the interactive query on the OBSERVED queue state —
        # one scan holds the slot and at least two wait behind it —
        # not after a fixed sleep that a loaded box stretches or
        # shrinks past the scans
        deadline = time.monotonic() + 120
        while True:
            g = batch_group()
            if g["running"] >= 1 and g["queued"] >= 2:
                break
            assert time.monotonic() < deadline, (
                f"scans never queued up: {g}, finished {order}")
            time.sleep(0.005)
        with olock:
            done_before = len(order)
        inter = threading.Thread(
            target=run, args=("inter", "inter0", quick_sql), daemon=True)
        inter.start()
        threads.append(inter)
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "query hung"
        labels = [lab for lab, _ in order]
        errors = [(lab, e) for lab, e in order if e is not None]
        assert not errors, errors
        assert len(labels) == 5
        pos = labels.index("inter")
        # the scan holding the slot when the interactive query arrived
        # (and the one that may already have finished: with two still
        # queued, done_before <= 1) may precede it; everything QUEUED
        # at that moment must yield — never later than position 2
        assert pos <= done_before + 1, (
            f"interactive query finished at position {pos} of "
            f"{labels} ({done_before} scans were done when it was "
            "submitted): starved behind queued scans")
    finally:
        srv.stop()
    if SAN.is_armed():
        assert SAN.violation_count() == 0, SAN.report()


def test_group_memory_share_governs_peak():
    """Per-group HBM shares (ISSUE 17): a query admitted through a
    group with a tiny memory_share runs with its device budget seeded
    from exec/membudget.group_share_bytes — EXPLAIN ANALYZE's
    peak_device_bytes must come in under that resolved share (the
    governor chunks instead of colliding into the group's slice)."""
    import re

    from presto_tpu.client import StatementClient
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import membudget as MB
    from presto_tpu.server.http_server import PrestoTpuServer
    from presto_tpu.server.resource_groups import (
        ResourceGroupManager,
        ResourceGroupSpec,
    )

    if SAN.is_armed():
        SAN.reset()
    share = 2.0 ** -12
    budget = MB.group_share_bytes(share)
    assert budget == 1 << 24  # the floor engaged: 16 MiB

    rg = ResourceGroupManager([ResourceGroupSpec(
        "global", ".*", hard_concurrency=4, max_queued=64,
        sub_groups=(
            ResourceGroupSpec("small", "small.*",
                              hard_concurrency=2, max_queued=64,
                              memory_share=share),
            ResourceGroupSpec("rest", ".*",
                              hard_concurrency=2, max_queued=64),
        ))])
    srv = PrestoTpuServer(
        {"tpch": TpchConnector(scale=0.01)},
        port=0, memory_budget_bytes=1 << 32, resource_groups=rg,
    )
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        cl = StatementClient(base, user="small0", catalog="tpch")
        cl.session_properties["result_cache_enabled"] = "false"
        res = cl.execute(
            "explain analyze select l_returnflag, count(*), "
            "sum(l_extendedprice) from lineitem "
            "group by l_returnflag order by l_returnflag")
        assert res.error is None, res.error
        text = "\n".join(str(r[0]) for r in res.rows)
        m = re.search(r"peak_device_bytes=(\d+)", text)
        assert m is not None, f"no peak_device_bytes in:\n{text}"
        peak = int(m.group(1))
        assert 0 < peak <= budget, (
            f"peak_device_bytes={peak} exceeds the group's resolved "
            f"share {budget}")
    finally:
        srv.stop()
    if SAN.is_armed():
        assert SAN.violation_count() == 0, SAN.report()
