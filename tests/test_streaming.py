"""ISSUE 14: the streaming subsystem — append-log connector
(connectors/stream.py), incremental view maintenance
(streaming/ivm.py), monotone offset tokens in the cache plane, and
tailing /v1/statement cursors.

Covers the subsystem contract by contract:
  - append-log semantics: offsets advance monotonically, delta scans
    emit only new pages, full scans compose with the ordinary engine;
  - THE acceptance pin: after an initial refresh over N rows,
    appending M << N rows and refreshing folds only the delta
    (delta_pages_folded >= 1, ivm_full_recomputes == 0, scanned-row
    accounting == M, not N) with rows identical to a cold full
    recompute AND the sqlite oracle (floats at the established
    9-sig-digit tolerance);
  - append -> refresh -> append -> refresh chains;
  - the loud full-recompute fallback (non-IVM-safe shapes,
    ivm_enabled=false) — counted, never silently wrong;
  - monotone offset tokens: a pinned-offset fragment entry still HITS
    after the log advances (the append path reclaims only live-head
    entries);
  - tailing cursors: exactly-the-delta rows per poll, the IVM path
    for registered view shapes, and a concurrent appender x 4 tailing
    clients at zero lock-sanitizer violations;
  - counter registration on every surface and an append writer
    beside an IVM reader.
"""

import collections
import json
import random
import threading
import urllib.request

import pytest

from presto_tpu import types as T
from presto_tpu.cache import ResultCache, shared_cache_if_exists
from presto_tpu.connectors.stream import (
    StreamConnector,
    StreamWindowConnector,
)
from presto_tpu.runner import LocalRunner
from presto_tpu.streaming import ivm as IVM

PAGE_ROWS = 1 << 11

VIEW_SQL = ("select k, count(*), sum(v), max(v) from events "
            "group by k order by k")


def _mkconn(n_rows: int, seed: int = 0, groups: int = 8):
    rng = random.Random(seed)
    conn = StreamConnector()
    conn.create_table(
        "events", ["k", "v"], [T.BIGINT, T.DOUBLE],
        [(rng.randrange(groups), rng.random() * 100.0)
         for _ in range(n_rows)],
    )
    return conn, rng


def _runner(conn):
    return LocalRunner({"stream": conn}, default_catalog="stream",
                       page_rows=PAGE_ROWS)


def _batch(rng, m: int, groups: int = 8):
    return [(rng.randrange(groups), rng.random() * 100.0)
            for _ in range(m)]


def _rows_close(a, b, tol=1e-9):
    assert len(a) == len(b), f"{len(a)} vs {len(b)} rows"
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                assert abs(float(va) - float(vb)) <= tol * max(
                    1.0, abs(float(vb))), (va, vb)
            else:
                assert va == vb, (va, vb)


@pytest.fixture(autouse=True)
def _clean_shared_state():
    """The shared result cache and IVM registry are process-shared by
    design; tests must not leak entries/views into each other."""
    rc = shared_cache_if_exists()
    if rc is not None:
        rc.clear()
    reg = IVM.shared_registry_if_exists()
    if reg is not None:
        for v in reg.views():
            reg.unregister(v.name)
    yield
    rc = shared_cache_if_exists()
    if rc is not None:
        rc.clear()
    reg = IVM.shared_registry_if_exists()
    if reg is not None:
        for v in reg.views():
            reg.unregister(v.name)


# ------------------------------------------------- append-log connector
def test_append_advances_offset_and_token():
    conn, rng = _mkconn(100)
    assert conn.offset("events") == 100
    assert conn.snapshot_version("events") == "off:100"
    new = conn.append("events", _batch(rng, 7))
    assert new == 107
    assert conn.snapshot_version("events") == "off:107"
    assert conn.appends_seen("events") >= 2  # create seed + append


def test_delta_scan_emits_only_new_rows():
    conn, rng = _mkconn(500)
    base = conn.offset("events")
    batch = _batch(rng, 23)
    conn.append("events", batch)
    pages = list(conn.scan_from("events", base))
    got = [r for p in pages for r in p.to_pylist()]
    assert len(got) == 23
    _rows_close(got, batch)
    # a delta scan from the head is empty
    assert list(conn.scan_from("events", conn.offset("events"))) == []


def test_full_scan_composes_with_engine_and_oracle():
    from tests.oracle import load_sqlite

    conn, _rng = _mkconn(1200)
    r = _runner(conn)
    got = r.execute(VIEW_SQL).rows
    db = load_sqlite(conn, ["events"])
    want = db.execute(
        "select k, count(*), sum(v), max(v) from events "
        "group by k order by k").fetchall()
    _rows_close(got, [tuple(w) for w in want])


def test_window_connector_pins_range():
    conn, rng = _mkconn(300)
    w = StreamWindowConnector(conn, "events", 0, 300)
    assert w.row_count("events") == 300
    assert w.snapshot_version("events") == "off:300@0"
    assert w.pinned_offset("events") == 300
    conn.append("events", _batch(rng, 50))
    # the pin holds while the log advances
    assert w.row_count("events") == 300
    assert w.snapshot_version("events") == "off:300@0"
    w.set_range(300, 350)
    rows = [r for p in w.pages("events") for r in p.to_pylist()]
    assert len(rows) == 50


def test_wait_for_offset_wakes_on_append():
    conn, rng = _mkconn(10)
    got = {}

    def waiter():
        got["off"] = conn.wait_for_offset("events", 10, 10.0)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    conn.append("events", _batch(rng, 3))
    t.join(timeout=10)
    assert not t.is_alive()
    assert got["off"] == 13
    # timeout path: no append, returns current offset
    assert conn.wait_for_offset("events", 13, 0.05) == 13


# ----------------------------------------------------- IVM: acceptance
def test_ivm_acceptance_pin():
    """THE acceptance contract: initial refresh over N rows, append
    M << N, refresh folds ONLY the delta — delta_pages_folded >= 1,
    ivm_full_recomputes == 0, scanned rows == M — and the rows equal
    a cold full recompute AND the sqlite oracle."""
    from tests.oracle import load_sqlite

    N, M = 4000, 64
    conn, rng = _mkconn(N)
    r = _runner(conn)
    sink = r.executor
    view = IVM.IvmRegistry().register(r, "dash", VIEW_SQL)
    assert view.ivm_safe, view.unsafe_reason

    _names, rows1, _types = IVM.refresh(
        view, session=r.session, sink=sink)
    assert sink.ivm_refreshes == 1
    assert sink.ivm_full_recomputes == 0
    assert view.last_delta_rows == N  # the initial fold covers the log

    conn.append("events", _batch(rng, M))
    folded_before = sink.delta_pages_folded
    _names, rows2, _types = IVM.refresh(
        view, session=r.session, sink=sink)
    assert sink.delta_pages_folded - folded_before >= 1
    assert sink.ivm_full_recomputes == 0
    assert sink.ivm_refreshes == 2
    # scanned-row accounting proportional to M, not N
    assert view.last_delta_rows == M

    cold = r.execute(VIEW_SQL).rows
    _rows_close(rows2, cold)
    db = load_sqlite(conn, ["events"])
    want = db.execute(
        "select k, count(*), sum(v), max(v) from events "
        "group by k order by k").fetchall()
    _rows_close(rows2, [tuple(w) for w in want])
    assert rows1 != rows2  # the delta really changed the aggregates


def test_ivm_chain_append_refresh_repeatedly():
    conn, rng = _mkconn(1500)
    r = _runner(conn)
    sink = r.executor
    view = IVM.IvmRegistry().register(r, "chain", VIEW_SQL)
    IVM.refresh(view, session=r.session, sink=sink)  # initial fold
    for i in range(4):
        conn.append("events", _batch(rng, 37 + i))
        _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
        cold = r.execute(VIEW_SQL).rows
        _rows_close(rows, cold)
        assert view.last_delta_rows == 37 + i
    assert sink.ivm_full_recomputes == 0
    assert sink.ivm_refreshes == 5


def test_refresh_without_new_data_serves_settled_result():
    conn, _rng = _mkconn(800)
    r = _runner(conn)
    view = IVM.IvmRegistry().register(r, "idle", VIEW_SQL)
    _n, rows1, _t = IVM.refresh(view, session=r.session,
                                sink=r.executor)
    folded = r.executor.delta_pages_folded
    _n, rows2, _t = IVM.refresh(view, session=r.session,
                                sink=r.executor)
    assert rows1 == rows2
    assert r.executor.delta_pages_folded == folded  # nothing folded


# ------------------------------------------- IVM: loud fallback paths
def test_non_ivm_safe_global_agg_falls_back_loudly():
    conn, rng = _mkconn(600)
    r = _runner(conn)
    sink = r.executor
    sql = "select count(*), sum(v) from events"
    view = IVM.IvmRegistry().register(r, "glob", sql)
    assert not view.ivm_safe
    assert "global aggregation" in view.unsafe_reason
    _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
    assert sink.ivm_full_recomputes == 1
    assert sink.ivm_refreshes == 0
    _rows_close(rows, r.execute(sql).rows)
    conn.append("events", _batch(rng, 10))
    _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
    assert sink.ivm_full_recomputes == 2
    _rows_close(rows, r.execute(sql).rows)


def test_non_ivm_safe_join_falls_back_loudly():
    conn, _rng = _mkconn(300)
    r = _runner(conn)
    sql = ("select a.k, count(*) from events a join events b "
           "on a.k = b.k group by a.k order by a.k")
    view = IVM.IvmRegistry().register(r, "joined", sql)
    assert not view.ivm_safe
    _n, rows, _t = IVM.refresh(view, session=r.session,
                               sink=r.executor)
    assert r.executor.ivm_full_recomputes == 1
    _rows_close(rows, r.execute(sql).rows)


def test_ivm_disabled_forces_full_recompute():
    conn, rng = _mkconn(700)
    r = _runner(conn)
    sink = r.executor
    view = IVM.IvmRegistry().register(r, "gated", VIEW_SQL)
    assert view.ivm_safe
    r.session.set("ivm_enabled", False)
    _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
    assert sink.ivm_full_recomputes == 1
    assert sink.ivm_refreshes == 0
    _rows_close(rows, r.execute(VIEW_SQL).rows)
    # re-enabling folds incrementally again (state re-folds from 0)
    r.session.set("ivm_enabled", True)
    conn.append("events", _batch(rng, 20))
    _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
    assert sink.ivm_refreshes == 1
    _rows_close(rows, r.execute(VIEW_SQL).rows)


def test_unsafe_reasons_are_specific():
    conn, _rng = _mkconn(50)
    r = _runner(conn)
    assert IVM.ivm_unsafe_reason(r.plan(VIEW_SQL), r.catalogs) is None
    reason = IVM.ivm_unsafe_reason(
        r.plan("select array_agg(v) from events group by k"),
        r.catalogs)
    assert "array_agg" in reason
    # non-stream tables never maintain incrementally
    from presto_tpu.connectors.tpch import TpchConnector

    r2 = LocalRunner({"tpch": TpchConnector(0.01)},
                     page_rows=PAGE_ROWS)
    reason = IVM.ivm_unsafe_reason(
        r2.plan("select l_linestatus, count(*) from lineitem "
                "group by l_linestatus"), r2.catalogs)
    assert "append-only" in reason


def test_view_shape_match_is_offset_independent():
    conn, rng = _mkconn(400)
    r = _runner(conn)
    reg = IVM.IvmRegistry()
    view = reg.register(r, "shape", VIEW_SQL)
    conn.append("events", _batch(rng, 900))  # moves counts/capacities
    assert reg.match(r.plan(VIEW_SQL)) is view
    assert reg.match(
        r.plan("select k, count(*) from events group by k")) is None


# ------------------------------------- monotone offset tokens (cache)
def test_pinned_offset_entry_hits_while_log_advances():
    """The satellite fix: a stream-scan fragment entry at offset N
    still HITS for a reader pinned at N after the log has advanced —
    the append path advances (reclaims live-head entries only)
    instead of discarding."""
    conn, _rng = _mkconn(1000)
    N = conn.offset("events")
    ex, window = IVM.windowed_executor(
        {"stream": conn}, "stream", "events", like=None)
    window.set_range(0, N)
    ex.result_cache = ResultCache()
    helper = _runner(conn)
    plan = helper.plan(VIEW_SQL)
    _n, rows1 = ex.execute(plan)
    assert ex.result_cache_misses >= 1
    key = next(iter(ex.result_cache._entries))
    assert ex.result_cache.entry_watermark(key) == N

    # the log advances: only live-head entries reclaim
    dropped = ex.result_cache.advance_tables({("stream", "events")})
    assert dropped == 0
    conn.append("events", [(1, 5.0)])
    _n, rows2 = ex.execute(plan)  # still pinned at N
    assert ex.result_cache_hits >= 1
    assert rows1 == rows2


def test_live_head_entry_reclaimed_on_insert_advance():
    conn, _rng = _mkconn(400)
    r = _runner(conn)
    r.session.set("result_cache_enabled", True)
    r.apply_session()
    rc = r.executor.result_cache
    r.execute(VIEW_SQL)  # live-head entries (no watermark)
    assert rc.entry_count >= 1
    keys = list(rc._entries)
    assert all(rc.entry_watermark(k) is None for k in keys)
    appends_before = r.executor.stream_appends_seen
    r.execute("insert into events select 3, 7.5")
    # the advance path reclaimed the unreachable live-head entries
    # and counted the observed append batch
    assert rc.entry_count == 0
    assert r.executor.stream_appends_seen == appends_before + 1
    # fresh read at the new offset recomputes correctly
    got = r.execute(VIEW_SQL).rows
    _rows_close(got, r.execute(VIEW_SQL).rows)


def test_view_cache_entry_advances_in_place():
    conn, rng = _mkconn(500)
    r = _runner(conn)
    r.session.set("result_cache_enabled", True)
    r.apply_session()
    rc = r.executor.result_cache
    view = IVM.IvmRegistry().register(r, "cached", VIEW_SQL)
    IVM.refresh(view, session=r.session, sink=r.executor)
    assert rc.entry_watermark(view.cache_key) == 500
    inv_before = rc.invalidations
    conn.append("events", _batch(rng, 25))
    r._invalidate_caches("stream", "events", append=True)
    # the watermarked view entry SURVIVED the append
    assert rc.entry_watermark(view.cache_key) == 500
    IVM.refresh(view, session=r.session, sink=r.executor)
    # ...and the refresh ADVANCED it in place, not via invalidation
    assert rc.entry_watermark(view.cache_key) == 525
    assert rc.invalidations == inv_before


# --------------------------------------------------- tailing cursors
def _tail_req(url, data=None, method="GET", tail=True, poll_ms=400):
    h = {"X-Presto-User": "tailer", "X-Presto-Catalog": "stream"}
    if tail:
        h["X-Presto-Session"] = (
            f"stream_tail_enabled=true,stream_poll_ms={poll_ms}")
    req = urllib.request.Request(url, data=data, headers=h,
                                 method=method)
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read().decode())


@pytest.fixture()
def tail_server():
    from presto_tpu.server.http_server import PrestoTpuServer

    conn, rng = _mkconn(60, groups=4)
    srv = PrestoTpuServer({"stream": conn}, default_catalog="stream",
                          port=0)
    port = srv.start()
    try:
        yield srv, conn, rng, f"http://127.0.0.1:{port}"
    finally:
        srv.stop()


def test_tail_cursor_delivers_exactly_the_delta(tail_server):
    srv, conn, rng, base = tail_server
    b = _tail_req(f"{base}/v1/statement",
                  b"select k, v from events where k = 1", "POST")
    assert b["stats"]["state"] == "RUNNING"
    assert "nextUri" in b
    initial = b.get("data", [])
    assert all(row[0] == 1 for row in initial)
    # idle poll: empty page, fresh nextUri (the tail heartbeat)
    b2 = _tail_req(b["nextUri"], poll_ms=100)
    assert "data" not in b2
    assert "nextUri" in b2
    batch = [(1, 999.5), (2, 1.0), (1, 123.25)]
    conn.append("events", batch)
    appends_before = srv._runner.executor.stream_appends_seen
    b3 = _tail_req(b2["nextUri"])
    assert b3.get("data") == [[1, 999.5], [1, 123.25]]
    # the poll observed the offset advance (counter surface)
    assert srv._runner.executor.stream_appends_seen > appends_before
    # cancel terminates the cursor: no nextUri on the next page
    _tail_req(f"{base}/v1/statement/{b['id']}", method="DELETE",
              tail=False)
    b4 = _tail_req(b3["nextUri"])
    assert "nextUri" not in b4
    assert b4["stats"]["state"] == "CANCELED"


def test_tail_cursor_rides_ivm_for_registered_view(tail_server):
    srv, conn, rng, base = tail_server
    reg = IVM.shared_registry()
    sql = "select k, count(*), sum(v) from events group by k order by k"
    reg.register(srv._runner, "live", sql)
    ex = srv._runner.executor
    b = _tail_req(f"{base}/v1/statement", sql.encode(), "POST")
    assert len(b["data"]) == 4  # the full initial snapshot
    assert ex.ivm_refreshes >= 1
    conn.append("events", [(0, 10.0), (0, 20.0)])
    folded_before = ex.delta_pages_folded
    b2 = _tail_req(b["nextUri"])
    # only the CHANGED aggregate row arrives, computed incrementally
    assert len(b2["data"]) == 1
    assert b2["data"][0][0] == 0
    assert ex.delta_pages_folded > folded_before
    assert ex.ivm_full_recomputes == 0
    assert ex.cursor_polls >= 2
    _tail_req(f"{base}/v1/statement/{b['id']}", method="DELETE",
              tail=False)


def test_non_stream_statement_ignores_tail_flag(tail_server):
    srv, conn, rng, base = tail_server
    b = _tail_req(f"{base}/v1/statement", b"select 1", "POST")
    # falls through to the normal protocol: the query FINISHES
    for _ in range(50):
        if "nextUri" not in b:
            break
        b = _tail_req(b["nextUri"])
    assert b["stats"]["state"] == "FINISHED"


def test_concurrent_appender_and_four_tailers(tail_server):
    """The PR-11 gate applied to the new subsystem: one appender
    races 4 tailing protocol clients; every client receives every
    log row exactly once (initial snapshot + deltas) and the armed
    lock sanitizer records ZERO violations."""
    from presto_tpu.obs import sanitizer as san

    srv, conn, rng, base = tail_server
    violations_before = san.violation_count()
    seed_rows = conn.host_rows("events")
    batches = [[(rng.randrange(4), 1000.0 + i * 100 + j)
                for j in range(25)] for i in range(8)]
    total = len(seed_rows) + sum(len(b) for b in batches)
    results = {}

    def tailer(idx: int) -> None:
        got = []
        b = _tail_req(f"{base}/v1/statement",
                      b"select k, v from events", "POST",
                      poll_ms=250)
        got.extend(b.get("data", []))
        while len(got) < total and "nextUri" in b:
            b = _tail_req(b["nextUri"], poll_ms=250)
            got.extend(b.get("data", []))
        results[idx] = (got, b["id"])

    threads = [threading.Thread(target=tailer, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()

    def appender() -> None:
        for batch in batches:
            conn.append("events", batch)

    a = threading.Thread(target=appender, daemon=True)
    a.start()
    a.join(timeout=30)
    for t in threads:
        t.join(timeout=60)
    assert all(not t.is_alive() for t in threads)

    want = collections.Counter(
        (int(k), float(v))
        for k, v in seed_rows + [r for b in batches for r in b]
    )
    for idx, (got, qid) in results.items():
        assert collections.Counter(
            (int(k), float(v)) for k, v in got) == want, (
            f"tailer {idx} row multiset diverged")
        _tail_req(f"{base}/v1/statement/{qid}", method="DELETE",
                  tail=False)
    assert san.violation_count() == violations_before
    assert srv._runner.executor.cursor_polls >= 4


# ------------------------------------------------ surfaces + harness
def test_counters_registered_and_surfaced(tail_server):
    from presto_tpu.exec import counters as CTRS

    for name in ("delta_pages_folded", "ivm_refreshes",
                 "ivm_full_recomputes", "cursor_polls",
                 "stream_appends_seen"):
        assert name in CTRS.QUERY_COUNTERS
    srv, conn, rng, base = tail_server
    snap = CTRS.snapshot(srv._runner.executor)
    assert "ivm_refreshes" in snap
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        text = r.read().decode()
    for metric in ("presto_tpu_ivm_refreshes_total",
                   "presto_tpu_delta_pages_folded_total",
                   "presto_tpu_cursor_polls_total",
                   "presto_tpu_stream_appends_seen_total",
                   "presto_tpu_ivm_full_recomputes_total"):
        assert metric in text


def test_append_writer_beside_ivm_reader():
    """A writer appends while a reader refreshes an IVM-safe view:
    every refresh folds a delta (never a full recompute), every append
    is counted, and the settled rows equal a cold recompute."""
    conn, rng = _mkconn(256)
    r = _runner(conn)
    sink = r.executor
    view = IVM.IvmRegistry().register(r, "dash", VIEW_SQL)
    IVM.refresh(view, session=r.session, sink=sink)  # settle + compile
    appends, errors = 12, []
    done = threading.Event()

    def writer():
        try:
            for _ in range(appends):
                conn.append("events", _batch(rng, 64))
                sink.count_stream_append()
                done.wait(0.01)  # pace: leave the reader CPU to fold
        except Exception as e:  # noqa: BLE001 - surfaced by assert
            errors.append(e)
        done.set()

    def reader():
        try:
            while not done.is_set():
                conn.wait_for_offset("events", view.settled_offset(), 0.2)
                IVM.refresh(view, session=r.session, sink=sink)
        except Exception as e:  # noqa: BLE001 - surfaced by assert
            errors.append(e)

    threads = [threading.Thread(target=f, daemon=True)
               for f in (writer, reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert sink.stream_appends_seen == appends
    assert sink.ivm_refreshes >= 2 and sink.ivm_full_recomputes == 0
    _n, rows, _t = IVM.refresh(view, session=r.session, sink=sink)
    assert view.settled_offset() == conn.offset("events") == 256 + 64 * appends
    _rows_close(rows, r.execute(VIEW_SQL).rows)


# ------------------------------------------- review-hardened contracts
def test_failed_append_leaves_log_untouched():
    """A mid-batch arity error must not orphan rows below the offset:
    the whole batch validates before anything mutates."""
    conn, _rng = _mkconn(5)
    with pytest.raises(ValueError):
        conn.append("events", [(1, 2.0), (3,)])  # bad arity mid-batch
    assert conn.offset("events") == 5
    rows = conn.host_rows("events")
    assert len(rows) == 5
    conn.append("events", [(9, 9.0)])
    assert conn.offset("events") == 6
    assert conn.host_rows("events")[-1] == (9, 9.0)


def test_concurrent_full_refresh_never_regresses_watermark():
    """The losing concurrent refresher re-reads the log head after
    winning the _refreshing flag, so a full-recompute view can never
    publish an older snapshot over a newer one."""
    conn, rng = _mkconn(300)
    r = _runner(conn)
    sql = "select count(*), sum(v) from events"  # unsafe: always full
    view = IVM.IvmRegistry().register(r, "race", sql)
    errors = []

    def refresher():
        try:
            for _ in range(5):
                IVM.refresh(view, session=r.session, sink=r.executor)
        except Exception as e:  # noqa: BLE001 - surfaced by assert
            errors.append(e)

    threads = [threading.Thread(target=refresher, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    for _ in range(6):
        conn.append("events", _batch(rng, 11))
    for t in threads:
        t.join(timeout=60)
    assert not errors
    # the settled result covers the final offset exactly
    assert view.settled_offset() == conn.offset("events")
    _n, rows, _t = IVM.refresh(view, session=r.session,
                               sink=r.executor)
    _rows_close(rows, r.execute(sql).rows)


def test_tail_recompute_watches_every_scanned_stream(tail_server):
    """A cursor over a statement scanning TWO streams must deliver
    rows when EITHER advances (the recompute mode's multi-stream
    poll)."""
    srv, conn, rng, base = tail_server
    conn.create_table("dims", ["k", "name"], [T.BIGINT, T.VARCHAR],
                      [(i, f"g{i}") for i in range(4)])
    sql = ("select d.name, count(*) from events e join dims d "
           "on e.k = d.k group by d.name order by d.name")
    b = _tail_req(f"{base}/v1/statement", sql.encode(), "POST")
    assert "nextUri" in b and b.get("data")
    # append to the SECOND stream (the dimension): a 5th group joins
    conn.append("dims", [(3, "g3b")])  # k=3 rows now match twice? no:
    # g3b duplicates k=3 -> join fan-out changes counts for k=3
    b2 = _tail_req(b["nextUri"])
    assert b2.get("data"), "append to the non-primary stream was lost"
    assert any(row[0] == "g3b" for row in b2["data"])
    _tail_req(f"{base}/v1/statement/{b['id']}", method="DELETE",
              tail=False)


def test_tail_cursor_memory_stays_bounded(tail_server):
    """The never-finishing cursor trims rows past the retry horizon
    instead of retaining everything it ever emitted."""
    from presto_tpu.server.http_server import _TAIL_RETAIN_SPANS

    srv, conn, rng, base = tail_server
    b = _tail_req(f"{base}/v1/statement",
                  b"select k, v from events", "POST", poll_ms=100)
    qid = b["id"]
    q = srv.manager.get(qid)
    total = len(b.get("data", []))
    for i in range(_TAIL_RETAIN_SPANS + 6):
        conn.append("events", _batch(rng, 30))
        b = _tail_req(b["nextUri"], poll_ms=400)
        total += len(b.get("data", []))
    # every appended row was delivered exactly once...
    assert total == conn.offset("events")
    # ...but the cursor retains only the retry horizon, not the log
    assert len(q.tail.rows) < total
    _tail_req(f"{base}/v1/statement/{qid}", method="DELETE",
              tail=False)
