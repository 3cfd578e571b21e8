"""ISSUE 13: the device-resident data plane.

Covers the three tentpole layers plus the satellites:
  - oracle parity of the DEVICE repartition kernel against the host
    splitmix64 path — same partition assignment per key type (int /
    float incl. -0.0 and NaN / bool / short+long decimal / dictionary)
    including the NULL sentinel;
  - ladder-bucket compaction + the skew->overflow-flag contract;
    exchange routing has ONE hash, whatever pallas_join_enabled says;
  - the acceptance pin: a forced-partitioned distributed q3-family
    query over same-process workers completes its EXCHANGE PHASE with
    zero h2d/d2h process-total deltas (measured at the last stage
    boundary via the scheduler's stage hook), zero h2d for the whole
    query, rows identical to the host-spool path AND the sqlite
    oracle, mesh_local_exchanges counted;
  - the fault-tolerance fallback: device-resident spools materialize
    host bytes LAZILY for HTTP consumers, and a worker lost
    mid-exchange still replays from surviving spools with identical
    rows;
  - buffer donation: buffers_donated >= 1 on an overflow-retry query
    with rows identical and peak_device_bytes no higher than the
    non-donated baseline; the membudget model discounts donated
    accumulators;
  - the xfercheck jnp.asarray gap is closed (seeded violation).
"""

import collections

import jax
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.dist import spool as SPOOL
from presto_tpu.dist.dcn import DcnRunner
from presto_tpu.exec import xfer as XF
from presto_tpu.exec.executor import Executor
from presto_tpu.page import Page
from presto_tpu.runner import LocalRunner
from presto_tpu.server.worker import WorkerServer
from tests.oracle import load_sqlite

SF = 0.01
PAGE_ROWS = 1 << 13

# q3-family: forced-partitioned join + group-by over integer columns
# (decimal-free so the sqlite oracle compares exactly)
Q3_FAMILY = (
    "select o_orderkey, count(*) c from lineitem "
    "join orders on l_orderkey = o_orderkey "
    "where o_orderkey < 1000 group by o_orderkey order by o_orderkey"
)


def rows_equal(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b))


def _key_page():
    """One page exercising every partitionable key family with NULLs."""
    return Page.from_arrays(
        [
            [1, -7, None, 4, 0, 2**40, -1, 5],
            [1.5, -0.0, 0.0, None, float("nan"), 2.5, -3.5, 1e300],
            [True, False, None, True, False, True, False, True],
            ["a", "b", "a", None, "c", "b", "zz", "a"],
            [105, None, -205, 305, 0, 105, 42, 7],       # decimal(9,2)
            [10**20, -(10**20), None, 7, 0, 10**20, 1, 2],  # p>18
        ],
        [T.BIGINT, T.DOUBLE, T.BOOLEAN, T.VARCHAR,
         T.DecimalType(9, 2), T.DecimalType(30, 2)],
    )


def _device_hash(page, keys):
    luts = tuple(
        XF.to_device(SPOOL._dict_value_hashes(page.block(k).dictionary))
        if page.block(k).dictionary is not None else None
        for k in keys
    )
    return np.asarray(SPOOL.device_row_hash_u64(page, keys, luts))


# --------------------------------------------------- kernel parity
@pytest.mark.parametrize("keys", [(0,), (1,), (2,), (3,), (4,), (5,),
                                  (0, 1, 2, 3, 4, 5)])
def test_device_hash_parity_per_key_type(keys):
    """The jnp kernel computes the SAME splitmix64 value-hash as the
    host path for every key family — int, float (-0.0/NaN
    normalized), bool, dictionary VALUES, short and long decimal —
    with NULL keys on the fixed sentinel, so both tiers route every
    row to the same partition."""
    page = _key_page()
    host_page = jax.device_get(page)
    host = SPOOL.row_hash_u64(host_page, keys)
    dev = _device_hash(page, keys)
    assert np.array_equal(host, dev)
    for nparts in (2, 3, 8):
        assert np.array_equal(host % nparts, dev % nparts)


@pytest.mark.parametrize("pallas_join", ["auto", "force"])
def test_device_partition_matches_host_partition(pallas_join):
    """Row multisets per partition agree between the tiers for every
    key type (device emits every partition incl. empties; host skips
    empties) — also under pallas_join_enabled=true: exchange routing
    has one hash, so a mixed pool co-partitions."""
    page = _key_page()
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.device_exchange = "true"
    ex.pallas_join = pallas_join
    for keys in ((0,), (1,), (2,), (3,), (4,), (5,), (0, 1)):
        dev = {
            p: sorted(map(repr, pp.to_pylist()))
            for p, pp in SPOOL.device_partition_pages(ex, page, keys, 4)
        }
        host = {
            p: sorted(map(repr, pp.to_pylist()))
            for p, pp in SPOOL.partition_host_page(
                jax.device_get(page), keys, 4)
        }
        for p in range(4):
            assert dev[p] == host.get(p, []), f"keys={keys} part={p}"


def test_device_partition_caps_ride_the_ladder():
    """Output pages land on ladder-bucket capacities; a skewed key
    (every row in one partition) overflows the chunk bucket and
    raises the deferred flag — the boosted-retry contract."""
    from presto_tpu.exec import shapes as SH

    n = 8192
    page = Page.from_arrays([[7] * n], [T.BIGINT])
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.device_exchange = "true"
    parts = SPOOL.device_partition_pages(ex, page, (0,), 8)
    cap = SH.exchange_partition_cap(page.capacity, 8, 1)
    assert all(pp.capacity == cap for _, pp in parts)
    assert cap < n  # the skewed partition cannot hold every row
    assert bool(ex._overflow_flagged())
    # boosted re-entry sizes one rung family up, on the ladder
    ex2 = Executor({"tpch": TpchConnector(SF)})
    ex2.device_exchange = "true"
    ex2._capacity_boost = 4
    parts2 = SPOOL.device_partition_pages(ex2, page, (0,), 8)
    assert all(pp.capacity == 4 * cap for _, pp in parts2)


# ------------------------------------------- acceptance: zero-crossing
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
def q3_base():
    """Single-node q3-family baseline rows, computed once: four tests
    compare distributed rows against it and the LocalRunner compile
    is the expensive part."""
    single = LocalRunner({"tpch": TpchConnector(SF)},
                         page_rows=PAGE_ROWS)
    return single.execute(Q3_FAMILY).rows


def _coord(workers, **props):
    defaults = {
        "stage_scheduler": "true",
        "join_distribution_type": "partitioned",
        "retry_backoff_ms": 20,
    }
    defaults.update(props)
    return DcnRunner({"tpch": TpchConnector(SF)}, workers,
                     default_catalog="tpch", page_rows=PAGE_ROWS,
                     session_props=defaults)


def test_mesh_local_exchange_zero_crossings(workers, q3_base):
    """THE acceptance pin: a forced-partitioned q3-family query over
    same-process workers with device_exchange_enabled records ZERO
    h2d/d2h process-total deltas for the exchange phase (snapshot at
    the last stage boundary — every worker emit and consumer ingest
    has happened by then), zero h2d for the whole query (only result
    decode crosses, d2h), and rows identical to both the host-spool
    path and the sqlite oracle."""
    base = q3_base

    # mesh_exchange_mode=false: this test pins the SPOOL plane's
    # ledger (per-partition spools + stats vectors); the ICI
    # all_to_all plane (ISSUE 18), which pulls no stats vectors at
    # all, has its own pin in test_ici_exchange_ledger_pin
    coord = _coord(workers, device_exchange_enabled="true",
                   mesh_exchange_mode="false")
    at_stage = {}

    def hook(fid):
        at_stage["totals"] = XF.process_totals()
        at_stage["spooled"] = coord.runner.executor \
            .spooled_exchange_pages

    coord._stage_hook = hook
    t0 = XF.process_totals()
    spooled0 = coord.runner.executor.spooled_exchange_pages
    try:
        rows = coord.execute(Q3_FAMILY)
    finally:
        coord._stage_hook = None
    t1 = XF.process_totals()
    assert coord.last_distribution == "stage-dag"
    # exchange phase: zero PAGE-DATA crossings end to end. The only
    # d2h is the adaptive spool-stats plane (ISSUE 15): ONE int64
    # per spooled partition entry — the per-partition row-count
    # vector the device partition program emits alongside the pages
    # — and ONE byte for each deferred overflow flag a task's attempt
    # reads at its end (a metered pull since ISSUE 37). Pinning the
    # bytes this closely keeps the zero-copy contract falsifiable:
    # any real page pull would dwarf 8 bytes/entry.
    ex_h2d = at_stage["totals"]["h2d_bytes"] - t0["h2d_bytes"]
    ex_d2h = at_stage["totals"]["d2h_bytes"] - t0["d2h_bytes"]
    stats_bytes = 8 * (at_stage["spooled"] - spooled0)
    assert ex_h2d == 0, f"exchange phase staged {ex_h2d} bytes h2d"
    assert 0 <= ex_d2h - stats_bytes < 256, (
        f"exchange phase pulled {ex_d2h} bytes d2h — expected "
        f"the spool-stats vectors ({stats_bytes} bytes) and a byte "
        f"an overflow flag")
    # whole query: nothing ever stages back; decode (and the stats
    # vectors) are the only d2h
    assert t1["h2d_bytes"] - t0["h2d_bytes"] == 0
    assert t1["d2h_bytes"] - t0["d2h_bytes"] > 0
    assert coord.runner.executor.mesh_local_exchanges >= 1
    # parity: host-spool path and sqlite oracle
    host_rows = _coord(workers,
                       device_exchange_enabled="false").execute(
        Q3_FAMILY)
    assert rows_equal(rows, host_rows)
    assert rows_equal(rows, base)
    db = load_sqlite(TpchConnector(SF), ["lineitem", "orders"])
    want = db.execute(Q3_FAMILY).fetchall()
    assert rows_equal(rows, want)


def test_host_spool_path_pays_the_copy_tax(workers):
    """The transfer-ledger diff the tentpole is graded by: the
    host-spool path records real h2d AND d2h exchange volume for the
    same query the device tier completes at zero (the
    d2h/h2d pair)."""
    coord = _coord(workers, device_exchange_enabled="false")
    t0 = XF.process_totals()
    coord.execute(Q3_FAMILY)
    t1 = XF.process_totals()
    assert t1["h2d_bytes"] - t0["h2d_bytes"] > 0
    assert t1["d2h_bytes"] - t0["d2h_bytes"] > 0


# ------------------------------------ fallback: lazy spools + replay
def test_lazy_spool_materializes_for_http(workers):
    """Device-resident spool entries hold Pages (no serialization at
    emit); an HTTP fetch — what a DCN-remote consumer or a replay
    does — lazily materializes byte-identical wire blobs, and the
    deserialized rows match the direct Page read."""
    import json
    import urllib.request

    from presto_tpu.dist import serde

    uri = workers[0]
    payload = {
        "taskId": "lazytest.f0.t0",
        "sql": None,
        "splitTable": "orders",
        "splitIndex": 0,
        "splitCount": 1,
        "outputPartitions": 3,
        "outputKeys": [0],
        "session": {"device_exchange_enabled": "true"},
        "fragment": None,
    }
    # ship a real fragment: scan orders, project keys
    r = LocalRunner({"tpch": TpchConnector(SF)}, page_rows=PAGE_ROWS)
    plan = r.plan("select o_orderkey, o_custkey from orders "
                  "where o_orderkey < 500")
    from presto_tpu.dist import plan_serde
    from presto_tpu.dist.fragmenter import clip_for_shipping

    payload["fragment"] = plan_serde.dumps(clip_for_shipping(plan))
    req = urllib.request.Request(
        f"{uri}/v1/task", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=10).close()
    # wait for completion via status plane
    import time

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
                f"{uri}/v1/task/lazytest.f0.t0", timeout=5) as resp:
            st = json.loads(resp.read().decode())
        if st["state"] != "RUNNING":
            break
        time.sleep(0.05)
    assert st["state"] == "FINISHED", st.get("error")
    # the spool holds LAZY page entries (nothing serialized at emit)
    from presto_tpu.server.worker import local_runtime

    rt = local_runtime(uri)
    task = rt.get_task("lazytest.f0.t0")
    entries = [e for p in task.spool.parts for e in p._entries]
    assert entries and all(e[0] == "page" for e in entries)
    # direct Page read (the mesh-local path)
    direct = []
    for p in range(3):
        for page in SPOOL.local_source_pages(uri, "lazytest.f0.t0", p):
            direct.extend(page.to_pylist())
    # HTTP fetch (the remote/replay path): lazy materialization
    fetched = []
    for p in range(3):
        for blob in SPOOL.fetch_spool_blobs(uri, "lazytest.f0.t0", p):
            fetched.extend(serde.deserialize_page(blob).to_pylist())
        # byte-identical on re-fetch (replay prefix verification)
        again = list(SPOOL.fetch_spool_blobs(uri, "lazytest.f0.t0", p))
        assert again == list(SPOOL.fetch_spool_blobs(
            uri, "lazytest.f0.t0", p))
    assert rows_equal(direct, fetched)
    urllib.request.urlopen(urllib.request.Request(
        f"{uri}/v1/task/lazytest.f0.t0", method="DELETE"),
        timeout=5).close()


def test_worker_loss_mid_exchange_replays(workers, q3_base):
    """Forced fallback: a worker lost between stages (HTTP down AND
    out of the local-runtime registry, so the mesh-local path cannot
    serve its spools) still completes — the scheduler excludes the
    node and replays its tasks on the survivor, and rows match the
    healthy run. Uses its own workers so the module fixture survives."""
    w1 = WorkerServer({"tpch": TpchConnector(SF)}, node_id="k1",
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    w2 = WorkerServer({"tpch": TpchConnector(SF)}, node_id="k2",
                      default_catalog="tpch", page_rows=PAGE_ROWS)
    uris = [f"http://127.0.0.1:{w1.start()}",
            f"http://127.0.0.1:{w2.start()}"]
    try:
        base = q3_base
        coord = _coord(uris, device_exchange_enabled="true",
                       task_retry_attempts=3)
        killed = {}

        def hook(fid):
            if not killed:
                killed["uri"] = True
                w1.stop()  # unregisters locally + kills HTTP

        coord._stage_hook = hook
        try:
            rows = coord.execute(Q3_FAMILY)
        finally:
            coord._stage_hook = None
        assert rows_equal(rows, base)
        assert coord.runner.executor.task_retries >= 1
    finally:
        w1.stop()
        w2.stop()


# -------------------------------------------------- buffer donation
def test_donation_overflow_retry_pin():
    """Acceptance: an overflow-retry query with donation forced
    reports buffers_donated >= 1 with rows identical to the
    non-donated baseline and peak_device_bytes no higher."""
    q = ("select n_regionkey, array_agg(n_nationkey) from nation "
         "group by n_regionkey")

    def run(donate):
        r = LocalRunner({"tpch": TpchConnector(SF)},
                        default_catalog="tpch", page_rows=PAGE_ROWS)
        r.session.set("buffer_donation_enabled", donate)
        # 5 nations per region vs 2 slots: guaranteed first-run
        # collect-state overflow onto the boost ladder
        r.session.set("array_agg_max_elements", 2)
        rows = r.execute(q).rows
        ex = r.executor
        return rows, ex

    rows_off, ex_off = run("false")
    rows_on, ex_on = run("true")
    assert ex_off.capacity_boost_retries > 0
    assert ex_on.capacity_boost_retries > 0
    assert rows_equal(rows_off, rows_on)
    assert ex_off.buffers_donated == 0
    assert ex_on.buffers_donated >= 1
    assert ex_on.peak_memory_bytes <= ex_off.peak_memory_bytes


def test_donation_oracle_parity_grouped_agg():
    """Donation changes allocations, never results: grouped
    aggregation with donation forced matches the sqlite oracle."""
    q = ("select l_orderkey, count(*), sum(l_quantity) from lineitem "
         "where l_orderkey < 400 group by l_orderkey "
         "order by l_orderkey")
    r = LocalRunner({"tpch": TpchConnector(SF)},
                    default_catalog="tpch", page_rows=PAGE_ROWS)
    r.session.set("buffer_donation_enabled", "true")
    rows = r.execute(q).rows
    assert r.executor.buffers_donated >= 1
    db = load_sqlite(TpchConnector(SF), ["lineitem"])
    want = db.execute(q).fetchall()
    assert rows_equal([tuple(x) for x in rows],
                      [tuple(x) for x in want])


def test_membudget_model_discounts_donated_state():
    """The footprint model learns donation: a donated fold
    accumulator counts half (merge in/out share one allocation), so
    the audited peak with donation on never exceeds the peak with it
    off — and the agg-state buffer is marked donated."""
    from presto_tpu.exec import membudget as MB

    r = LocalRunner({"tpch": TpchConnector(SF)},
                    default_catalog="tpch", page_rows=PAGE_ROWS)
    plan = r.plan("select l_orderkey, sum(l_quantity) from lineitem "
                  "group by l_orderkey")
    ex = r.executor
    ex.buffer_donation = "false"
    off = MB.audit(ex, plan)
    ex.buffer_donation = "true"
    on = MB.audit(ex, plan)
    assert on.peak_bytes <= off.peak_bytes
    donated = [b for b in on.buffers if b.donated]
    assert any(b.label == "agg state" for b in donated)
    assert not any(b.donated for b in off.buffers)
    for b in donated:
        assert b.live_bytes == b.bytes // 2


def test_donated_jit_wrapper_is_salted():
    """Flipping the donation knob mid-executor must not hand a
    donating program to a non-donating call site (the cache-key salt
    contract)."""
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.buffer_donation = "true"
    f1 = ex._jit(("k",), lambda x: x + 1, donate_argnums=(0,))
    ex.buffer_donation = "false"
    f2 = ex._jit(("k",), lambda x: x + 1, donate_argnums=(0,))
    assert f1 is not f2
    import jax.numpy as jnp

    x = jnp.arange(4)
    assert np.array_equal(np.asarray(f2(x)), np.arange(4) + 1)
    assert np.array_equal(np.asarray(x), np.arange(4))  # NOT donated


# ------------------------- ISSUE 18: ICI all_to_all exchange plane
def _partition_rows(pairs_or_lists, nparts):
    """Normalize both planes' outputs to sorted row-repr lists per
    partition: spool plane yields (p, page) pairs, the ICI plane a
    list-of-page-lists indexed by partition."""
    out = [[] for _ in range(nparts)]
    if isinstance(pairs_or_lists, list) and pairs_or_lists and \
            isinstance(pairs_or_lists[0], list):
        for p, plist in enumerate(pairs_or_lists):
            for pp in plist:
                out[p].extend(map(repr, pp.to_pylist()))
    else:
        for p, pp in pairs_or_lists:
            out[p].extend(map(repr, pp.to_pylist()))
    return [sorted(r) for r in out]


# every key family at nparts=4, plus ONE nparts=2 case: the routing
# hash is nparts-independent (h % D), so one extra D pins the modulo
# plumbing without paying a shard_map compile per (keys, D) pair
@pytest.mark.parametrize("keys,nparts", [
    ((0,), 4), ((0,), 2), ((1,), 4), ((3,), 4), ((4,), 4), ((5,), 4),
    ((0, 1, 2, 3, 4, 5), 4),
])
def test_ici_vs_spool_partition_parity_per_key_type(keys, nparts):
    """The routing contract the fallback depends on: the all_to_all
    program and the spool partitioner put EVERY row in the SAME
    partition for every key family — NULL sentinel, -0.0/NaN
    normalization, dictionary VALUE hashes, short and long decimal —
    because both compute the identical splitmix64 row hash."""
    from presto_tpu.dist import executor as DX

    page = _key_page()
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.device_exchange = "true"
    parts, nbytes = DX.ici_exchange_pages(ex, [page], keys, nparts)
    ici = _partition_rows(parts, nparts)
    spool = _partition_rows(
        list(SPOOL.device_partition_pages(ex, page, keys, nparts)),
        nparts)
    assert ici == spool, f"keys={keys} nparts={nparts}"
    assert sum(len(r) for r in ici) == len(page.to_pylist())
    assert nbytes > 0


def test_ici_skew_overflow_boosts_and_preserves_rows():
    """Seeded skew on the ICI path: every row hashes to ONE partition,
    overflowing the chunk-bucketed landing capacity — the OR-reduced
    overflow flag settles on the boost ladder (capacity_boost_retries
    counted) and no row is dropped."""
    from presto_tpu.dist import executor as DX

    n = 1 << 14
    page = Page.from_arrays([[7] * n], [T.BIGINT])
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.device_exchange = "true"
    r0 = ex.capacity_boost_retries
    parts, _ = DX.ici_exchange_pages(ex, [page], (0,), 4)
    assert ex.capacity_boost_retries - r0 >= 1
    rows = [r for plist in parts for pp in plist
            for r in pp.to_pylist()]
    assert len(rows) == n
    nonempty = [p for p, plist in enumerate(parts)
                if any(pp.num_rows() for pp in plist)]
    assert len(nonempty) == 1  # the skewed key routes to ONE shard


def test_ici_exchange_ledger_pin(workers, q3_base, monkeypatch):
    """THE ISSUE-18 acceptance pin: on the mesh path the q3-family
    exchange phase crosses ZERO bytes in EITHER direction (no spool
    stats vectors — the collective pulls nothing) AND serializes ZERO
    spool blobs (the wire codec never runs), with ici_exchanges
    counted and rows identical to the spool plane and the sqlite
    oracle."""
    base = q3_base

    blobs = {"n": 0}
    real = SPOOL.spool_blob

    def counting_blob(page):
        blobs["n"] += 1
        return real(page)

    monkeypatch.setattr(SPOOL, "spool_blob", counting_blob)
    coord = _coord(workers, device_exchange_enabled="true")  # auto mesh
    snaps = []

    def hook(fid):
        snaps.append(XF.process_totals())

    coord._stage_hook = hook
    t0 = XF.process_totals()
    try:
        rows = coord.execute(Q3_FAMILY)
    finally:
        coord._stage_hook = None
    t1 = XF.process_totals()
    ex = coord.runner.executor
    assert coord.last_distribution == "stage-dag"
    assert ex.ici_exchanges >= 1
    assert ex.mesh_exchange_fallbacks == 0
    assert ex.ici_bytes > 0
    # q3's DAG is [repartition, repartition, gather]; each _stage_hook
    # boundary fires AFTER that stage's barrier AND its post-barrier
    # all_to_all, so the second-to-last snapshot closes the exchange
    # phase. (The final gather stage still pays the ISSUE-15 gather-
    # edge spool-stats pull — 8 bytes/page — which is NOT an exchange
    # crossing; the mesh plane deleted the repartition-edge stats
    # entirely, which is exactly what this pin holds at ZERO.)
    assert len(snaps) >= 2
    ex_h2d = snaps[-2]["h2d_bytes"] - t0["h2d_bytes"]
    ex_d2h = snaps[-2]["d2h_bytes"] - t0["d2h_bytes"]
    assert ex_h2d == 0, f"ICI exchange staged {ex_h2d} bytes h2d"
    assert ex_d2h == 0, f"ICI exchange pulled {ex_d2h} bytes d2h"
    assert blobs["n"] == 0, (
        f"mesh path serialized {blobs['n']} spool blobs — the wire "
        f"codec must never run on the ICI plane")
    # whole query: only result decode crosses (d2h)
    assert t1["h2d_bytes"] - t0["h2d_bytes"] == 0
    assert t1["d2h_bytes"] - t0["d2h_bytes"] > 0
    # parity: spool plane and sqlite oracle
    monkeypatch.setattr(SPOOL, "spool_blob", real)
    spool_rows = _coord(workers, device_exchange_enabled="true",
                        mesh_exchange_mode="false").execute(Q3_FAMILY)
    assert rows_equal(rows, spool_rows)
    assert rows_equal(rows, base)
    db = load_sqlite(TpchConnector(SF), ["lineitem", "orders"])
    assert rows_equal(rows, db.execute(Q3_FAMILY).fetchall())


def test_ici_trace_failure_falls_back_to_spool(workers, q3_base,
                                               monkeypatch):
    """Mid-query fallback: when the collective cannot lower (forced
    here by making ici_exchange_pages raise), the scheduler falls
    back LOUDLY to the spool partitioner — counted, logged — and the
    query still returns identical rows, because the fallback routes
    with the bit-identical splitmix64 hash."""
    from presto_tpu.dist import executor as DX

    base = q3_base

    def boom(ex, pages, keys, nparts):
        raise RuntimeError("forced trace failure")

    monkeypatch.setattr(DX, "ici_exchange_pages", boom)
    coord = _coord(workers, device_exchange_enabled="true")
    rows = coord.execute(Q3_FAMILY)
    ex = coord.runner.executor
    assert ex.mesh_exchange_fallbacks >= 1
    assert ex.ici_exchanges == 0
    assert rows_equal(rows, base)


# ------------------------------------------------- xfercheck jnp gap
def test_xfercheck_catches_jnp_asarray_of_host_array(tmp_path):
    """The satellite: a jnp.asarray of a non-literal argument is an
    h2d primitive the gate must see (undeclared -> finding); host
    literals stay exempt."""
    from tools.xfercheck import run_xfercheck

    bad = tmp_path / "presto_tpu" / "exec" / "victim.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        "import jax.numpy as jnp\n"
        "def stage(arr):\n"
        "    return jnp.asarray(arr)\n"
        "def literal_ok():\n"
        "    return jnp.asarray([1, 2, 3])\n"
    )
    findings = run_xfercheck([str(bad)])
    assert any(f.rule == "xfer-registry" and "stage" in f.message
               for f in findings)
    assert not any("literal_ok" in f.message for f in findings)
