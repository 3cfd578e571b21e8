"""The tpch_resident_1chip deployment (ISSUE 33) through the path the
benchmark's cell takes: the cell's configuration written as etc/,
server_from_etc -> /v1/statement -> the serial path's runner and
executor, checked against the benchmark's plain references over the
INNER generator's rows, with what the program says of itself on
/metrics and on the attempt span."""

import pytest

from benchmarks.harness import manifest, reference, serve
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.exec.counters import QUERY_COUNTERS
from presto_tpu.server.http_server import QueryManager

CELL = manifest.load_cell("scan_sf10_resident_solo")
STATEMENTS = {st.key: st for st in CELL.every}
SLOTS = 15000 * 7           # lineitem at the rehearsal's SF0.01
TABLE_BYTES = (SLOTS + (1 << 17)) * 93
# the session properties that put the chip's drivers on the CPU
CHIP_PATH = {"fused_partial_agg_enabled": "true",
             "split_batch_size": "8",
             "query_trace_enabled": "true"}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    etc = str(tmp_path_factory.mktemp("resident") / "etc")
    props = serve.write_etc(etc, CELL.config, rehearse=True)
    assert props["tpch"] == {
        "connector.name": "resident", "resident.inner": "tpch",
        "tpch.scale-factor": serve.REHEARSE_SCALE_FACTOR,
        "resident.tables": "lineitem"}
    srv = serve.Served(etc, CELL.chips)
    want = reference.answers(
        CELL.every, srv.catalogs, props,
        str(tmp_path_factory.mktemp("answers")), log=lambda **kw: None)
    yield srv, want
    srv.stop()


def _spans(info):
    out, todo = [], [info]
    while todo:
        node = todo.pop()
        if isinstance(node, dict):
            if "kind" in node and "attrs" in node:
                out.append(node)
            todo.extend(node.values())
        elif isinstance(node, list):
            todo.extend(node)
    return out


def test_the_references_read_the_generator_not_the_store(served):
    """host_pages goes by splits / page_for_split, which the resident
    connector hands to its inner connector: computing the references
    loads nothing."""
    srv, want = served
    conn = srv.catalogs["tpch"]
    assert isinstance(conn, ResidentConnector)
    assert all(want[key] for key in STATEMENTS)
    assert conn.resident_loads == 0 or srv.metrics()[
        "resident_loads"] == conn.resident_loads


@pytest.mark.parametrize("key", sorted(STATEMENTS))
def test_served_statement_equals_the_plain_reference(key, served):
    srv, want = served
    st = STATEMENTS[key]
    client = srv.client(st.catalog)
    client.session_properties.update(CHIP_PATH)
    res = client.execute(st.sql)
    assert res.state == "FINISHED", res.error
    got = reference.engine_encoding(res.columns, res.rows)
    assert reference.mismatch(got, want[key]) == ""
    spans = _spans(srv.query_info(res.query_id))
    attempt = [sp for sp in spans if sp["kind"] == "attempt"][-1]["attrs"]
    launches = attempt["launches"]
    # 26 splits of 4,095 slots... the server's page-rows is 262144: one
    # split holds the rehearsal's whole table, so one launch, per split
    assert launches.get("stored", 0) + launches.get(
        "stored_batch", 0) >= 1, launches
    assert not set(launches) & {"fused", "fused_batch", "filter",
                                "resident_read"}, launches
    width = 45 if st.template == "q1" else 29
    splits = attempt["resident_splits_scanned"]
    assert splits >= 1
    assert attempt["resident_bytes_scanned"] == \
        splits * (1 << 17) * width
    metrics = srv.metrics()
    # the serial path's /metrics: the last statement's gauges, the
    # catalogs' lifetime totals
    assert metrics["resident_bytes_scanned"] == \
        attempt["resident_bytes_scanned"]
    assert metrics["resident_splits_scanned"] == splits
    assert metrics["resident_table_bytes"] == TABLE_BYTES
    assert metrics["resident_loads"] == 1
    assert metrics["resident_load_wall_us"] > 0


def test_the_load_is_a_span_of_the_statement_that_touched_first(served):
    srv, _want = served
    client = srv.client("tpch")
    client.session_properties.update(CHIP_PATH)
    srv.catalogs["tpch"].drop_cache()
    assert srv.metrics()["resident_table_bytes"] == 0
    res = client.execute(STATEMENTS["q6_sf10#0"].sql)
    loads = [sp for sp in _spans(srv.query_info(res.query_id))
             if sp["kind"] == "resident_load"]
    assert [sp["name"] for sp in loads] == ["lineitem"]
    assert loads[0]["attrs"] == {"columns": 16, "slots": SLOTS,
                                 "bytes": TABLE_BYTES}
    res = client.execute(STATEMENTS["q6_sf10#1"].sql)
    assert not [sp for sp in _spans(srv.query_info(res.query_id))
                if sp["kind"] == "resident_load"]
    assert srv.metrics()["resident_table_bytes"] == TABLE_BYTES


def test_the_counters_are_declared_where_every_surface_reads_them():
    kinds = {name: QUERY_COUNTERS[name][0] for name in (
        "resident_table_bytes", "resident_loads",
        "resident_load_wall_us", "resident_bytes_scanned",
        "resident_splits_scanned")}
    assert kinds == {
        "resident_table_bytes": "gauge", "resident_loads": "counter",
        "resident_load_wall_us": "counter",
        "resident_bytes_scanned": "gauge",
        "resident_splits_scanned": "gauge"}
    # per-attempt counts sum over the concurrent path's executors; the
    # catalogs' totals are every executor's to read and are not summed
    assert {"resident_bytes_scanned", "resident_splits_scanned"} <= set(
        QueryManager._EXEC_TOTAL_SUMS)
    assert not {"resident_table_bytes", "resident_loads",
                "resident_load_wall_us"} & set(
        QueryManager._EXEC_TOTAL_SUMS)
