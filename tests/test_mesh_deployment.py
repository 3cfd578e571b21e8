"""The tpch_mesh4 deployment (ISSUE 29) on four of conftest's virtual
devices, through the path the benchmark's cell takes:
server_from_etc(mesh=make_mesh(4)) -> /v1/statement -> planner ->
add_exchanges -> DistExecutor, checked against the benchmark's plain
references (sqlite over the connector's rows) and, for what they do not
cover, against the one-device runner."""

import pytest

from benchmarks.harness import manifest, reference, serve
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.dist.executor import make_mesh
from presto_tpu.runner import LocalRunner

CELL = manifest.load_cell("mesh4_join_solo")
STATEMENTS = {st.key: st for st in CELL.every}
SF = 0.005


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The cell's configuration as served, every scale factor 0.01, over
    a four-device mesh; the references' answers beside it."""
    etc = str(tmp_path_factory.mktemp("mesh4") / "etc")
    props = serve.write_etc(etc, CELL.config, rehearse=True)
    srv = serve.Served(etc, CELL.chips)
    want = reference.answers(
        CELL.every, srv.catalogs, props,
        str(tmp_path_factory.mktemp("answers")), log=lambda **kw: None)
    yield srv, want
    srv.stop()


# with the deployment's own exchange decisions (at SF0.01 Q3's partial
# states are gathered), and with every group-by repartitioned, which is
# what SF1 takes on the chip: all_to_all, shard-local final aggregation,
# top-N on every chip below the gather
@pytest.mark.parametrize("gather_capacity", [None, 16],
                         ids=["as_deployed", "repartitioned"])
@pytest.mark.parametrize("key", sorted(STATEMENTS))
def test_mesh_statement_equals_the_plain_reference(
        key, gather_capacity, served):
    srv, want = served
    st = STATEMENTS[key]
    client = srv.client(st.catalog)
    client.session_properties["query_trace_enabled"] = "true"
    if gather_capacity is not None:
        client.session_properties["agg_gather_capacity"] = str(
            gather_capacity)
    res = client.execute(st.sql)
    got = reference.engine_encoding(res.columns, res.rows)
    assert want[key], "the reference has no row: nothing is compared"
    assert reference.mismatch(got, want[key]) == ""
    info = srv.query_info(res.query_id)
    attempts = [sp for sp in _spans(info) if sp["kind"] == "attempt"]
    launches = attempts[-1]["attrs"]["launches"]
    assert launches.get("d_scan", 0) >= 1, launches
    if gather_capacity is not None:
        assert launches.get("d_repartition", 0) >= 1, launches
        assert attempts[-1]["attrs"]["exchange_launches"] >= 2
        if st.template == "q3":
            assert launches.get("d_topn_local", 0) >= 1, launches
    metrics = srv.metrics()
    assert metrics["exchange_launches"] >= 1
    assert metrics["device_launches"] > metrics["exchange_launches"]


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


# ------------------------------------------------- runners, one and four
@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def single(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13)


@pytest.fixture(scope="module")
def mesh4(conn):
    runner = LocalRunner(
        {"tpch": conn}, page_rows=1 << 13, mesh=make_mesh(4),
        dist_options=dict(broadcast_rows=64, gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner


@pytest.mark.parametrize("limit", [7, 40])
def test_topn_whose_keys_tie_across_chips(limit, single, mesh4):
    """Suppliers by their number of lineitems: many groups share a
    count, and the groups of one count lie on different chips. Any
    `limit` rows that are a correct top-N will do: the sort keys equal
    the reference's, and every row is a row of the full answer."""
    full = single.execute(
        "select l_suppkey, count(*) from lineitem group by l_suppkey"
    ).rows
    counts = sorted((c for _k, c in full), reverse=True)
    assert counts[limit - 1] == counts[limit], "no tie at the cut"
    got = mesh4.execute(
        "select l_suppkey, count(*) c from lineitem group by l_suppkey "
        f"order by c desc limit {limit}").rows
    assert [c for _k, c in got] == counts[:limit]
    assert len({k for k, _c in got}) == limit
    assert set(got) <= set(full)
    launches = _last_attempt(mesh4)["launches"]
    assert launches["d_topn_local"] >= 1 and launches["d_gather"] >= 1
    assert launches["topn_local"] == 1  # the replicated final stage


def test_topn_over_a_sharded_scan_merges_page_after_page(single, mesh4):
    """Several scan rounds: every chip merges its running top-N with
    each page's (d_topn_merge), and ships `limit` rows once."""
    sql = ("select l_orderkey, l_linenumber, l_extendedprice "
           "from lineitem order by l_extendedprice desc, l_orderkey, "
           "l_linenumber limit 12")
    assert mesh4.execute(sql).rows == single.execute(sql).rows
    launches = _last_attempt(mesh4)["launches"]
    assert launches["d_scan"] >= 2
    assert launches["d_topn_local"] == launches["d_scan"]
    assert launches["d_topn_merge"] == launches["d_scan"] - 1
    assert launches["d_gather"] == 1


def _last_attempt(runner):
    return [sp for sp in runner.last_trace.spans()
            if sp.kind == "attempt"][-1].attrs


def _attempts(runner):
    return [sp.attrs for sp in runner.last_trace.spans()
            if sp.kind == "attempt"]


def test_mesh_aggregation_is_sized_by_the_one_rule(single, mesh4):
    """More groups than the first attempt's size: the shard-local final
    aggregation (1/D of the rule's capacity a chip) flags overflow
    through its psum, the statement re-enters boosted and equals the
    one-device answer; the sizing stands on the attempt spans."""
    sql = ("select l_orderkey, count(*), sum(l_quantity) from lineitem "
           "group by l_orderkey")
    want = sorted(single.execute(sql).rows)
    assert len(want) > 4096  # groups, against 4096 optimistic slots
    mesh4.session.set("agg_optimistic_rows", 4096)
    try:
        got = sorted(mesh4.execute(sql).rows)
        attempts = _attempts(mesh4)
    finally:
        mesh4.session.set("agg_optimistic_rows", 1 << 18)
    assert got == want
    assert [a["outcome"] for a in attempts] == ["overflow", "ok"]
    first, second = attempts
    assert (first["boost"], first["agg_sized_by"]) == (1, "optimistic")
    assert first["agg_cap"] == 4096 and first["agg_parts"] == 1
    assert second["boost"] > 1 and second["agg_sized_by"] == "boost"
    assert second["agg_cap"] > first["agg_cap"]
    assert mesh4.executor.capacity_boost_retries >= 1
    # with the default optimistic size the first attempt holds, and a
    # chip's final capacity is its share of it, not the planner's bound
    mesh4.execute(sql)
    (only,) = _attempts(mesh4)
    assert only["outcome"] == "ok" and only["boost"] == 1
