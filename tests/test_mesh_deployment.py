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


# -------------------------------- the compaction before the aggregation
# (ISSUE 30) The cell's own statements over four devices with every
# group-by repartitioned, as SF1 takes on the chip, and pages small
# enough for several scan rounds. Q3's optimistic size is set so that a
# scan round's page (4 x 8,192 slots) is wider than the rule's buffer
# (8,192 slots, 2,048 a chip), which is the shape SF1 has on the chip
# (4 x 262,144 against 262,144).
ROUND_SLOTS = 1 << 13


@pytest.fixture(scope="module")
def cell_mesh(served):
    srv, want = served
    runner = LocalRunner(
        srv.catalogs, default_catalog=CELL.every[0].catalog,
        page_rows=ROUND_SLOTS, mesh=make_mesh(4),
        dist_options=dict(gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner, want


def _with_optimistic_rows(runner, rows, sql):
    runner.session.set("agg_optimistic_rows", rows)
    try:
        return runner.execute(sql).rows, _attempts(runner)
    finally:
        runner.session.set("agg_optimistic_rows", 1 << 18)


@pytest.mark.parametrize("key", sorted(
    k for k, st in STATEMENTS.items() if st.template == "q3"))
def test_mesh_q3_compacts_and_aggregates_once(key, cell_mesh):
    runner, want = cell_mesh
    got, (only,) = _with_optimistic_rows(
        runner, ROUND_SLOTS, STATEMENTS[key].sql)
    assert reference.mismatch(got, want[key]) == ""
    launches = only["launches"]
    rounds = launches["d_scan"]
    assert rounds >= 3, launches
    assert launches["d_stream_compact1"] == rounds
    assert launches["d_stream_compact2"] == rounds - 1
    assert (launches["d_agg_partial"], launches["d_repartition"],
            launches["d_agg_final"]) == (1, 1, 1), launches
    assert only["exchange_launches"] == 2  # one repartition, one gather
    # the rule's buffer as one chip would size it, not 0
    sizing = runner.executor._agg_sizing(_partial_step(runner, key))
    assert only["agg_compact_rows"] == sizing.compact_rows == ROUND_SLOTS
    assert only["agg_sized_by"] == "optimistic"


@pytest.mark.parametrize("key", sorted(
    k for k, st in STATEMENTS.items() if st.template == "q5"))
def test_mesh_q5_bypasses_the_compaction(key, cell_mesh):
    """n_name is a dictionary key: dense group ids cost nothing a sparse
    page, so the rule asks for no buffer and the partial step still
    runs once a scan round."""
    runner, want = cell_mesh
    got = runner.execute(STATEMENTS[key].sql).rows
    assert reference.mismatch(got, want[key]) == ""
    (only,) = _attempts(runner)
    launches = only["launches"]
    assert not [lab for lab in launches if "stream_compact" in lab]
    assert launches["d_agg_partial"] == launches["d_scan"] >= 3
    assert only["agg_compact_rows"] == 0


def _partial_step(runner, key):
    from presto_tpu.exec import plan as P

    todo = [runner.plan(STATEMENTS[key].sql)]
    while todo:
        node = todo.pop()
        if isinstance(node, P.Aggregation) and node.step == "partial":
            return node
        todo.extend(node.children())
    raise AssertionError("no partial aggregation in the plan")


@pytest.fixture(scope="module")
def mesh4_scan_order(conn):
    """Four devices, group-bys repartitioned, and the generated joins
    left where the scan put their rows: a row reaches the aggregation
    on the chip that generated its split."""
    runner = LocalRunner(
        {"tpch": conn}, page_rows=ROUND_SLOTS, mesh=make_mesh(4),
        dist_options=dict(gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner


def test_rows_past_a_chips_share_of_the_buffer_reenter_boosted(
        single, mesh4_scan_order):
    mesh4 = mesh4_scan_order
    """700 groups, so no aggregation capacity is passed; 15,000 joined
    rows a chip against its 2,048 slots of the compaction buffer: the
    compaction's psum'd flag is the overflow, and the boosted attempt
    equals one device."""
    sql = ("select l_suppkey, l_linenumber, count(*), sum(l_quantity) "
           "from lineitem join orders on l_orderkey = o_orderkey "
           "group by l_suppkey, l_linenumber")
    want = sorted(single.execute(sql).rows)
    assert len(want) < 2048
    got, attempts = _with_optimistic_rows(mesh4, ROUND_SLOTS, sql)
    assert sorted(got) == want
    assert [a["outcome"] for a in attempts] == ["overflow", "ok"]
    first, second = attempts
    assert (first["boost"], first["agg_sized_by"]) == (1, "optimistic")
    assert first["agg_cap"] == first["agg_compact_rows"] == ROUND_SLOTS
    assert second["boost"] > 1 and second["agg_sized_by"] == "boost"
    # with the default size a chip's share holds its rows
    got = mesh4.execute(sql).rows
    (only,) = _attempts(mesh4)
    assert sorted(got) == want and only["outcome"] == "ok"
    assert only["agg_compact_rows"] == 1 << 18
    assert only["launches"]["d_stream_compact1"] == 1


@pytest.mark.parametrize("optimistic_rows,outcomes", [
    (ROUND_SLOTS, ["overflow", "ok"]), (1 << 18, ["ok"])],
    ids=["past_the_share", "inside_the_share"])
def test_rows_of_one_chips_splits_alone(
        optimistic_rows, outcomes, conn, single, mesh4_scan_order):
    """Skew: a key range that lies inside the first scan split, so every
    valid row is on chip 0 and the other three compact nothing. Equal to
    one device whether chip 0's rows pass its share of the buffer or
    not."""
    first, second = conn.splits("lineitem", target_rows=ROUND_SLOTS)[:2]
    below = int(min(_valid_keys(conn, second)))
    assert len(_valid_keys(conn, first)) > ROUND_SLOTS // 4
    sql = ("select l_orderkey, count(*), sum(l_quantity) "
           "from lineitem join orders on l_orderkey = o_orderkey "
           f"where l_orderkey < {below} group by l_orderkey")
    want = sorted(single.execute(sql).rows)
    assert sum(n for _k, n, _q in want) == len(_valid_keys(conn, first))
    got, attempts = _with_optimistic_rows(
        mesh4_scan_order, optimistic_rows, sql)
    assert sorted(got) == want
    assert [a["outcome"] for a in attempts] == outcomes
    assert attempts[0]["agg_compact_rows"] > 0
    # labels in the order of their first launch: no row changed chips
    # between the scan and the compaction
    order = list(attempts[-1]["launches"])
    assert order.index("d_scan") < order.index("d_stream_compact1") \
        < order.index("d_repartition"), order


def _valid_keys(conn, split):
    import numpy as np

    page = conn.page_for_split(split, ("l_orderkey",))
    return np.asarray(page.block(0).data)[np.asarray(page.valid)]
