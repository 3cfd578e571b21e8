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
# states are gathered), with every group-by repartitioned, which is
# what SF1 takes on the chip: all_to_all, shard-local final aggregation,
# top-N on every chip below the gather, and with that and the scan's
# rounds (pages of 4,096 slots, so that there are several) launched a
# batch at a time, which is what a TPU takes under split_batch_size's
# auto (ISSUE 40)
SESSIONS = {
    "as_deployed": {},
    "repartitioned": {"agg_gather_capacity": "16"},
    "batched": {"agg_gather_capacity": "16", "page_rows": "4096",
                "split_batch_size": "4"},
}


@pytest.mark.parametrize("session", sorted(SESSIONS))
@pytest.mark.parametrize("key", sorted(STATEMENTS))
def test_mesh_statement_equals_the_plain_reference(
        key, session, served):
    srv, want = served
    st = STATEMENTS[key]
    client = srv.client(st.catalog)
    client.session_properties["query_trace_enabled"] = "true"
    client.session_properties.update(SESSIONS[session])
    res = client.execute(st.sql)
    got = reference.engine_encoding(res.columns, res.rows)
    assert want[key], "the reference has no row: nothing is compared"
    assert reference.mismatch(got, want[key]) == ""
    info = srv.query_info(res.query_id)
    attempts = [sp for sp in _spans(info) if sp["kind"] == "attempt"]
    launches = attempts[-1]["attrs"]["launches"]
    # Q3's and Q5's scan chains are one program a round, or a batch
    # of rounds
    rounds = attempts[-1]["attrs"]["mesh_fused_rounds"]
    scans = launches.get("d_fused", 0) + launches.get("d_fused_batch", 0)
    assert scans >= 1, launches
    assert "d_scan" not in launches and "d_genjoin" not in launches
    metrics = srv.metrics()
    if session == "batched":
        assert launches["d_fused_batch"] >= 2 and rounds > 4, launches
        assert rounds - 1 <= attempts[-1]["attrs"][
            "mesh_batched_rounds"] == metrics["mesh_batched_rounds"]
        assert metrics["program_launches"] == scans == -(-rounds // 4)
    else:
        assert rounds == launches["d_fused"]
        assert attempts[-1]["attrs"]["mesh_batched_rounds"] == 0
    if session != "as_deployed":
        assert launches.get("d_repartition", 0) >= 1, launches
        assert attempts[-1]["attrs"]["exchange_launches"] >= 2
        if st.template == "q3":
            assert launches.get("d_topn_local", 0) >= 1, launches
    assert metrics["exchange_launches"] >= 1
    assert metrics["device_launches"] > metrics["exchange_launches"]
    # every row count the query trace kept rode in a launch (ISSUE 37):
    # on the attempt span, on /metrics and in the operator spans
    kept = sum(sp["attrs"]["pages"] for sp in _spans(info)
               if sp["kind"] == "operator")
    assert attempts[-1]["attrs"]["row_counts_eager"] == 0 \
        == metrics["row_counts_eager"]
    assert attempts[-1]["attrs"]["row_counts_launched"] == kept \
        == metrics["row_counts_launched"] > scans


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
    # the planner's projection over the scan makes a chain of two: a
    # round is d_fused (a bare scan stays d_scan:
    # test_a_chain_ends_below_an_exchange)
    rounds = launches["d_fused"]
    assert rounds >= 2 and "d_scan" not in launches
    assert launches["d_topn_local"] == rounds
    assert launches["d_topn_merge"] == rounds - 1
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
    # a scan round is one program: generator, both generated joins,
    # filter and project (ISSUE 32); the one d_project left is the
    # projection above the final aggregation
    rounds = launches["d_fused"]
    assert rounds >= 3, launches
    assert only["mesh_fused_rounds"] == rounds
    assert runner.executor.program_launches == rounds  # not twice
    assert not {"d_scan", "d_genjoin", "d_filter"} & set(launches)
    assert launches.get("d_project", 0) <= 1, launches
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
    assert launches["d_agg_partial"] == launches["d_fused"] >= 3
    assert only["mesh_fused_rounds"] == launches["d_fused"]
    assert not {"d_scan", "d_genjoin", "d_filter"} & set(launches)
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
    assert order.index("d_fused") < order.index("d_stream_compact1") \
        < order.index("d_repartition"), order


def _valid_keys(conn, split):
    import numpy as np

    page = conn.page_for_split(split, ("l_orderkey",))
    return np.asarray(page.block(0).data)[np.asarray(page.valid)]


# ------------------------------------------ one program a scan round
# (ISSUE 32) Where a SHARDED subtree is a chain of Filter / Project /
# build-free generated joins over a generated scan, a round is ONE
# shard_map program (d_fused) that applies the step list the one-chip
# fused stream applies; everything else keeps one program a plan node.
def _per_node(monkeypatch):
    """Today's per-node programs: the fused round never engages."""
    from presto_tpu.dist.executor import DistExecutor

    monkeypatch.setattr(DistExecutor, "_fused_rounds",
                        lambda self, node: None)


def _fused_chains(runner, sql, monkeypatch):
    """Every chain of ``sql`` that ran fused, with the pages it
    yielded a round: [(top node, [page, ...])]."""
    from presto_tpu.dist.executor import DistExecutor

    seen = []
    fused_rounds = DistExecutor._fused_rounds

    def recording(self, node):
        stream = fused_rounds(self, node)
        if stream is None:
            return None
        pages = []
        seen.append((node, pages))

        def tee():
            for page in stream:
                pages.append(page)
                yield page
        return tee()

    monkeypatch.setattr(DistExecutor, "_fused_rounds", recording)
    rows = runner.execute(sql).rows
    monkeypatch.setattr(DistExecutor, "_fused_rounds", fused_rounds)
    return rows, seen


def _assert_pages_equal(got, want):
    import jax
    import numpy as np

    assert len(got) == len(want) >= 2, (len(got), len(want))
    for rnd, (a, b) in enumerate(zip(got, want)):
        assert a.capacity == b.capacity, rnd
        assert np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
        assert len(a.blocks) == len(b.blocks)
        for ch, (x, y) in enumerate(zip(a.blocks, b.blocks)):
            assert (x.type, x.dictionary) == (y.type, y.dictionary)
            xs, ys = jax.tree.leaves(x), jax.tree.leaves(y)
            assert len(xs) == len(ys), (rnd, ch)  # data (+ nulls)
            for u, v in zip(xs, ys):
                # every slot, the masked ones too
                assert np.array_equal(np.asarray(u), np.asarray(v)), (
                    rnd, ch)


@pytest.mark.parametrize("template", ["q3", "q5"])
def test_fused_round_yields_the_per_node_chains_page(
        template, cell_mesh, monkeypatch):
    """Same rounds, same splits, same pages: what d_stream_compact1/2,
    d_agg_partial and the rest consume is slot for slot what the
    per-node chain (d_scan -> d_genjoin... -> d_filter -> d_project)
    yields for the round."""
    runner, want = cell_mesh
    key = min(k for k, st in STATEMENTS.items()
              if st.template == template)
    rows, chains = _fused_chains(runner, STATEMENTS[key].sql,
                                 monkeypatch)
    assert reference.mismatch(rows, want[key]) == ""
    ((top, fused),) = chains  # one scan chain a statement
    ex = runner.executor
    assert ex.mesh_fused_rounds == len(fused)
    _per_node(monkeypatch)
    ex._begin_attempt()
    per_node = list(ex.pages(top))
    assert ex.mesh_fused_rounds == 0
    assert ex.device_launches > 2 * len(per_node)
    _assert_pages_equal(fused, per_node)


@pytest.fixture(scope="module")
def tpcds_mesh():
    from presto_tpu.connectors.tpcds import TpcdsConnector

    conn = TpcdsConnector(0.01)
    runner = LocalRunner(
        {"tpcds": conn}, default_catalog="tpcds", page_rows=1 << 12,
        mesh=make_mesh(4))
    runner.session.set("query_trace_enabled", True)
    return runner


def test_fused_round_keeps_a_windowed_joins_flag(tpcds_mesh,
                                                 monkeypatch):
    """store_sales to store_returns on ticket and item is a WINDOWED
    generated join: its multi-match flag stays a psum'd, deferred
    flag of the round's one program, and the page is the per-node
    chain's."""
    sql = ("select ss_item_sk, ss_ticket_number, ss_quantity, "
           "sr_return_quantity from store_sales join store_returns "
           "on ss_ticket_number = sr_ticket_number "
           "and ss_item_sk = sr_item_sk where ss_quantity > 10")
    rows, chains = _fused_chains(tpcds_mesh, sql, monkeypatch)
    fused_attempt = _last_attempt(tpcds_mesh)
    ((top, fused),) = chains
    rounds = len(fused)
    assert fused_attempt["launches"]["d_fused"] == rounds
    assert fused_attempt["mesh_fused_rounds"] == rounds
    assert "d_genjoin_win" not in fused_attempt["launches"]
    ex = tpcds_mesh.executor
    ex._begin_attempt()
    flags = []
    for _page in ex.pages(top):
        flags = list(ex._pending_overflow)
    assert len(flags) == rounds  # one deferred flag a round
    assert all(f.shape == () and not bool(f) for f in flags)
    _per_node(monkeypatch)
    ex._begin_attempt()
    per_node = list(ex.pages(top))
    assert len(ex._pending_overflow) == rounds
    _assert_pages_equal(fused, per_node)
    assert sorted(tpcds_mesh.execute(sql).rows) == sorted(rows)
    launches = _last_attempt(tpcds_mesh)["launches"]
    assert launches["d_genjoin_win"] == launches["d_scan"] == rounds
    assert _last_attempt(tpcds_mesh)["mesh_fused_rounds"] == 0


def _run_plan(runner, source, names):
    """A hand-built plan on the runner's executor, traced: its rows
    and its last attempt span's attrs."""
    from presto_tpu import obs
    from presto_tpu.exec import plan as P

    trace = obs.maybe_trace(runner.session, sql="a hand-built plan")
    obs.attach(runner.executor, trace)
    try:
        _names, rows = runner.executor.execute(
            P.Output(source=source, names=names))
    finally:
        obs.finalize(runner.executor, trace)
    return rows, [sp for sp in trace.spans()
                  if sp.kind == "attempt"][-1].attrs


def test_a_chain_ends_below_an_exchange(mesh4, single):
    """_scan_chain walks through an Exchange (on one chip it moves
    nothing); over a mesh it moves rows, so a chain that holds one
    keeps today's programs, and the links below it are a chain of
    their own."""
    from presto_tpu.exec import plan as P
    from presto_tpu.expr.ir import InputRef
    from presto_tpu import types as T

    ex = mesh4.executor
    scan = P.TableScan("tpch", "lineitem", ("l_orderkey", "l_quantity"))
    swap = (InputRef(1, T.DecimalType(12, 2)), InputRef(0, T.BIGINT))
    over = P.Project(
        P.Exchange(scan, kind="repartition", keys=(0,)), swap)
    assert ex._scan_chain(over, through_joins=True) is not None
    assert ex._fused_rounds(over) is None
    rows, attempt = _run_plan(
        mesh4, P.Exchange(over, kind="gather"), ("q", "k"))
    assert attempt["mesh_fused_rounds"] == 0
    launches = attempt["launches"]
    # a bare sharded scan has no chain to fuse: d_scan, one a round
    assert launches["d_scan"] == launches["d_repartition"] \
        == launches["d_project"] >= 2 and "d_fused" not in launches
    assert ex.program_launches == launches["d_scan"]
    want = sorted(single.execute(
        "select l_quantity, l_orderkey from lineitem").rows)
    assert sorted(rows) == want
    # the links below the exchange fuse; the one above stays
    under = P.Project(P.Exchange(
        P.Project(scan, (swap[1], swap[0])), kind="repartition",
        keys=(0,)), swap)
    rows, attempt = _run_plan(
        mesh4, P.Exchange(under, kind="gather"), ("q", "k"))
    launches = attempt["launches"]
    assert launches["d_fused"] == launches["d_project"] \
        == attempt["mesh_fused_rounds"] >= 2
    assert "d_scan" not in launches
    assert sorted(rows) == want


@pytest.mark.parametrize("split_batch", ["auto", "4"])
def test_a_host_page_connector_keeps_the_per_node_programs(split_batch):
    """No generator on the device (gen_body is None): the scan stages
    host pages (_scan_staged) and the chain above it runs one program
    a plan node, whatever the batch rule says."""
    from presto_tpu import types as T
    from presto_tpu.connectors.memory import MemoryConnector

    mem = MemoryConnector()
    mem.create_table("t", ("a", "b"), (T.BIGINT, T.BIGINT),
                     [(i, i % 7) for i in range(1000)])
    runner = LocalRunner({"memory": mem}, default_catalog="memory",
                         page_rows=128, mesh=make_mesh(4))
    runner.session.set("query_trace_enabled", True)
    runner.session.set("split_batch_size", split_batch)
    got = runner.execute("select a + b from t where b < 3").rows
    assert sorted(got) == sorted(
        (i + i % 7,) for i in range(1000) if i % 7 < 3)
    attempt = _last_attempt(runner)
    assert attempt["mesh_fused_rounds"] == 0 \
        == attempt["mesh_batched_rounds"]
    assert not {"d_fused", "d_fused_batch"} & set(attempt["launches"])
    assert {"d_filter", "d_project"} & set(attempt["launches"])


def test_a_live_cache_point_in_the_chain_stays_a_boundary(mesh4):
    """The rule _fused_stream has: a chain member that is a live
    result-cache point must stay a pages() boundary; its own miss
    path (inflight) fuses."""
    from presto_tpu.exec import plan as P

    plan = mesh4.plan("select l_orderkey, l_quantity + 1 from lineitem "
                      "where l_quantity < 10")
    ex = mesh4.executor
    top = plan
    while ex._fused_rounds(top) is None:
        (top,) = top.children()
    inner = top.children()[0]
    assert isinstance(inner, (P.Filter, P.Project, P.TableScan))
    try:
        ex._cache_points = {id(inner): ("entry",)}
        assert ex._fused_rounds(top) is None
        ex._cache_inflight = {id(inner)}
        assert ex._fused_rounds(top) is not None
    finally:
        ex._cache_points, ex._cache_inflight = {}, set()


# ------------------------------------- a batch of scan rounds a launch
# (ISSUE 40) A scan of several rounds is launched a batch of rounds at
# a time (d_fused_batch: the one-split body once a split in a
# sequential loop), sized by the rule one chip uses for its splits
# (split_batch_size: auto engages on a TPU only, an integer forces it
# here). Seven rounds of 2,048 slots a chip: batches of 2 + 2 + 2 and
# a lone tail round (d_fused), of 4 + 3, and one of 7.
BATCH_SLOTS = 1 << 11
BATCHES = {2: [2, 2, 2, 1], 4: [4, 3], 16: [7]}


@pytest.fixture(scope="module")
def batch_mesh(conn):
    runner = LocalRunner(
        {"tpch": conn, "tpch_sf1": conn},
        default_catalog=CELL.every[0].catalog, page_rows=BATCH_SLOTS,
        mesh=make_mesh(4), dist_options=dict(gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner


@pytest.fixture(scope="module")
def batch_single(conn):
    return LocalRunner({"tpch": conn, "tpch_sf1": conn},
                       default_catalog=CELL.every[0].catalog,
                       page_rows=BATCH_SLOTS)


def _with_split_batch(runner, size, run):
    runner.session.set("split_batch_size", str(size))
    try:
        return run()
    finally:
        runner.session.set("split_batch_size", "auto")


def _chip_major(pages, chips=4):
    """The rounds' pages as the one page a batch of them is: a chip's
    shard of each round, in scan order, then the next chip's."""
    import jax
    import numpy as np

    def stack(*leaves):
        shards = [np.asarray(x).reshape(chips, -1) for x in leaves]
        return np.concatenate(shards, axis=1).reshape(-1)

    return jax.tree.map(stack, *pages)


@pytest.mark.parametrize("size", sorted(BATCHES))
@pytest.mark.parametrize("template", ["q3", "q5"])
def test_batched_rounds_yield_the_rounds_pages_and_rows(
        template, size, batch_mesh, batch_single, monkeypatch):
    """Same splits on the same chips, same slots: a batch's page is
    its rounds' pages laid side by side a chip, the statement's rows
    are the round-a-launch run's and one device's, and the per-page
    programs above the chain run once a batch."""
    key = min(k for k, st in STATEMENTS.items()
              if st.template == template)
    sql = STATEMENTS[key].sql
    want, ((top, rounds),) = _fused_chains(batch_mesh, sql, monkeypatch)
    plain = _last_attempt(batch_mesh)
    n_rounds = sum(BATCHES[size])
    assert plain["launches"]["d_fused"] == n_rounds == len(rounds)
    assert plain["mesh_batched_rounds"] == 0
    assert "d_fused_batch" not in plain["launches"]
    assert sorted(want) == sorted(batch_single.execute(sql).rows)

    got, ((top_b, batches),) = _with_split_batch(
        batch_mesh, size,
        lambda: _fused_chains(batch_mesh, sql, monkeypatch))
    assert got == want
    only = _last_attempt(batch_mesh)
    launches = only["launches"]
    widths = BATCHES[size]
    many = [w for w in widths if w > 1]
    assert launches["d_fused_batch"] == len(many)
    assert launches.get("d_fused", 0) == len(widths) - len(many)
    assert batch_mesh.executor.program_launches == len(widths)
    assert only["mesh_fused_rounds"] == n_rounds
    assert only["mesh_batched_rounds"] == sum(many)
    assert only["row_counts_eager"] == 0
    assert not {"d_scan", "d_genjoin", "d_filter"} & set(launches)
    # once a batch, not once a round
    if template == "q5":
        assert launches["d_agg_partial"] == len(widths)
    else:
        assert launches["d_stream_compact1"] \
            + launches.get("d_stream_compact2", 0) <= 2 * len(widths)
        assert launches["d_agg_partial"] == 1
    # slot for slot, the masked ones and the tail round's padded
    # splits too (they yield no row)
    assert [p.capacity for p in batches] == [
        w * rounds[0].capacity for w in widths]
    at = 0
    for page, width in zip(batches, widths):
        whole = _chip_major(rounds[at:at + width]) if width > 1 \
            else rounds[at]
        _assert_pages_equal([page, page], [whole, whole])
        at += width
    assert sum(int(p.num_rows()) for p in batches) == sum(
        int(p.num_rows()) for p in rounds)


def test_batching_is_sized_by_one_chips_rule(batch_mesh):
    """B x n slots a chip stay under the row line and the governor's
    scan share a chip, by Executor._split_batch_max with a round's row
    D splits wide; off on a CPU under auto; a scan of one round is a
    round."""
    from presto_tpu.exec import shapes as SH

    ex = batch_mesh.executor
    plan = batch_mesh.plan(STATEMENTS[min(STATEMENTS)].sql)
    top = plan
    while ex._fused_rounds(top) is None:
        (top,) = top.children()[:1]

    def labels(split_batch):
        ex._jit_cache.clear()
        ex.split_batch = split_batch
        try:
            ex._fused_rounds(top)
        finally:
            ex.split_batch = "auto"
        return sorted((k[0], k[3]) if k[0] == "d_fused_batch"
                      else (k[0],) for k in ex._jit_cache)

    assert labels("auto") == [("d_fused",)]
    assert labels(0) == [("d_fused",)]
    assert labels(1) == [("d_fused",)]
    assert labels(4) == [("d_fused_batch", 3), ("d_fused_batch", 4)]
    assert labels(64) == [("d_fused_batch", 7)]
    rows_max = SH.SPLIT_BATCH_ROWS_MAX
    try:
        # the row line is a chip's: 5 rounds of 2,047 slots at most
        SH.SPLIT_BATCH_ROWS_MAX = 5 * BATCH_SLOTS
        assert labels(64) == [("d_fused_batch", 3),
                              ("d_fused_batch", 4)]
    finally:
        SH.SPLIT_BATCH_ROWS_MAX = rows_max
    # the governor's scan share of ONE chip's budget (the mesh's is D
    # chips'): room for two rounds' rows a chip, not eight
    budget = ex.device_memory_budget
    from presto_tpu.exec import membudget as MB
    from presto_tpu.exec.executor import _row_bytes

    row = max(_row_bytes(ex.output_types(top)), _row_bytes(
        ex.output_types(ex._scan_chain(top, through_joins=True)[0])))
    try:
        ex.device_memory_budget = (
            2 * (BATCH_SLOTS - 1) * row * MB.SCAN_SHARE_DIV + 1)
        ex._budget_resolved = None
        assert labels(64) == [("d_fused",), ("d_fused_batch", 2)]
    finally:
        ex.device_memory_budget = budget
        ex._budget_resolved = None
    ex._jit_cache.clear()


def test_a_scan_of_one_round_is_not_a_batch(conn):
    runner = LocalRunner({"tpch": conn}, page_rows=1 << 16,
                         mesh=make_mesh(4))
    runner.session.set("query_trace_enabled", True)
    runner.session.set("split_batch_size", "16")
    sql = "select l_orderkey + 1 from lineitem where l_quantity < 3"
    single = LocalRunner({"tpch": conn}, page_rows=1 << 16)
    assert sorted(runner.execute(sql).rows) == sorted(
        single.execute(sql).rows)
    only = _last_attempt(runner)
    assert only["launches"]["d_fused"] == 1 == only["mesh_fused_rounds"]
    assert only["mesh_batched_rounds"] == 0


def test_no_batched_round_on_one_device(batch_single):
    """One chip batches its splits in its own driver (fused_batch);
    the mesh's counter stays 0 there."""
    sql = STATEMENTS[min(STATEMENTS)].sql
    batch_single.session.set("query_trace_enabled", True)
    try:
        _with_split_batch(batch_single, 4,
                          lambda: batch_single.execute(sql))
        only = _last_attempt(batch_single)
    finally:
        batch_single.session.set("query_trace_enabled", False)
    assert only["launches"]["fused_batch"] >= 1
    assert only["mesh_batched_rounds"] == 0 == only["mesh_fused_rounds"]


def test_no_batched_round_above_an_exchange(mesh4):
    """A chain that holds an Exchange keeps a program a plan node
    whatever the batch rule says; the chain below it batches."""
    from presto_tpu.exec import plan as P
    from presto_tpu.expr.ir import InputRef
    from presto_tpu import types as T

    scan = P.TableScan("tpch", "lineitem", ("l_orderkey", "l_quantity"))
    swap = (InputRef(1, T.DecimalType(12, 2)), InputRef(0, T.BIGINT))
    over = P.Project(
        P.Exchange(scan, kind="repartition", keys=(0,)), swap)
    ex = mesh4.executor
    ex.split_batch = 4  # _run_plan drives the executor, not the session
    try:
        rows, attempt = _run_plan(
            mesh4, P.Exchange(over, kind="gather"), ("q", "k"))
        assert attempt["mesh_batched_rounds"] == 0 \
            == attempt["mesh_fused_rounds"]
        assert attempt["launches"]["d_scan"] >= 2
        under = P.Project(P.Exchange(
            P.Project(scan, (swap[1], swap[0])), kind="repartition",
            keys=(0,)), swap)
        rows_under, attempt = _run_plan(
            mesh4, P.Exchange(under, kind="gather"), ("q", "k"))
    finally:
        ex.split_batch = "auto"
    assert sorted(rows_under) == sorted(rows)
    assert attempt["mesh_batched_rounds"] >= 2
    assert attempt["launches"]["d_fused_batch"] \
        == attempt["launches"]["d_repartition"] >= 1


def test_no_batched_round_through_a_live_cache_point(batch_mesh):
    from presto_tpu.exec import plan as P

    plan = batch_mesh.plan(
        "select l_orderkey, l_quantity + 1 from lineitem "
        "where l_quantity < 10")
    ex = batch_mesh.executor
    top = plan
    while ex._fused_rounds(top) is None:
        (top,) = top.children()
    inner = top.children()[0]
    assert isinstance(inner, (P.Filter, P.Project, P.TableScan))
    ex.split_batch = 4
    try:
        ex._begin_attempt()
        pages = list(ex.pages(top))
        assert ex.mesh_batched_rounds == ex.mesh_fused_rounds > len(pages)
        ex._cache_points = {id(inner): ("entry",)}
        assert ex._fused_rounds(top) is None
    finally:
        ex._cache_points, ex._cache_inflight = {}, set()
        ex.split_batch = "auto"


def test_overflow_above_a_batch_reenters_boosted(
        batch_single, batch_mesh):
    """The compaction of a batch's page drops rows past a chip's share
    of the buffer (2,048 slots of its 8,192 against ≈ 7,500 joined
    rows a chip): its psum'd flag sends the statement to the boosted
    attempt, which answers exactly."""
    sql = ("select l_suppkey, l_linenumber, count(*), sum(l_quantity) "
           "from lineitem join orders on l_orderkey = o_orderkey "
           "group by l_suppkey, l_linenumber")
    want = sorted(batch_single.execute(sql).rows)
    got, attempts = _with_split_batch(
        batch_mesh, 4, lambda: _with_optimistic_rows(
            batch_mesh, ROUND_SLOTS, sql))
    assert sorted(got) == want
    assert [a["outcome"] for a in attempts] == ["overflow", "ok"]
    assert attempts[-1]["mesh_batched_rounds"] \
        == attempts[-1]["mesh_fused_rounds"] >= 4
    assert attempts[-1]["launches"]["d_fused_batch"] >= 2


def test_a_flag_raised_in_a_batchs_second_split_reenters_boosted(
        tpcds_mesh, monkeypatch):
    """A windowed generated join's multi-match flag is OR'd over the
    loop's splits and psum'd once: raised for one row of a chip's
    SECOND split of the first batch alone, it still sends the
    statement to the boosted attempt (the general join), which answers
    what the unflagged statement answers."""
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.exec.executor import Executor

    sql = ("select ss_item_sk, ss_ticket_number, ss_quantity, "
           "sr_return_quantity from store_sales join store_returns "
           "on ss_ticket_number = sr_ticket_number "
           "and ss_item_sk = sr_item_sk where ss_quantity > 10")
    want = sorted(tpcds_mesh.execute(sql).rows)
    conn = tpcds_mesh.executor.catalogs["tpcds"]
    splits = conn.splits("store_sales", target_rows=1 << 12)
    assert len(splits) > 8  # chip 1's second split: index 4 + 1
    page = conn.page_for_split(
        splits[5], ("ss_ticket_number", "ss_item_sk", "ss_quantity"))
    ticket, item, _quantity = next(
        row for row, ok in zip(zip(*(
            np.asarray(page.block(c).data).tolist() for c in range(3))),
            np.asarray(page.valid).tolist()) if ok and row[2] > 10)
    kernel = Executor.generated_join_kernel

    def flagging(node, info):
        kern, windowed = kernel(node, info)
        if not windowed:
            return kern, windowed
        names = list(_scan_columns(node))
        t_ch = names.index("ss_ticket_number")
        i_ch = names.index("ss_item_sk")

        def flagged(pg):
            out, multi = kern(pg)
            return out, multi | jnp.any(
                pg.valid & (pg.block(t_ch).data == ticket)
                & (pg.block(i_ch).data == item))
        return flagged, windowed

    monkeypatch.setattr(Executor, "generated_join_kernel",
                        staticmethod(flagging))
    tpcds_mesh.executor._jit_cache.clear()
    got = _with_split_batch(
        tpcds_mesh, 4, lambda: tpcds_mesh.execute(sql).rows)
    attempts = _attempts(tpcds_mesh)
    tpcds_mesh.executor._jit_cache.clear()
    assert sorted(got) == want
    assert [a["outcome"] for a in attempts] == ["overflow", "ok"]
    assert attempts[-1]["boost"] > 1


def _scan_columns(join):
    from presto_tpu.exec import plan as P

    node = join.left
    while not isinstance(node, P.TableScan):
        (node,) = node.children()[:1]
    return node.columns
