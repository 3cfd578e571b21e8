"""Joins between tables that are stored (ISSUE 44): over a catalog of
``resident.tables=*`` a join's build side is read from the store and
built, once a statement, into a lookup structure on the device
(Executor._stored_build: program ``stored_build``), and its probe is a
step of the fused scan (``stored_probe`` / ``stored_probe_batch``). No
table the catalog stores is generated: not by a scan, not by a join."""

import sqlite3

import pytest

from benchmarks.harness import manifest, reference, serve
from presto_tpu import config
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import executor as EX
from presto_tpu.exec.counters import QUERY_COUNTERS
from presto_tpu.runner import LocalRunner
from presto_tpu.server.http_server import QueryManager

SF = 0.01
PAGE_ROWS = 16384
CELL = manifest.load_cell("join_sf1_resident_solo")
STATEMENTS = {st.key: st for st in CELL.every}
JOINS = {"q3": 2, "q5": 5}
# the joins among them whose key an earlier build carries (ISSUE 45):
# probed inside that build's program, once a build row, with no step of
# their own in the fused scan (Q3: customer on orders; Q5: nation and
# region on supplier, customer on orders)
RIDERS = {"q3": {"orders": ["customer"], "customer": []},
          "q5": {"supplier": ["nation", "region"], "nation": [],
                 "region": [], "orders": ["customer"], "customer": []}}
AT_BUILD = {"q3": 1, "q5": 3}
# stored slots a statement's builds read at SF0.01 (orders 15,000,
# customer 1,500, supplier 100, nation 25, region 5)
BUILD_ROWS = {"q3": 16500, "q5": 16630}
DRIVERS = {"one_split": "auto", "batched": 4}
COUNTERS = ("join_builds", "join_build_rows", "join_build_bytes",
            "join_build_wall_us", "join_probes_at_build")
CHIP_PATH = {"fused_partial_agg_enabled": "true",
             "split_batch_size": "8",
             "query_trace_enabled": "true"}


def _stored():
    """The catalog as etc/ makes it: config.resident() with *."""
    return config._builtin_factories()["resident"]({
        "resident.inner": "tpch", "tpch.scale-factor": str(SF),
        "resident.tables": "*"})


def _runner(conn, split_batch="auto"):
    runner = LocalRunner({"tpch": conn}, default_catalog="tpch",
                         page_rows=PAGE_ROWS)
    runner.session.set("fused_partial_agg_enabled", "true")
    runner.session.set("split_batch_size", split_batch)
    runner.session.set("query_trace_enabled", True)
    return runner


def _attempts(runner):
    return [sp for sp in runner.last_trace.spans()
            if sp.kind == "attempt"]


@pytest.fixture
def step_kinds(monkeypatch):
    """The kinds of every step list Executor._chain_steps makes."""
    seen = []
    orig = EX.Executor._chain_steps

    def spy(self, chain):
        steps = orig(self, chain)
        seen.append([kind for kind, _fn in steps])
        return steps

    monkeypatch.setattr(EX.Executor, "_chain_steps", spy)
    return seen


@pytest.fixture(scope="module")
def generated():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def stored():
    return _stored()


@pytest.fixture(scope="module")
def want(generated, tmp_path_factory):
    """The benchmark's sqlite references over the GENERATOR's rows."""
    return reference.answers(
        CELL.every, {"tpch_sf1": generated}, {"tpch_sf1": {"sf": SF}},
        str(tmp_path_factory.mktemp("answers")), log=lambda **kw: None)


@pytest.fixture(scope="module")
def oracle(generated):
    from tests.oracle import load_sqlite

    return load_sqlite(generated, ["customer", "orders", "lineitem",
                                   "supplier", "nation", "region"])


# ------------------------------------ the cell's statements, both drivers
@pytest.mark.parametrize("driver", sorted(DRIVERS))
@pytest.mark.parametrize("key", sorted(STATEMENTS))
def test_stored_q3_q5_equal_the_oracle_and_the_generated_catalog(
        key, driver, generated, stored, want, step_kinds):
    """Q3 and Q5 (both variants of join_solo.json) over the stored
    schema: the sqlite reference's rows and, row for row, the generated
    catalog's; every join reports a build and none is generated; a
    join whose key an earlier build carries is probed in that build
    and has no step in the scan."""
    st = STATEMENTS[key]
    gen = _runner(generated, DRIVERS[driver]).execute(st.sql)
    runner = _runner(stored, DRIVERS[driver])
    before = runner.executor.generated_joins_used
    del step_kinds[:]
    res = runner.execute(st.sql)
    ex = runner.executor
    assert res.rows == gen.rows and res.rows
    assert reference.mismatch(res.rows, want[key]) == ""
    assert ex.generated_joins_used == before
    assert ex.pallas_joins_used == 0
    assert ex.capacity_boost_retries == 0
    joins = JOINS[st.template]
    assert (ex.join_builds, ex.join_build_rows) == (
        joins, BUILD_ROWS[st.template])
    assert ex.join_probes_at_build == AT_BUILD[st.template]
    (kinds,) = [k for k in step_kinds if "sjoin" in k]
    assert kinds.count("sjoin") == joins - AT_BUILD[st.template]
    assert ex.join_build_bytes > 4 * ex.join_build_rows
    assert ex.join_build_wall_us > 0
    (attempt,) = _attempts(runner)
    launches = attempt.attrs["launches"]
    assert launches["stored_build"] == joins
    probes = launches.get("stored_probe", 0) + launches.get(
        "stored_probe_batch", 0)
    assert probes == ex.program_launches >= 1
    # the probe is a step of the scan: no program a plan node, no
    # materialized join, no generator
    assert not set(launches) & {
        "fused", "fused_batch", "stored", "stored_batch", "filter",
        "join_build", "join_probe", "join_probe_unique", "genjoin",
        "resident_read"}, launches
    assert (attempt.attrs["join_builds"],
            attempt.attrs["join_build_rows"],
            attempt.attrs["join_probes_at_build"]) == (
        joins, BUILD_ROWS[st.template], AT_BUILD[st.template])
    builds = [sp for sp in runner.last_trace.spans()
              if sp.kind == "join_build"]
    assert len(builds) == joins
    assert all(sp.parent_id == attempt.span_id for sp in builds)
    assert sum(sp.attrs["rows"] for sp in builds) == \
        BUILD_ROWS[st.template]
    assert sum(sp.attrs["bytes"] for sp in builds) == ex.join_build_bytes
    assert {sp.attrs["structure"] for sp in builds} == {"direct"}
    assert all(sp.attrs["capacity"] >= 4 * sp.attrs["rows"]
               and sp.attrs["table"] == sp.name for sp in builds)
    assert {sp.name: sp.attrs["riders"] for sp in builds} == \
        RIDERS[st.template]
    # a rider is built before what it rides on, outside its span
    at = {sp.name: sp for sp in builds}
    for table, riders in RIDERS[st.template].items():
        assert all(at[r].t1 <= at[table].t0 for r in riders)


def test_star_loads_only_the_tables_a_statement_scans():
    conn = _stored()
    assert isinstance(conn, ResidentConnector)
    runner = _runner(conn)
    assert conn.resident_loads == 0 and conn.resident_table_bytes == 0
    runner.execute(STATEMENTS["q3_sf1#0"].sql)
    assert sorted(conn._store) == ["customer", "lineitem", "orders"]
    runner.execute(STATEMENTS["q5_sf1#0"].sql)
    assert sorted(conn._store) == ["customer", "lineitem", "nation",
                                   "orders", "region", "supplier"]
    assert conn.resident_loads == 6


def test_a_stored_table_is_never_generated(generated):
    """The one rule of connectors/cached.py: what the catalog stores
    answers none of the generator's five entry points; what it does
    not store answers as the inner connector does."""
    some = ResidentConnector(TpchConnector(SF), tables=["orders"])
    cols = ("o_orderkey", "o_custkey")
    assert some.gen_at("orders", cols) is None
    assert some.key_inverse("orders", "o_orderkey") is None
    assert some.key_window_inverse("orders", "o_orderkey") is None
    assert some.gen_body("orders", 8, cols) is None
    assert some.gen_batch("orders", 8, cols) is None
    assert some.stores("orders") and not some.stores("customer")
    assert some.gen_at("customer", ("c_custkey",)) is not None
    assert some.key_inverse("customer", "c_custkey") is not None
    assert some.gen_body("customer", 8, ("c_custkey",)) is not None
    # a join from a generated table into the stored one is a build, the
    # other way round a generated join
    runner = _runner(some)
    sql = ("select count(*), sum(o_totalprice) from lineitem, orders "
           "where l_orderkey = o_orderkey and l_quantity < 5")
    res = runner.execute(sql)
    assert res.rows == _runner(generated).execute(sql).rows
    assert runner.executor.join_builds == 1


def test_the_launches_of_a_stored_q5_are_bounded(stored):
    """A stored Q5 is a handful of launches: five builds, the scan's
    batches with every probe and the partial aggregation inside, the
    final aggregation and the sort; not one a page and plan node."""
    runner = _runner(stored, split_batch=8)
    runner.execute(STATEMENTS["q5_sf1#0"].sql)     # loads the tables
    runner.execute(STATEMENTS["q5_sf1#0"].sql)
    ex = runner.executor
    (attempt,) = _attempts(runner)
    launches = attempt.attrs["launches"]
    # 105,000 slots in 7 splits of 16,383: one batched launch of 8
    assert ex.splits_scanned == 7
    assert launches["stored_build"] == 5
    assert launches["stored_probe_batch"] == ex.program_launches == 1
    assert ex.device_launches == sum(launches.values()) <= 10, launches


# ------------------------------------------------------------ edge cases
EDGES = {
    # (sql, stored builds expected, boosted retries expected)
    "duplicate_build_keys": (
        "select count(*), sum(s_acctbal), sum(c_acctbal) "
        "from customer, supplier where c_nationkey = s_nationkey",
        0, 0),
    "probe_keys_without_a_match": (
        "select count(*), sum(o_totalprice), min(c_custkey), "
        "max(c_custkey) from orders, customer where o_custkey = "
        "c_custkey and c_custkey between 500 and 620", 1, 0),
    "empty_build": (
        "select count(*), sum(o_totalprice) from orders, customer "
        "where o_custkey = c_custkey and c_mktsegment = 'NOSUCH'",
        1, 0),
    "left_join": (
        "select o_orderkey, o_custkey, c_acctbal from orders left join "
        "customer on o_custkey = c_custkey and c_acctbal > 5000 "
        "where o_orderkey < 2000", 1, 0),
    "filter_and_projection_above_the_build_scan": (
        "select count(*), sum(l_extendedprice), sum(twice), max(day) "
        "from lineitem, (select o_orderkey as k, o_totalprice * 2 as "
        "twice, o_orderdate as day from orders where o_orderdate < "
        "date '1995-01-01' and o_totalprice > 1000) t "
        "where l_orderkey = k and l_quantity < 10", 1, 0),
    "two_key_pairs": (
        "select count(*), sum(l_extendedprice) from lineitem, supplier, "
        "orders, customer where l_suppkey = s_suppkey and l_orderkey = "
        "o_orderkey and o_custkey = c_custkey and c_nationkey = "
        "s_nationkey", 3, 0),
}
# the oracle holds decimals unscaled and dates as epoch days
ORACLE_SQL = {
    "left_join": EDGES["left_join"][0].replace(
        "c_acctbal > 5000", "c_acctbal > 500000"),
    "filter_and_projection_above_the_build_scan":
        EDGES["filter_and_projection_above_the_build_scan"][0].replace(
            "date '1995-01-01'", str(reference.days("1995-01-01"))
        ).replace("o_totalprice > 1000", "o_totalprice > 100000"
                  ).replace("l_quantity < 10", "l_quantity < 1000"),
}


def _oracle_rows(oracle: sqlite3.Connection, name: str):
    sql = ORACLE_SQL.get(name, EDGES[name][0])
    return [tuple(r) for r in oracle.execute(sql).fetchall()]


@pytest.mark.parametrize("name", sorted(EDGES))
def test_edge_case_gives_the_oracles_rows(name, generated, stored,
                                          oracle):
    sql, builds, retries = EDGES[name]
    runner = _runner(stored)
    res = runner.execute(sql)
    ex = runner.executor
    assert sorted(res.rows) == sorted(_runner(generated).execute(sql).rows)
    assert sorted(res.rows, key=repr) == sorted(
        _oracle_rows(oracle, name), key=repr)
    assert (ex.join_builds, ex.capacity_boost_retries) == (
        builds, retries)
    assert ex.generated_joins_used == 0


# ------------------------- a join that rides on an earlier build (ISSUE 45)
_TWO_JOINS = ("select count(*), sum(l_extendedprice), min(c_custkey), "
              "max(c_custkey) from lineitem, orders, customer "
              "where l_orderkey = o_orderkey and o_custkey = c_custkey ")
RIDING = {
    # sql, stored builds, joins probed inside a build, sjoin steps,
    # boosted retries
    "rider_without_a_match_for_some_build_rows": (
        _TWO_JOINS + "and c_custkey between 500 and 620", 2, 1, 1, 0),
    "empty_rider_build": (
        _TWO_JOINS + "and c_mktsegment = 'NOSUCH'", 2, 1, 1, 0),
    "key_pair_whose_probe_side_is_another_builds": (
        EDGES["two_key_pairs"][0], 3, 1, 2, 0),
    "rider_of_a_rider": (
        "select count(*), sum(l_extendedprice), min(r_name) "
        "from lineitem, supplier, nation, region where l_suppkey = "
        "s_suppkey and s_nationkey = n_nationkey and n_regionkey = "
        "r_regionkey and r_name = 'ASIA'", 3, 2, 1, 0),
    "left_join_on_a_carried_key": (
        "select count(*), sum(l_extendedprice), count(c_custkey) "
        "from lineitem join orders on l_orderkey = o_orderkey "
        "left join customer on o_custkey = c_custkey "
        "and c_acctbal > 5000", 2, 0, 2, 0),
    "inner_join_on_a_left_joins_key": (
        "select count(*), sum(l_extendedprice), count(c_custkey) "
        "from lineitem left join orders on l_orderkey = o_orderkey "
        "and o_totalprice > 100000 "
        "join customer on o_custkey = c_custkey", 2, 0, 2, 0),
    "filter_between_the_two_joins": (
        "select count(*), sum(p) from (select l_extendedprice as p, "
        "o_custkey as k from lineitem, orders where l_orderkey = "
        "o_orderkey and l_quantity + o_shippriority < 10) t, customer "
        "where k = c_custkey and c_acctbal > 0", 2, 0, 2, 0),
    # customer keyed by a column that repeats, which the first attempt
    # is told is unique (below): customer rides on supplier, its
    # build's flag joins the ladder, the boosted retry builds nothing
    "rider_with_duplicate_keys": (
        "select count(*), sum(l_extendedprice), sum(c_acctbal) "
        "from lineitem, supplier, customer where l_suppkey = s_suppkey "
        "and s_nationkey = c_nationkey and l_quantity < 3", 0, 0, 1, 1),
}
RIDING_ORACLE_SQL = {
    "left_join_on_a_carried_key":
        RIDING["left_join_on_a_carried_key"][0].replace(
            "c_acctbal > 5000", "c_acctbal > 500000"),
    "inner_join_on_a_left_joins_key":
        RIDING["inner_join_on_a_left_joins_key"][0].replace(
            "o_totalprice > 100000", "o_totalprice > 10000000"),
    "filter_between_the_two_joins":
        RIDING["filter_between_the_two_joins"][0].replace(
            "l_quantity + o_shippriority < 10",
            "l_quantity + 100 * o_shippriority < 1000"),
    "rider_with_duplicate_keys":
        RIDING["rider_with_duplicate_keys"][0].replace(
            "l_quantity < 3", "l_quantity < 300"),
}


@pytest.mark.parametrize("name", sorted(RIDING))
def test_a_join_rides_on_the_build_that_carries_its_key(
        name, generated, stored, oracle, step_kinds, monkeypatch):
    """Executor._ride_stored_joins: an inner stored join whose pivot
    key an earlier inner stored join's build carries, with only such
    joins between them, is probed inside that build's program (a build
    row whose rider finds nothing has no entry, so its probe rows are
    dropped) and has no step; a left join, or a Filter / Project
    between the two, leaves today's steps. The rows are the oracle's
    and the generated catalog's either way."""
    sql, builds, at_build, sjoins, retries = RIDING[name]
    if name == "rider_with_duplicate_keys":
        honest = EX.Executor._scan_column_unique
        monkeypatch.setattr(
            EX.Executor, "_scan_column_unique",
            lambda self, n, ch: self._capacity_boost == 1
            or honest(self, n, ch))
    runner = _runner(stored)
    res = runner.execute(sql)
    ex = runner.executor
    monkeypatch.undo()
    assert sorted(res.rows) == sorted(_runner(generated).execute(sql).rows)
    want = oracle.execute(RIDING_ORACLE_SQL.get(name, sql)).fetchall()
    assert sorted(res.rows, key=repr) == sorted(
        (tuple(r) for r in want), key=repr)
    assert (ex.join_builds, ex.join_probes_at_build,
            ex.capacity_boost_retries) == (builds, at_build, retries)
    assert ex.generated_joins_used == 0
    first = next(k for k in step_kinds if "sjoin" in k)
    assert first.count("sjoin") == sjoins
    attempts = _attempts(runner)
    assert len(attempts) == 1 + retries
    built = {sp.name: sp.attrs["riders"]
             for sp in runner.last_trace.spans()
             if sp.kind == "join_build"}
    if retries:
        assert attempts[0].attrs["outcome"] == "overflow"
        assert built == {"customer": [], "supplier": ["customer"]}
        assert "stored_build" not in attempts[-1].attrs["launches"]
    else:
        assert sum(len(r) for r in built.values()) == at_build


def test_the_audit_counts_a_riders_columns_on_the_build_it_rides(stored):
    """membudget.audit reads the chain as _fused_stream does: the page
    of orders' build holds customer's columns after its own, so its
    line is what join_build's span reports, and a build without a
    rider keeps its own."""
    from presto_tpu.exec import membudget as MB

    runner = _runner(stored)
    sql = STATEMENTS["q5_sf1#0"].sql
    runner.execute(sql)
    spans = {sp.name: sp.attrs["bytes"]
             for sp in runner.last_trace.spans()
             if sp.kind == "join_build"}
    lines = {b.label.split()[3]: b for b in MB.audit(
        runner.executor, runner.plan(sql)).buffers
        if b.label.startswith("stored join build")}
    assert sorted(lines) == sorted(spans) == sorted(RIDERS["q5"])
    for table, buf in lines.items():
        assert buf.rows * buf.row_bytes >= spans[table]
    # orders: 4 B x 65,536 entries, the floor, and 15,000 rows of
    # three columns of its own and customer's two, 8 B each and the
    # validity's two: without the rider's columns 16 B a row fewer
    assert spans["orders"] == 4 * 65536 + 8 + 15000 * (2 + 8 * 5)
    assert spans["customer"] == 4 * 8192 + 8 + 1500 * (2 + 8 * 2)


def test_a_build_over_its_capacity_retries_to_the_same_rows(
        generated, stored, monkeypatch):
    """Order keys use 8 of every 32 values: with one entry a stored
    slot the direct-address table cannot hold them, the build's flag
    joins the deferred ladder, and the boosted retry, where no stored
    join is eligible, takes the materialized join: the same rows."""
    st = STATEMENTS["q3_sf1#0"]
    gen = _runner(generated).execute(st.sql)
    monkeypatch.setattr(EX, "STORED_JOIN_KEY_SPREAD", 1)
    runner = _runner(stored)
    res = runner.execute(st.sql)
    ex = runner.executor
    assert res.rows == gen.rows and res.rows
    assert ex.capacity_boost_retries == 1
    first, second = _attempts(runner)
    assert (first.attrs["outcome"], second.attrs["outcome"]) == (
        "overflow", "ok")
    assert "stored_build" not in second.attrs["launches"]
    assert second.attrs["launches"]["join_build"] == 2
    # the last attempt's gauges: it built nothing
    assert ex.join_builds == 0 and ex.generated_joins_used == 0


# ------------------------------------------------ the deployment, served
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    etc = str(tmp_path_factory.mktemp("resident_join") / "etc")
    props = serve.write_etc(etc, CELL.config, rehearse=True)
    assert props["tpch_sf1"] == {
        "connector.name": "resident", "resident.inner": "tpch",
        "tpch.scale-factor": serve.REHEARSE_SCALE_FACTOR,
        "resident.tables": "*"}
    srv = serve.Served(etc, CELL.chips)
    yield srv
    srv.stop()


def test_the_counters_have_one_meaning_after_two_statements(served):
    """The serial path's /metrics shows the LAST statement's gauges,
    not a sum over statements (the four are per-attempt gauges like
    resident_bytes_scanned; the concurrent path sums its per-query
    executors' over _EXEC_TOTAL_SUMS), once each."""
    client = served.client("tpch_sf1")
    client.session_properties.update(CHIP_PATH)
    seen = {}
    for template, key in (("q5", "q5_sf1#0"), ("q3", "q3_sf1#0")):
        res = client.execute(STATEMENTS[key].sql)
        assert res.state == "FINISHED", res.error
        metrics = served.metrics()
        assert metrics["join_builds"] == JOINS[template]
        assert metrics["join_build_rows"] == BUILD_ROWS[template]
        assert metrics["join_probes_at_build"] == AT_BUILD[template]
        assert metrics["join_build_bytes"] > 0
        assert metrics["join_build_wall_us"] > 0
        assert metrics["generated_joins_used"] == 0
        seen[template] = metrics
        info = served.query_info(res.query_id)
        (execute,) = [p for p in info["phases"]
                      if p["kind"] == "execute"]
        spans = [s for s in execute["spans"] if s["kind"] == "join_build"]
        assert len(spans) == JOINS[template]
        tree = [sp for stage in info["stages"]
                for task in stage["tasks"] for sp in task["spans"]]
        (attempt,) = [sp for sp in tree if sp["kind"] == "attempt"]
        assert attempt["attrs"]["join_probes_at_build"] == \
            AT_BUILD[template]
        assert {sp["name"]: sp["attrs"]["riders"] for sp in tree
                if sp["kind"] == "join_build"} == RIDERS[template]
        assert sum(s["endUs"] - s["startUs"] for s in spans) == \
            pytest.approx(metrics["join_build_wall_us"], abs=25)
    assert seen["q3"]["join_build_bytes"] != seen["q5"]["join_build_bytes"]
    text = serve.http_text(f"{served.url}/metrics")
    for name in COUNTERS:
        assert text.count(f"\npresto_tpu_{name} ") == 1, name
        assert f"# TYPE presto_tpu_{name} gauge" in text
        assert QUERY_COUNTERS[name][0] == "gauge"
    assert set(COUNTERS) <= set(QueryManager._EXEC_TOTAL_SUMS)


def test_the_cell_is_the_join_cell_but_for_where_a_table_comes_from():
    """The controlled pair: join_sf1_solo's traffic file, statements,
    properties and end-to-end metrics; the catalogs alone differ."""
    gen = manifest.load_cell("join_sf1_solo")
    assert CELL.traffic == gen.traffic
    assert [st.sql for st in CELL.every] == [st.sql for st in gen.every]
    assert CELL.config["config_properties"] == \
        gen.config["config_properties"]
    assert [m["name"] for m in CELL.end_to_end] == \
        [m["name"] for m in gen.end_to_end]
    for name, sf in (("tpch", "10"), ("tpch_sf1", "1")):
        assert CELL.config["catalogs"][name] == {
            "connector.name": "resident", "resident.inner": "tpch",
            "tpch.scale-factor": sf, "resident.tables": "*"}
    assert set(gen.config["guarantees"]) < set(CELL.config["guarantees"])
    assert {"join_build_ms_per_query", "join_build_rows_per_query",
            "join_build_device_ms_per_query",
            "join_probe_device_ms_per_query", "join_roofline",
            "scan_roofline"} <= {m["name"] for m in CELL.per_layer}


def _block(data, type_):
    from presto_tpu.page import Block

    return Block(data=data, type=type_, nulls=None, dictionary=None)


@pytest.mark.parametrize("name", ["integer", "date", "varchar_codes",
                                  "bigint", "double", "boolean"])
def test_a_build_holds_its_narrow_integer_columns_wide(name):
    """What a probe gathers from is an ARGUMENT of its program, and the
    TPU compiler keeps a 64-bit argument's halves in vector memory
    where a 32-bit one can stay in HBM (tests/test_chip_compile.py
    holds the compiled step to it): a build's INTEGER, DATE and
    dictionary-code columns are int64 in the build page and their own
    dtype again on the gathered rows, value for value; 64-bit,
    floating and boolean columns pass as they are."""
    import jax.numpy as jnp

    from presto_tpu import types as T

    data, type_, wide = {
        "integer": (jnp.array([-7, 0, 2**31 - 1], jnp.int32),
                    T.INTEGER, True),
        "date": (jnp.array([9204, -1, 0], jnp.int32), T.DATE, True),
        "varchar_codes": (jnp.array([0, 3, 1], jnp.int32),
                          T.VarcharType(), True),
        "bigint": (jnp.array([1, -2**40, 3], jnp.int64), T.BIGINT,
                   False),
        "double": (jnp.array([0.5, -1.0, 2.0], jnp.float64), T.DOUBLE,
                   False),
        "boolean": (jnp.array([True, False, True]), T.BOOLEAN, False),
    }[name]
    blk = _block(data, type_)
    held = EX._carried_wide(blk)
    assert held.data.dtype == (jnp.int64 if wide else data.dtype)
    assert held.type == type_
    back = EX._carried_narrow(held)
    assert back.data.dtype == data.dtype
    assert back.data.tolist() == data.tolist()
    if not wide:
        assert held is blk and back is blk


def test_a_stored_build_page_carries_no_32_bit_integer_column(stored):
    """Q3's build of ``orders`` carries o_orderdate and
    o_shippriority: 32-bit in the store, 64-bit in the build page, and
    the statement's rows keep the columns' own values (the oracle test
    above compares them)."""
    import jax
    import jax.numpy as jnp

    seen = []
    orig = EX.Executor._stored_build

    def spy(self, node, info):
        built = orig(self, node, info)
        seen.append((info.scan.table, [
            x.dtype for x in jax.tree_util.tree_leaves(built[2].blocks)]))
        return built

    runner = LocalRunner({"tpch_sf1": stored},
                         default_catalog="tpch_sf1", page_rows=PAGE_ROWS)
    EX.Executor._stored_build = spy
    try:
        runner.execute(STATEMENTS["q3_sf1#0"].sql)
    finally:
        EX.Executor._stored_build = orig
    # customer rides on orders: built first, probed in orders' build,
    # whose page carries its columns too
    assert [t for t, _ in seen] == ["customer", "orders"]
    for _table, dtypes in seen:
        assert dtypes and all(
            dt == jnp.int64 or not jnp.issubdtype(dt, jnp.integer)
            for dt in dtypes), seen
