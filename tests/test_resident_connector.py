"""Tables that are stored, not generated (ISSUE 33): the resident
connector (connectors/cached.py) behind a catalog of etc/, read by the
executor's fused scan step as program ARGUMENTS, one copy a table
whatever the statements' columns, constraints and page sizes, loaded
once however many threads touch it first, known to the memory governor,
and traced."""

import gc
import os
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, reference
from benchmarks.harness.scanbytes import column_bytes
from presto_tpu import types as T
from presto_tpu.config import load_catalogs
from presto_tpu.connectors import cached
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import membudget as MB
from presto_tpu.exec.programs import PROGRAM_LABELS
from presto_tpu.runner import LocalRunner

SF = 0.01
PAGE_ROWS = 4096
CELL = manifest.load_cell("scan_sf10_resident_solo")
STATEMENTS = {st.key: st for st in CELL.every}
# a page-emitting chain: filter + project, no aggregation
CHAIN = ("select l_orderkey, l_extendedprice * (1 - l_discount) "
         "from lineitem where l_quantity < 3 "
         "and l_shipdate < date '1993-01-01'")
DRIVERS = {"one_split": "auto", "batched": 8}


def _runner(conn, split_batch="auto", **kw):
    runner = LocalRunner({"tpch": conn}, default_catalog="tpch",
                         page_rows=PAGE_ROWS, **kw)
    runner.session.set("fused_partial_agg_enabled", "true")
    runner.session.set("split_batch_size", split_batch)
    runner.session.set("query_trace_enabled", True)
    return runner


def _resident():
    return ResidentConnector(TpchConnector(SF), tables=["lineitem"])


def _attempt(runner):
    return [sp for sp in runner.last_trace.spans()
            if sp.kind == "attempt"][-1]


@pytest.fixture(scope="module")
def generated():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def want(generated, tmp_path_factory):
    """The benchmark's plain references over the GENERATOR's rows."""
    return reference.answers(
        CELL.every, {"tpch": generated}, {"tpch": {"sf": SF}},
        str(tmp_path_factory.mktemp("answers")), log=lambda **kw: None)


# ------------------------------------- the same answers, the same driver
@pytest.mark.parametrize("driver", sorted(DRIVERS))
@pytest.mark.parametrize("key", sorted(STATEMENTS) + ["chain"])
def test_resident_scan_equals_generated_scan_and_reference(
        key, driver, generated, want):
    """Q1, Q6 and a page-emitting chain over resident(tpch) give the
    rows of tpch and of the benchmark's references, through the fused
    driver: the launches and splits of the generated statement, its
    programs under the stored source's labels, and no per-page filter
    or project program."""
    sql = CHAIN if key == "chain" else STATEMENTS[key].sql
    runs = {}
    for name, conn in (("generated", generated),
                       ("resident", _resident())):
        runner = _runner(conn, DRIVERS[driver])
        res = runner.execute(sql)
        ex = runner.executor
        runs[name] = (res, ex.program_launches, ex.splits_scanned,
                      _attempt(runner).attrs)
    (gen, g_launches, g_splits, g_attrs), \
        (res, r_launches, r_splits, r_attrs) = \
        runs["generated"], runs["resident"]
    assert sorted(res.rows) == sorted(gen.rows) and res.rows
    if key != "chain":
        # a runner's rows are in the engine's encoding already
        # (unscaled decimals, epoch days), as the references' are
        assert reference.mismatch(res.rows, want[key]) == ""
    assert (r_launches, r_splits) == (g_launches, g_splits)
    assert r_splits == 26 and r_launches == (
        26 if driver == "one_split" else 4)
    # launch for launch the generated statement's programs, the scan
    # step under the stored source's labels: no per-page filter or
    # project program, no generator (but the load's, in this, the
    # table's first statement: one page of all columns, written once)
    g_by, r_by = g_attrs["launches"], dict(r_attrs["launches"])
    assert (r_by.pop("scan_gen"), r_by.pop("resident_store")) == (1, 1)
    names = {"stored": "fused", "stored_batch": "fused_batch"}
    assert {names.get(lab, lab): n for lab, n in r_by.items()} == g_by
    assert sum(r_by.get(lab, 0) for lab in names) == r_launches
    if driver == "batched":
        assert r_by["stored_batch"] == 4
    # what the launches were handed: every real split's slice of the
    # touched columns and the validity
    assert r_attrs["resident_splits_scanned"] == r_splits
    assert g_attrs["resident_splits_scanned"] == 0
    assert g_attrs["resident_bytes_scanned"] == 0


# --------------------------------------------------- one copy, one load
def _schema_bytes(conn, table):
    schema = conn.table_schema(table)
    return sum(column_bytes(str(c.type)) for c in schema.columns) + 1


def test_one_copy_whatever_the_columns_constraints_and_page_sizes():
    """Both variants of Q1 (7 columns, two l_shipdate bounds) and of Q6
    (4 columns, two date ranges), then other page sizes and a scan
    through pages(): one load, and the store holds slots x the
    schema's widths + validity, unchanged by the statements."""
    conn = _resident()
    runner = _runner(conn)
    assert runner.executor.resident_table_bytes == 0
    sizes = []
    for key in sorted(STATEMENTS):
        runner.execute(STATEMENTS[key].sql)
        sizes.append(runner.executor.resident_table_bytes)
    slots = conn.row_count("lineitem")
    assert slots == 15000 * 7
    pad = 1 << 17   # the table's own bucket: no split is longer
    assert sizes == [(slots + pad) * _schema_bytes(conn, "lineitem")] * 4
    assert _schema_bytes(conn, "lineitem") == 93
    assert (conn.resident_loads, runner.executor.resident_loads) == (1, 1)
    assert runner.executor.resident_load_wall_us == \
        conn.resident_load_wall_us > 0
    # another page size, a pushed constraint, a column subset through
    # pages(): the same copy
    other = LocalRunner({"tpch": conn}, default_catalog="tpch",
                        page_rows=1 << 14)
    other.execute(STATEMENTS["q6_sf10#0"].sql)
    rows = sum(int(p.num_rows()) for p in conn.pages(
        "lineitem", ["l_orderkey"], target_rows=5000,
        constraint=(("l_orderkey", 1, 4000),)))
    assert 0 < rows < 60175
    assert conn.resident_loads == 1
    assert conn.resident_table_bytes == sizes[0]
    # tables that are not named are generated as ever, and cost nothing
    assert runner.execute("select count(*) from orders").rows == [(15000,)]
    assert conn.resident_table_bytes == sizes[0]


def test_pages_of_a_resident_table_are_the_generators_pages(generated):
    conn = _resident()
    for columns in (None, ["l_shipdate", "l_extendedprice", "l_comment"]):
        got = list(conn.pages("lineitem", columns, target_rows=30000))
        ref = list(generated.pages("lineitem", columns,
                                   target_rows=30000))
        assert len(got) == len(ref) == 4
        for g, r in zip(got, ref):
            assert g.capacity == r.capacity
            valid = np.asarray(r.valid)
            assert (np.asarray(g.valid) == valid).all()
            for gb, rb in zip(g.blocks, r.blocks):
                assert gb.type == rb.type
                assert gb.dictionary == rb.dictionary
                assert gb.data.dtype == rb.data.dtype
                assert (np.asarray(gb.data)[valid]
                        == np.asarray(rb.data)[valid]).all()


def test_four_threads_prewarming_four_statements_load_once():
    """The benchmark's compile phase: one catalog map, a runner and a
    thread a statement, all at once (harness/serve._run_all)."""
    catalogs = {"tpch": _resident()}
    failures = []

    def prewarm(st):
        try:
            runner = LocalRunner(catalogs, default_catalog="tpch",
                                 page_rows=PAGE_ROWS)
            runner.session.set("query_trace_enabled", True)
            runner.prewarm(st.sql)
        except BaseException as e:  # noqa: BLE001 - asserted below
            failures.append((st.key, e))

    threads = [threading.Thread(target=prewarm, args=(st,))
               for st in CELL.every]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, failures
    assert catalogs["tpch"].resident_loads == 1


# ------------------------------------------- arguments, never constants
def test_stored_columns_are_parameters_of_the_lowered_program():
    """The batched stored-source program of Q6: its lowered text takes
    the four touched columns (64-bit ones as uint32[2, slots]) and the
    validity as parameters of the table's size, and holds no constant
    of that size."""
    conn = _resident()
    runner = _runner(conn, 8)
    runner.execute(STATEMENTS["q6_sf10#0"].sql)
    ex = runner.executor
    programs = {k[0]: p for k, p in ex._jit_cache.items()
                if k[0] in ("stored", "stored_batch")}
    assert set(programs) == {"stored_batch"}   # 26 splits: 8, 8, 8, 2
    src = conn.stored_source(
        "lineitem", ("l_quantity", "l_extendedprice", "l_discount",
                     "l_shipdate"))
    slots = conn.row_count("lineitem") + (1 << 17)
    assert [(x.shape, str(x.dtype)) for x in
            jax.tree_util.tree_leaves(src.args)] == [
        ((2, slots), "uint32")] * 3 + [((slots,), "int32"),
                                       ((slots,), "bool")]
    assert src.slot_bytes == 3 * 8 + 4 + 1
    starts = jnp.zeros(8, jnp.int64)
    text = programs["stored_batch"].jitted.lower(
        *src.args, starts, starts).as_text()
    main = text[text.index("func.func public @main"):]
    signature = main[:main.index("{\n")]
    assert signature.count(f"tensor<2x{slots}xui32>") == 3
    assert signature.count(f"tensor<{slots}xi32>") == 1
    assert signature.count(f"tensor<{slots}xi1>") == 1
    # (the slice's index clamp is a scalar constant of that VALUE)
    constants = [line for line in text.splitlines()
                 if "stablehlo.constant" in line
                 and f"{slots}x" in line.split(":")[-1]]
    assert not constants, constants[:2]
    assert len(text) < 200_000
    # every program over the store has a label of its own, family scan
    for label in ("stored", "stored_batch", "resident_store",
                  "resident_read"):
        assert PROGRAM_LABELS[label] == "scan"


# ------------------------------------------------------------ the budget
def test_the_budget_is_smaller_by_what_is_resident():
    conn = _resident()
    runner = _runner(conn, 8)
    ex = runner.executor
    assert ex._budget() == MB.resolve_budget(0) == MB.CPU_BUDGET
    runner.execute(CHAIN)
    held = conn.resident_table_bytes
    assert held > 0 and ex._budget() == MB.CPU_BUDGET - held
    # an explicit budget is held to what is left
    assert MB.resolve_budget(1 << 20, resident=held) == 1 << 20
    assert MB.resolve_budget(1 << 40, resident=held) == \
        MB.CPU_BUDGET - held
    assert MB.resolve_budget(1 << 40) == 1 << 40
    # _split_batch_max plans the stacked batch with it: a budget that
    # the resident bytes leave 8 pages' share of allows a batch of 1
    row_b = 64
    share = PAGE_ROWS * row_b * MB.SCAN_SHARE_DIV
    ex.device_memory_budget = 8 * share
    assert ex._split_batch_max(PAGE_ROWS, False, row_b) == 8
    ex.device_memory_budget = 0
    ex._budget_resolved = None
    real = MB.CPU_BUDGET
    try:
        MB.CPU_BUDGET = held + 2 * share
        assert ex._budget() == 2 * share
        assert ex._split_batch_max(PAGE_ROWS, False, row_b) == 2
    finally:
        MB.CPU_BUDGET = real


def test_a_table_larger_than_the_budget_fails_at_load(monkeypatch):
    monkeypatch.setattr(MB, "CPU_BUDGET", 1 << 20)
    conn = _resident()
    runner = _runner(conn)
    with pytest.raises(MemoryError) as e:
        runner.execute(STATEMENTS["q6_sf10#0"].sql)
    msg = str(e.value)
    assert "'lineitem'" in msg and "105000 slots" in msg
    assert str(1 << 20) in msg
    assert conn.resident_table_bytes == 0 and conn.resident_loads == 0


# ------------------------------------------------------------------ etc/
def _write_catalog(tmp_path, **props):
    os.makedirs(tmp_path / "catalog", exist_ok=True)
    with open(tmp_path / "catalog" / "tpch.properties", "w") as f:
        f.writelines(f"{k}={v}\n" for k, v in props.items())
    return str(tmp_path)


def test_load_catalogs_builds_the_resident_catalog(tmp_path):
    etc = _write_catalog(tmp_path, **{
        "connector.name": "resident", "resident.inner": "tpch",
        "tpch.scale-factor": "0.01", "resident.tables": "lineitem"})
    conn = load_catalogs(etc)["tpch"]
    assert isinstance(conn, ResidentConnector)
    assert isinstance(conn._inner, TpchConnector)
    assert conn.row_count("lineitem") == 105000     # the inner's scale
    assert conn._tables == {"lineitem"}
    assert conn.gen_body("lineitem", 8, ("l_orderkey",)) is None
    assert conn.gen_body("orders", 8, ("o_orderkey",)) is not None
    assert conn.gen_at("orders", ("o_orderdate",)) is not None


@pytest.mark.parametrize("tables", ["*", " * ", None])
def test_star_and_absent_both_store_every_table(tmp_path, tables):
    """``resident.tables=*`` (ISSUE 44) is the explicit spelling of
    "every table, each at its first scan"; nothing loads before one."""
    props = {} if tables is None else {"resident.tables": tables}
    etc = _write_catalog(tmp_path, **{
        "connector.name": "resident", "resident.inner": "tpch",
        "tpch.scale-factor": "0.01"}, **props)
    conn = load_catalogs(etc)["tpch"]
    assert isinstance(conn, ResidentConnector) and conn._tables is None
    assert all(conn.stores(t) for t in conn.tables())
    assert conn.resident_loads == 0 and not conn._store
    assert conn.gen_body("orders", 8, ("o_orderkey",)) is None
    assert conn.gen_at("orders", ("o_orderdate",)) is None
    assert conn.key_inverse("orders", "o_orderkey") is None


@pytest.mark.parametrize("props, what", [
    ({"resident.inner": "tpch", "resident.tables": "*,lineitem"},
     "* stands for every table and is given alone"),
    ({"resident.inner": "tpch", "resident.tables": "lineitem, *"},
     "* stands for every table and is given alone"),
    ({"resident.inner": "nosuch"}, "unknown resident.inner 'nosuch'"),
    ({"resident.inner": "resident"}, "unknown resident.inner"),
    ({}, "unknown resident.inner ''"),
    ({"resident.inner": "tpch", "resident.tables": "lineitem,linitem"},
     "resident.tables names ['linitem']"),
])
def test_load_catalogs_raises_on_an_unknown_inner_or_table(
        tmp_path, props, what):
    etc = _write_catalog(tmp_path, **{"connector.name": "resident",
                                      "tpch.scale-factor": "0.01"},
                         **props)
    with pytest.raises(ValueError) as e:
        load_catalogs(etc)
    assert what in str(e.value)


# ---------------------------------------------------------------- writes
def test_a_write_moves_the_snapshot_and_frees_the_stale_copy():
    inner = MemoryConnector()
    inner.create_table("t", ["a", "s"], [T.BIGINT, T.VARCHAR],
                       [(i, "xy"[i % 2]) for i in range(100)])
    conn = ResidentConnector(inner)
    runner = LocalRunner({"mem": conn}, default_catalog="mem")
    assert runner.execute("select sum(a), count(s) from t").rows == \
        [(4950, 100)]
    first = conn._store["t"]
    held = conn.resident_table_bytes
    assert held == (100 + 128) * (8 + 4 + 1) and conn.resident_loads == 1
    inner.insert("t", [(1000, None)])    # THROUGH the wrapper's inner
    assert runner.execute("select sum(a), count(s) from t").rows == \
        [(5950, 100)]
    assert conn.resident_loads == 2
    assert conn._store["t"] is not first
    assert first.snapshot != conn._store["t"].snapshot
    assert list(conn._store) == ["t"]       # the stale copy is gone
    # ... and freed: nothing holds its buffers, not the executor's
    # cached stored-source program either (it closes over how a split
    # is read, never over the source's arguments)
    assert any(k[0] == "stored" for k in runner.executor._jit_cache)
    stale = [weakref.ref(x) for x in jax.tree_util.tree_leaves(first.page)]
    del first
    gc.collect()
    assert [r() for r in stale] == [None] * 3
    # the NULL gave the string column a null mask: 1 B a slot more
    assert conn.resident_table_bytes == (101 + 128) * (8 + 4 + 1 + 1)
    # the runner's own write path frees the copy at once
    runner.execute("insert into t select 7, 'x'")
    assert conn.resident_table_bytes == 0
    assert runner.execute("select count(*) from t").rows == [(102,)]
    assert conn.resident_loads == 3


def test_a_connector_without_a_snapshot_is_never_stored():
    class NoToken(MemoryConnector):
        def snapshot_version(self, table):
            return None

    inner = NoToken()
    inner.create_table("t", ["a"], [T.BIGINT], [(1,), (2,)])
    conn = ResidentConnector(inner)
    assert sum(int(p.num_rows()) for p in conn.pages("t")) == 2
    assert conn.stored_source("t", ("a",)) is None
    assert (conn.resident_loads, conn.resident_table_bytes) == (0, 0)


# ------------------------------------------- paths that do not fuse today
def test_a_mesh_scans_a_resident_table_through_pages():
    from presto_tpu.dist.executor import make_mesh

    conn = _resident()
    mesh = LocalRunner({"tpch": conn}, default_catalog="tpch",
                       page_rows=1 << 13, mesh=make_mesh(2))
    single = LocalRunner({"tpch": TpchConnector(SF)},
                         default_catalog="tpch", page_rows=1 << 13)
    sql = STATEMENTS["q1_sf10#0"].sql
    assert mesh.execute(sql).rows == single.execute(sql).rows
    assert conn.resident_loads == 1


def test_a_larger_page_than_the_pad_grows_the_pad_once():
    inner = MemoryConnector()
    inner.create_table("t", ["a"], [T.BIGINT],
                       [(i,) for i in range(1000)])
    conn = ResidentConnector(inner)
    assert sum(int(p.num_rows()) for p in conn.pages(
        "t", target_rows=100)) == 1000
    st = conn._store["t"]
    assert st.pad == 1024   # the table's bucket: covers every page size
    conn._store["t"] = cached.dataclasses.replace(st, pad=128, page=(
        jax.tree_util.tree_map(lambda x: x[..., :1128], st.page)))
    assert sum(int(p.num_rows()) for p in conn.pages(
        "t", target_rows=100)) == 1000
    assert conn._store["t"].pad == 128
    assert sum(int(p.num_rows()) for p in conn.pages(
        "t", target_rows=600)) == 1000
    assert conn._store["t"].pad == 1024 and conn.resident_loads == 1
