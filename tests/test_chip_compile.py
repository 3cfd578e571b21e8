"""Chip-compiler rehearsals: the main path's kernels compiled for a
described (not attached) TPU v5e at real sizes.

The TPU's compiler is installed here and compiles for a topology that
is described, so what it refuses — a Pallas kernel that does not lower
through Mosaic, a program over the device's memory, a collective that
cannot be partitioned — is caught in tier-1, at no chip time. Nothing
runs: these tests say nothing about results or speed (chip_smoke.py
does, on the chip).

The topology is described inside a module-scoped fixture (only the
xdist worker that is handed this file loads the TPU library), and the
persistent compile cache is off around the compiles: an entry written
for a described device cannot be read back without a chip.

Code that asks ``jax.default_backend()`` sees the CPU here, so the
tests compile the jitted kernels themselves and steer the few backend
switches the kernels consult (``ops/agg._MM_BACKEND``, the dist
executor's CPU-only rendezvous fence) to their TPU side.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as PS,
    SingleDeviceSharding,
)

from presto_tpu import types as T
from presto_tpu.exec import shapes as SH
from presto_tpu.page import Block, Page

PAGE_ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_branches(monkeypatch):
    """The TPU side of the backend switches the compiled kernels read."""
    from presto_tpu.ops import agg as A

    monkeypatch.setattr(A, "_MM_BACKEND", True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    """The same pytree of shapes, placed on the described device(s)."""
    return jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, sharding), tree)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _bigint_page(n, sharding, columns=2):
    col = _spec((n,), jnp.int64, sharding)
    return Page(
        blocks=tuple(Block(data=col, type=T.BIGINT, nulls=None,
                           dictionary=None) for _ in range(columns)),
        valid=_spec((n,), jnp.bool_, sharding),
    )


def _find(node, t):
    """The first plan node of type ``t``, depth first."""
    if isinstance(node, t):
        return node
    for c in node.children():
        r = _find(c, t)
        if r is not None:
            return r
    return None


def test_dim_pallas_probe_lowers_through_mosaic(one_chip):
    from presto_tpu.ops import pallas_join as PJ

    layout = PJ.plan_layout(1800)
    assert layout[0] == "dim"
    tables = tuple(_spec((layout[1], 8, 128), jnp.int32, one_chip)
                   for _ in range(4))
    compiled = _compile(
        lambda ph, t: PJ.probe_index(ph, t, layout, interpret=False),
        _spec((100352,), jnp.uint64, one_chip), tables)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qnum", [1, 6])
def test_fused_page_step_compiles(qnum, one_chip, tpu_branches):
    """Filter -> project -> partial aggregation of one 2^20-row
    lineitem page as ONE program (the step __graft_entry__.entry()
    exposes for Q1)."""
    from presto_tpu.connectors.base import Split
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import agg_states as S
    from presto_tpu.exec import plan as P
    from presto_tpu.exec.executor import (
        _partial_agg_page,
        _partial_global_agg,
        _project_page,
    )
    from presto_tpu.expr.eval import evaluate_filter
    from presto_tpu.runner import LocalRunner
    from tests.tpch_queries import QUERIES

    conn = TpchConnector(scale=1.0)
    runner = LocalRunner({"tpch": conn}, page_rows=PAGE_ROWS)
    plan = runner.plan(QUERIES[qnum])

    agg = _find(plan, P.Aggregation)
    while isinstance(agg.source, P.Aggregation):
        agg = agg.source  # the partial step sits under the final one
    filt, scan = _find(plan, P.Filter), _find(plan, P.TableScan)
    in_types = runner.executor._agg_in_types(agg)
    layouts = tuple(tuple(S.state_layout(s.function, t))
                    for s, t in zip(agg.aggregates, in_types))

    def step(page):
        projected = _project_page(
            agg.source.exprs,
            evaluate_filter(filt.predicate, page, jnp))
        if agg.group_channels:
            return _partial_agg_page(
                agg.group_channels, agg.aggregates, layouts, projected,
                8, 64)
        return _partial_global_agg(agg.aggregates, layouts, projected)

    page = jax.eval_shape(lambda: conn.page_for_split(
        Split("lineitem", 0, PAGE_ROWS), scan.columns))
    assert page.capacity == PAGE_ROWS
    _compile(step, _on(page, one_chip))


@pytest.mark.parametrize("groups", [8, 4096])
def test_onehot_matmul_aggregation_compiles(groups, one_chip):
    from presto_tpu.ops import agg as A

    compiled = _compile(
        lambda data, ids: (A._mm_sum_int(data, ids, groups),
                           A._mm_count(ids, groups)),
        _spec((PAGE_ROWS,), jnp.int64, one_chip),
        _spec((PAGE_ROWS,), jnp.int32, one_chip))
    # the n x G one-hot fuses into the dot: it is never a buffer
    assert (compiled.memory_analysis().temp_size_in_bytes
            < PAGE_ROWS * groups)


def test_sort_join_probe_compiles_at_the_build_ceiling(one_chip):
    """searchsorted(method="sort") + gather of one 2^20-row probe page
    against a SAFE_BUFFER_ROWS (2M) hash-sorted unique-key build."""
    from presto_tpu.exec.executor import _probe_join_page_unique

    nb = SH.SAFE_BUFFER_ROWS
    index = (
        (_spec((nb,), jnp.uint64, one_chip),),
        _spec((nb,), jnp.bool_, one_chip),
        _spec((nb,), jnp.uint64, one_chip),
        _spec((nb,), jnp.int32, one_chip),
    )
    _compile(
        lambda page, build, idx: _probe_join_page_unique(
            (0,), (0,), "inner", False, page, build, idx, PAGE_ROWS),
        _bigint_page(PAGE_ROWS, one_chip), _bigint_page(nb, one_chip),
        index)


def test_q3_compacted_aggregation_compiles(one_chip, tpu_branches):
    """Q3 at SF1 as Executor._agg_sizing sizes its first attempt
    (ISSUE 26): a 2^22-slot join-output page (16 splits a launch)
    compacts into the 262,144-row buffer (the merge of two buffers
    is the same program at an eighth of the size: not compiled here),
    and the three-key sorted partial aggregation runs once over the
    dense page at a 262,144 group capacity."""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import agg_states as S
    from presto_tpu.exec import plan as P
    from presto_tpu.exec.executor import (
        _compact_with_flag,
        _partial_agg_page,
    )
    from presto_tpu.runner import LocalRunner
    from tests.tpch_queries import QUERIES

    runner = LocalRunner({"tpch": TpchConnector(scale=1.0)},
                         page_rows=1 << 18)
    ex = runner.executor
    ex.fault_rows = SH.SAFE_BUFFER_ROWS
    ex.device_memory_budget = (16 << 30) * 7 // 8

    agg = _find(runner.plan(QUERIES[3]), P.Aggregation)
    sz = ex._agg_sizing(agg)
    assert (sz.parts, sz.cap, sz.compact_rows) == (1, 1 << 18, 1 << 18)
    types = ex.output_types(agg.source)
    layouts = tuple(
        tuple(S.state_layout(s.function, t))
        for s, t in zip(agg.aggregates, ex._agg_in_types(agg)))

    def page(n):
        return Page(
            blocks=tuple(
                Block(data=_spec((n,), np.dtype(t.numpy_dtype), one_chip),
                      type=t, nulls=None, dictionary=None)
                for t in types),
            valid=_spec((n,), jnp.bool_, one_chip))

    C = sz.compact_rows
    _compile(lambda pg: _compact_with_flag(pg, C),
             page(SH.SPLIT_BATCH_ROWS_MAX))
    _compile(
        lambda pg: _partial_agg_page(
            agg.group_channels, agg.aggregates, layouts, pg, sz.cap, 64),
        page(C))


class _Handed(BaseException):
    """A launch caught before it runs (a BaseException: the batched
    driver's escape to the one-split loop catches Exception)."""


@pytest.mark.parametrize("template", ["q1", "q6"])
def test_stored_scan_batch_takes_the_table_as_arguments(
        template, one_chip, tpu_branches, monkeypatch):
    """ISSUE 33: lineitem at SF10 as the resident store holds it
    (105 M slots and the pad; 64-bit columns as uint32[2, slots])
    handed to the chip's batched fused scan step of Q1 / Q6, 64 splits
    a launch, as ARGUMENTS. It compiles for the chip, and by the
    compiler's own account its temporaries are megabytes: nothing the
    size of a column is made. (A 64-bit argument of a column's size is
    split into its halves at the top of every program that takes it:
    0.85 GB of temporaries and 20 ms a launch, PERF.md, PR 33.)"""
    from benchmarks.harness import manifest
    from presto_tpu.cache.rules import snapshot_of
    from presto_tpu.connectors import cached
    from presto_tpu.connectors.base import Split
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import membudget as MB
    from presto_tpu.exec import programs as PG
    from presto_tpu.runner import LocalRunner

    cell = manifest.load_cell("scan_sf10_resident_solo")
    (st,) = [s for s in cell.every if s.key == f"{template}_sf10#0"]
    inner = TpchConnector(scale=10.0)
    conn = cached.ResidentConnector(inner, tables=["lineitem"])
    slots = inner.row_count("lineitem")
    cap = slots + cached.LOAD_ROWS
    assert slots == 105_000_000
    names = tuple(inner.table_schema("lineitem").column_names())
    piece = jax.eval_shape(lambda: inner.page_for_split(
        Split("lineitem", 0, cached.LOAD_ROWS), names))
    # the store's buffers, described, not made (cached._load_locked)
    page = jax.tree.map(
        lambda x: _spec((2, cap), jnp.uint32, one_chip)
        if x.dtype.itemsize == 8 else _spec((cap,), x.dtype, one_chip),
        piece)
    nbytes = sum(x.dtype.itemsize * x.size for x in jax.tree.leaves(page))
    assert nbytes == cap * 93
    conn._store["lineitem"] = cached._Stored(
        snapshot_of(inner, "lineitem"), page,
        tuple(cached._leaf_dtypes([b]) for b in piece.blocks),
        slots, cached.LOAD_ROWS, nbytes)
    monkeypatch.setattr(MB, "device_hbm_bytes", lambda: 16 << 30)

    def handed(sink, program, *args, **kwargs):
        raise _Handed(program, args)

    monkeypatch.setattr(PG, "launch", handed)
    runner = LocalRunner({"tpch": conn}, default_catalog="tpch",
                         page_rows=1 << 18)
    runner.executor.fault_rows = SH.SAFE_BUFFER_ROWS
    with pytest.raises(_Handed) as caught:
        runner.execute(st.sql)
    program, args = caught.value.args
    assert program.label == "stored_batch"
    assert runner.executor._budget() == \
        (16 << 30) - (16 << 30) // MB.HEADROOM_DIV - nbytes
    *buffers, starts, counts = jax.tree.leaves(args)
    assert starts.shape == counts.shape == (SH.SPLIT_BATCH_MAX,)
    columns = {"q1": 7, "q6": 4}[template]
    assert len(buffers) == columns + 1      # and the validity
    assert all(b.shape[-1] == cap for b in buffers)
    compiled = program.jitted.lower(*_on(args, one_chip)).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > cap * (
        {"q1": 44, "q6": 28}[template] + 1)
    assert memory.temp_size_in_bytes < 64 << 20, memory
    assert memory.output_size_in_bytes < 1 << 20


def test_mesh_q3_compaction_compiles_shard_local(topo, tpu_branches):
    """Q3 at SF1 over four chips (ISSUE 30): a scan round's join output
    (262,144 slots a chip) compacts into each chip's 65,536-slot share
    of the rule's 262,144-row buffer. Shard-local: the only collective
    is the overflow flag's all-reduce. (The merge of two shares is the
    same kernel at half the size: not compiled here.)"""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import plan as P
    from presto_tpu.runner import LocalRunner
    from tests.tpch_queries import QUERIES

    mesh = Mesh(np.array(topo.devices), ("d",))
    runner = LocalRunner({"tpch": TpchConnector(scale=1.0)},
                         page_rows=1 << 18, mesh=mesh)
    ex = runner.executor
    ex.fault_rows = SH.SAFE_BUFFER_ROWS
    ex.device_memory_budget = (16 << 30) * 7 // 8

    agg = _find(runner.plan(QUERIES[3]), P.Aggregation)
    while agg.step != "partial":
        agg = _find(agg.source, P.Aggregation)
    sz = ex._agg_sizing(agg)
    assert (sz.cap, sz.compact_rows) == (1 << 18, 1 << 18)
    share = sz.compact_rows // mesh.devices.size
    ex._stream_compact_fns(agg, sz.compact_rows)
    sharded = NamedSharding(mesh, PS("d"))
    page = Page(
        blocks=tuple(
            Block(data=_spec((4 << 18,), np.dtype(t.numpy_dtype), sharded),
                  type=t, nulls=None, dictionary=None)
            for t in ex.output_types(agg.source)),
        valid=_spec((4 << 18,), jnp.bool_, sharded))
    compiled = ex._jit_cache[
        ("d_stream_compact1", share, "rows")].jitted.lower(
        page).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "all-to-all" not in text and "all-gather" not in text
    # the accumulator's row count rides in the launch (ISSUE 37): a
    # chip's own, sharded like the page, so it adds no collective
    (out_page, _flag), rows = compiled.output_shardings
    assert all(s.spec == PS("d") for s in jax.tree.leaves(out_page))
    assert rows.spec == PS("d")


@pytest.mark.parametrize("rounds", [1, 11], ids=["round", "batch"])
def test_mesh_q3_scan_round_compiles_as_one_shard_local_program(
        rounds, topo, tpu_branches):
    """Q3 at SF1 over four chips (ISSUE 32): a scan round's whole chain
    (the generator of 262,143 lineitem slots a chip, both generated
    joins, filter, project) is ONE program, d_fused, with no
    collective in it (no windowed join: no flag), a sharded page out
    and, sharded like it, each chip's count of the page's rows. On a
    TPU the scan's 11 rounds are one launch (ISSUE 40): d_fused_batch
    at the exact width 11, the rule's 16 being the most, a sequential
    loop over a chip's starts whose page is 11 rounds' slots a chip."""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runner import LocalRunner
    from tests.tpch_queries import QUERIES

    mesh = Mesh(np.array(topo.devices), ("d",))
    runner = LocalRunner({"tpch": TpchConnector(scale=1.0)},
                         page_rows=1 << 18, mesh=mesh)
    ex = runner.executor
    ex.fault_rows = SH.SAFE_BUFFER_ROWS
    ex.device_memory_budget = (16 << 30) * 7 // 8
    if rounds == 1:
        ex.split_batch = 0  # a round a launch: the CPU's auto
    top = runner.plan(QUERIES[3])
    while ex._fused_rounds(top) is None:
        (top,) = top.children()[:1]
    ((key, program),) = ex._jit_cache.items()
    n = (1 << 18) - 1
    assert key[:3] == ("d_fused" if rounds == 1 else "d_fused_batch",
                       top, n)
    assert ex._split_batch_max(n, scanned=False) == (
        0 if rounds == 1 else 16)
    assert rounds == 1 or key[3] == rounds
    assert ex.generated_joins_used == 2
    d = mesh.devices.size
    compiled = program.jitted.lower(_spec(
        (d,) if rounds == 1 else (d, rounds), jnp.int64,
        NamedSharding(mesh, PS("d")))).compile()
    text = compiled.as_text()
    for collective in ("all-reduce", "all-to-all", "all-gather"):
        assert collective not in text, collective
    assert (" while(" in text) == (rounds > 1)
    # the page's row count rides in the launch (ISSUE 37), a chip's
    # own: still no collective (above)
    (out_page, flags), rows = compiled.output_shardings
    assert flags == () and rows.spec == PS("d")
    assert all(s.spec == PS("d") for s in jax.tree.leaves(out_page))
    assert len(out_page.blocks) == len(ex.output_types(top))
    (out_shapes, _flags), _rows = jax.eval_shape(
        program.jitted, _spec((d,) if rounds == 1 else (d, rounds),
                              jnp.int64, NamedSharding(mesh, PS("d"))))
    assert out_shapes.valid.shape == (d * rounds * n,)


def test_four_device_repartition_is_an_all_to_all(topo, tpu_branches):
    from presto_tpu.dist import executor as DX

    mesh = Mesh(np.array(topo.devices), ("d",))
    d = mesh.devices.size
    assert d == 4
    rows = d * (1 << 18)
    out_cap = SH.exchange_partition_cap(rows, d, 1)
    program = DX._ici_program(mesh, (0,), (None,), 0, d, out_cap)
    assert program.label == "d_ici_exchange" and program.exchange
    compiled = program.jitted.lower(
        _bigint_page(rows, NamedSharding(mesh, PS("d")))).compile()
    assert "all-to-all" in compiled.as_text()
    # each device holds its shard, not the whole page
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < rows * (8 + 8 + 1)


@pytest.mark.parametrize("template", ["q3", "q5"])
def test_stored_join_build_and_probe_compile_at_sf1(
        template, one_chip, tpu_branches, monkeypatch):
    """ISSUE 44: the schema at SF1 as the resident store holds it,
    handed to a stored Q3 / Q5 as ARGUMENTS: one ``stored_build`` a
    join over the whole stored build table (orders: 1.5 M slots into a
    direct-address table of 8 M entries), then the chip's batched
    fused scan step over lineitem, 16 splits a launch, with every
    probe inside and the builds as arguments. All of them compile for
    the chip; no 64-bit sort is in a build and no sort at all in the
    probe step, whose temporaries stay far below a table's size.
    ISSUE 45: a join whose key an earlier build carries is probed in
    that build's program (customer in orders'; nation and region in
    supplier's), so the step gathers 7 times a slot for Q5, not 14,
    and 3 times for Q3, not 6."""
    from benchmarks.harness import manifest
    from presto_tpu.cache.rules import snapshot_of
    from presto_tpu.connectors import cached
    from presto_tpu.connectors.base import Split
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import membudget as MB
    from presto_tpu.exec import programs as PG
    from presto_tpu.runner import LocalRunner

    cell = manifest.load_cell("join_sf1_resident_solo")
    (st,) = [s for s in cell.every if s.key == f"{template}_sf1#0"]
    inner = TpchConnector(scale=1.0)
    conn = cached.ResidentConnector(inner)
    tables = {"q3": ("lineitem", "orders", "customer"),
              "q5": ("lineitem", "orders", "customer", "supplier",
                     "nation", "region")}[template]
    held = 0
    for table in tables:
        slots = inner.row_count(table)
        pad = min(cached.LOAD_ROWS, SH.bucket(slots))
        names = tuple(inner.table_schema(table).column_names())
        piece = jax.eval_shape(lambda t=table, n=names, p=pad: (
            inner.page_for_split(Split(t, 0, min(p, 1 << 16)), n)))
        page = jax.tree.map(
            lambda x, c=slots + pad: _spec((2, c), jnp.uint32, one_chip)
            if x.dtype.itemsize == 8 else _spec((c,), x.dtype, one_chip),
            piece)
        nbytes = sum(x.dtype.itemsize * x.size
                     for x in jax.tree.leaves(page))
        held += nbytes
        conn._store[table] = cached._Stored(
            snapshot_of(inner, table), page,
            tuple(cached._leaf_dtypes([b]) for b in piece.blocks),
            slots, pad, nbytes)
    monkeypatch.setattr(MB, "device_hbm_bytes", lambda: 16 << 30)
    built = []

    def handed(sink, program, *args, **kwargs):
        if program.label != "stored_build":
            raise _Handed(program, args)
        compiled = program.jitted.lower(*_on(args, one_chip)).compile()
        built.append((compiled, args))
        return jax.eval_shape(program.jitted, *args)

    monkeypatch.setattr(PG, "launch", handed)
    runner = LocalRunner({"tpch_sf1": conn}, default_catalog="tpch_sf1",
                         page_rows=1 << 18)
    runner.executor.fault_rows = SH.SAFE_BUFFER_ROWS
    with pytest.raises(_Handed) as caught:
        runner.execute(st.sql)
    program, args = caught.value.args
    assert program.label == "stored_probe_batch"
    assert len(built) == {"q3": 2, "q5": 5}[template]
    for compiled, _args in built:
        # the compiler sorts a scatter's (index, row) pairs, 32-bit
        # both: no 64-bit sort, the kind that compiles for minutes
        sorts = [ln for ln in compiled.as_text().splitlines()
                 if " sort(" in ln]
        assert not [ln for ln in sorts if "64[" in ln], sorts
    compiled = program.jitted.lower(*_on(args, one_chip)).compile()
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert " sort(" not in text
    assert memory.temp_size_in_bytes < 1 << 30, memory
    assert memory.argument_size_in_bytes < held
    # every gather of the step, and of the builds that probe their
    # riders, reads a lookup structure the compiler keeps in the
    # core's vector memory (memory space S(1) of the layout): a
    # carried 32-bit column handed over as an argument of its own
    # width stays in HBM, where a gathered row costs three times as
    # much and the statement's time takes a level of its own every
    # process (PERF.md, PR 44), so the build holds such columns as
    # 64-bit (_carried_wide)
    def gathers_of(text):
        defined = dict(re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ", text, re.M))
        lines = [ln for ln in text.splitlines()
                 if "kind=kCustom" in ln and "/gather" in ln]
        for ln in lines:
            operand = re.search(r" fusion\(%?([\w.\-]+)", ln).group(1)
            assert "S(1)" in defined[operand], (operand, defined[operand])
        return lines

    # Q5: supplier's table, s_nationkey's halves, n_name; orders'
    # table, c_nationkey's halves. Q3: orders' table, o_orderdate,
    # o_shippriority
    assert len(gathers_of(text)) == {"q3": 3, "q5": 7}[template]
    # every build gathers its own table back (the duplicate check);
    # orders' build, the last, also probes customer once an ORDER:
    # customer's table and a gather a 32-bit word of its two columns
    # (c_mktsegment's codes are one word, c_nationkey two)
    in_builds = [len(gathers_of(c.as_text())) for c, _a in built]
    assert in_builds[-1] == {"q3": 5, "q5": 6}[template], in_builds
