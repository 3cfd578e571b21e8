"""An attempt's deferred scalars dispatch no eager program over a mesh
(ISSUE 37): the (plan node, page) row counts the query trace and
EXPLAIN ANALYZE keep ride in the launch that makes the page
(Page.rows), the overflow flags are read in one metered pull, and on
one device the programs stay what they were."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from benchmarks.harness import manifest
from presto_tpu import types as T
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.dist.executor import DistExecutor, make_mesh
from presto_tpu.exec import programs as PG
from presto_tpu.exec import xfer as XF
from presto_tpu.exec.counters import QUERY_COUNTERS
from presto_tpu.exec.executor import Executor
from presto_tpu.page import Page
from presto_tpu.runner import LocalRunner
from tests.tpch_queries import QUERIES

CELL = manifest.load_cell("mesh4_join_solo")
STATEMENTS = {st.template: st for st in reversed(CELL.every)}
SF = 0.005
COUNTERS = ("row_counts_launched", "row_counts_eager")


@pytest.fixture(scope="module")
def mesh4():
    """The cell's statements over four devices with every group-by
    repartitioned, as SF1 takes on the chip, in several scan rounds."""
    conn = TpchConnector(SF)
    runner = LocalRunner(
        {"tpch": conn, "tpch_sf1": conn},
        default_catalog=CELL.every[0].catalog, page_rows=1 << 13,
        mesh=make_mesh(4),
        dist_options=dict(broadcast_rows=64, gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner


def _recording_pages(monkeypatch):
    """Every page each pages() boundary of the LAST attempt yielded:
    {id(node): (label, [page, ...])}."""
    seen = {}
    pages, begin = Executor.pages, Executor._begin_attempt

    def recording(self, node):
        for page in pages(self, node):
            seen.setdefault(
                id(node), (type(node).__name__, []))[1].append(page)
            yield page

    def begin_attempt(self):
        seen.clear()
        begin(self)

    monkeypatch.setattr(Executor, "pages", recording)
    monkeypatch.setattr(Executor, "_begin_attempt", begin_attempt)
    return seen


def _eager(seen):
    """(label, rows, pages) a node, the rows as page.num_rows() gives
    them for the same pages."""
    return sorted(
        (label, sum(int(page.num_rows()) for page in pages), len(pages))
        for label, pages in seen.values())


def _operators(runner):
    return sorted((sp.name, sp.attrs["rows"], sp.attrs["pages"])
                  for sp in runner.last_trace.spans()
                  if sp.kind == "operator")


def _attempts(runner):
    return [sp.attrs for sp in runner.last_trace.spans()
            if sp.kind == "attempt"]


def _explained(text_rows):
    """(rows, pages) of every plan line of an EXPLAIN ANALYZE."""
    out = []
    for (line,) in text_rows:
        m = re.search(r"\[wall [^,]*, ([\d,]+) pages, ([\d,]+) rows\]",
                      line)
        if m:
            out.append((int(m.group(2).replace(",", "")),
                        int(m.group(1).replace(",", ""))))
    return sorted(out)


@pytest.mark.parametrize("chain", ["fused", "per_node"])
@pytest.mark.parametrize("template", ["q3", "q5"])
def test_mesh_row_counts_ride_in_the_launch(
        template, chain, mesh4, monkeypatch):
    """Every operator span's rows and pages, and EXPLAIN ANALYZE's, are
    what page.num_rows() gives for the same pages, with not one
    num_rows() dispatched in the attempt: whether a scan round is one
    program (d_fused) or one a plan node."""
    if chain == "per_node":
        monkeypatch.setattr(DistExecutor, "_fused_rounds",
                            lambda self, node: None)
    sql = STATEMENTS[template].sql
    seen = _recording_pages(monkeypatch)
    want = mesh4.execute(sql).rows  # traces the programs
    ex = mesh4.executor
    (attempt,) = _attempts(mesh4)
    by_node = _eager(seen)
    boundaries = sum(pages for _label, _rows, pages in by_node)
    assert len(by_node) >= 6 and boundaries > len(by_node)
    assert any(rows > 0 for _label, rows, _pages in by_node)
    assert _operators(mesh4) == by_node
    for where in (attempt, {name: getattr(ex, name)
                            for name in COUNTERS}):
        assert where["row_counts_eager"] == 0
        assert where["row_counts_launched"] == boundaries
    assert ("d_fused" in attempt["launches"]) == (chain == "fused")
    assert ("d_scan" in attempt["launches"]) == (chain == "per_node")
    # the programs are traced: a num_rows() now is an eager dispatch
    with monkeypatch.context() as m:
        m.setattr(Page, "num_rows", _never)
        assert mesh4.execute(sql).rows == want
        assert _operators(mesh4) == by_node
        explained = mesh4.execute("explain analyze " + sql).rows
    assert _explained(explained) == sorted(
        (rows, pages) for _label, rows, pages in by_node)
    counters = explained[-1][0]
    assert "row_counts_eager=0," in counters
    assert f"row_counts_launched={boundaries}," in counters


def _never(self):
    raise AssertionError("page.num_rows() inside a mesh attempt")


@pytest.fixture(scope="module")
def mesh4_rounds():
    """The same over seven scan rounds of 2,048 slots a chip, so that
    a forced split_batch_size launches them 2 + 2 + 2 + 1 or 4 + 3."""
    conn = TpchConnector(SF)
    runner = LocalRunner(
        {"tpch": conn, "tpch_sf1": conn},
        default_catalog=CELL.every[0].catalog, page_rows=1 << 11,
        mesh=make_mesh(4),
        dist_options=dict(broadcast_rows=64, gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    return runner


def _chain_and_result_rows(operators):
    """(node, rows) of the nodes whose rows do not depend on how many
    pages the scan came in: a partial aggregation emits its groups a
    page, and the exchange above it carries them."""
    return sorted((name, rows) for name, rows, _pages in operators
                  if name not in ("Aggregation", "Exchange"))


@pytest.mark.parametrize("size,launches", [(2, 4), (4, 2)])
@pytest.mark.parametrize("template", ["q3", "q5"])
def test_a_batchs_page_brings_the_sum_of_its_splits_counts(
        template, size, launches, mesh4_rounds, monkeypatch):
    """With a batch of rounds a launch (ISSUE 40) the batch's page
    brings ONE count from its launch, the sum of its splits' rows, a
    chip's own a chip; nothing is counted eagerly, and every plan
    node's rows are the round-a-launch run's."""
    runner = mesh4_rounds
    sql = STATEMENTS[template].sql
    want = runner.execute(sql).rows
    (plain,) = _attempts(runner)
    assert plain["launches"]["d_fused"] == 7
    rows_by_node = _chain_and_result_rows(_operators(runner))
    seen = _recording_pages(monkeypatch)
    runner.session.set("split_batch_size", str(size))
    try:
        assert runner.execute(sql).rows == want  # traces the programs
        (attempt,) = _attempts(runner)
        by_node = _eager(seen)
        batches = [pages for label, pages in seen.values()
                   if len(pages) == launches]
        with monkeypatch.context() as m:
            m.setattr(Page, "num_rows", _never)
            assert runner.execute(sql).rows == want
            (again,) = _attempts(runner)
    finally:
        runner.session.set("split_batch_size", "auto")
    assert attempt["mesh_batched_rounds"] == 7 - (size == 2)
    assert attempt["launches"]["d_fused_batch"] == launches - (size == 2)
    assert _operators(runner) == by_node
    assert _chain_and_result_rows(by_node) == rows_by_node
    boundaries = sum(pages for _label, _rows, pages in by_node)
    for where in (attempt, again):
        assert where["row_counts_eager"] == 0
        assert where["row_counts_launched"] == boundaries
    assert boundaries < plain["row_counts_launched"]
    # the chain's pages: a count a chip, in the launch's own output
    assert batches, sorted(seen.values())
    for page in batches[0]:
        assert page.rows.shape == (4,)
        per_chip = np.asarray(page.valid).reshape(4, -1).sum(axis=1)
        assert np.asarray(page.rows).tolist() == per_chip.tolist()


def test_a_passed_through_page_keeps_its_count(mesh4):
    """A gather over a REPLICATED source and Output hand the page on
    as it is, so the count it brought serves each boundary; a page
    derived from it starts without one."""
    mesh4.execute(STATEMENTS["q5"].sql)
    by_rows = {}
    for sp in mesh4.last_trace.spans():
        if sp.kind == "operator" and sp.attrs["pages"] == 1:
            by_rows.setdefault(sp.attrs["rows"], []).append(sp.name)
    assert any("Output" in names and len(names) >= 2
               for names in by_rows.values()), by_rows
    page = Page.from_arrays([[1, 2, 3]], [T.BIGINT])
    page.rows = jnp.asarray([3], jnp.int32)
    for derived in (page.with_valid(page.valid),
                    page.with_blocks(page.blocks),
                    page.select_channels([0]),
                    page.append_blocks(page.blocks)):
        assert derived.rows is None
    leaves, tree = jax.tree.flatten(page)
    assert len(leaves) == 2  # the column and the mask: not the count
    assert jax.tree.unflatten(tree, leaves).rows is None


def test_a_boosted_retry_drops_the_failed_attempts_counts(
        mesh4, monkeypatch):
    """A forced overflow: the statement re-enters boosted, and the
    spans and counters are the successful attempt's alone."""
    sql = ("select l_orderkey, count(*), sum(l_quantity) from lineitem "
           "group by l_orderkey")
    seen = _recording_pages(monkeypatch)
    mesh4.session.set("agg_optimistic_rows", 4096)
    try:
        rows = mesh4.execute(sql).rows
    finally:
        mesh4.session.set("agg_optimistic_rows", 1 << 18)
    *failed, second = _attempts(mesh4)
    first = failed[0]
    assert [a["outcome"] for a in failed] == ["overflow"] * len(failed)
    assert failed and second["outcome"] == "ok"
    assert second["boost"] > first["boost"]
    assert len(rows) > 4096
    by_node = _eager(seen)  # the last attempt's pages
    assert _operators(mesh4) == by_node
    boundaries = sum(pages for _label, _rows, pages in by_node)
    assert second["row_counts_launched"] == boundaries \
        == mesh4.executor.row_counts_launched
    assert second["row_counts_eager"] == 0
    assert "row_counts_launched" not in first  # an attempt that failed


def test_one_device_counts_eagerly_and_says_so():
    """On one device the programs return no count: pages() computes
    each with num_rows(), and the counter tells."""
    runner = LocalRunner({"tpch": TpchConnector(SF)},
                         page_rows=1 << 13)
    runner.session.set("query_trace_enabled", True)
    runner.execute(QUERIES[6])
    (attempt,) = _attempts(runner)
    pages = sum(sp.attrs["pages"] for sp in runner.last_trace.spans()
                if sp.kind == "operator")
    assert attempt["row_counts_eager"] == pages >= 2
    assert attempt["row_counts_launched"] == 0
    assert not any("rows" in key for key in runner.executor._jit_cache)
    runner.session.set("query_trace_enabled", False)
    runner.execute(QUERIES[6])
    ex = runner.executor
    assert (ex.row_counts_eager, ex.row_counts_launched) == (0, 0)


def test_the_counters_are_declared_and_summed():
    from presto_tpu.server.http_server import QueryManager

    for name in COUNTERS:
        assert QUERY_COUNTERS[name][0] == "gauge"
        assert name in QueryManager._EXEC_TOTAL_SUMS
    ex = Executor({"tpch": TpchConnector(SF)})
    ex.row_counts_launched = ex.row_counts_eager = 5
    ex._begin_attempt()
    assert (ex.row_counts_launched, ex.row_counts_eager) == (0, 0)


# ------------------------------------------------- the overflow flags
def _replicated_flag(value):
    return jax.device_put(
        jnp.asarray(value),
        NamedSharding(make_mesh(4), PartitionSpec()))


@pytest.mark.parametrize("n,set_at", [
    (0, None), (1, None), (1, 0), (23, None), (23, 0), (23, 17),
    (23, 22)])
def test_overflow_flags_are_read_in_one_metered_pull(
        n, set_at, monkeypatch):
    """No flag: no read. Any number: ONE pull through exec/xfer.py,
    a byte a flag, counted as a device wait, and no eager ``|``."""
    ex = Executor({"tpch": TpchConnector(SF)})
    ex._pending_overflow = [
        (_replicated_flag if i % 2 else jnp.asarray)(i == set_at)
        for i in range(n)]
    pulls = []
    to_host = XF.to_host

    def one_pull(tree, label="page"):
        pulls.append((label, len(tree)))
        return to_host(tree, label=label)

    monkeypatch.setattr(XF, "to_host", one_pull)
    monkeypatch.setattr(
        type(jnp.asarray(True)), "__or__",
        lambda self, other: pytest.fail("an eager | over the flags"))
    prev = XF.swap_sink(ex)
    try:
        assert ex._overflow_flagged() is (set_at is not None)
    finally:
        XF.swap_sink(prev)
    assert pulls == ([("overflow-flag", n)] if n else [])
    assert (ex.d2h_transfers, ex.d2h_bytes) == ((1, n) if n else (0, 0))
    assert (ex.device_wait_us > 0) == (n > 0)


# ------------------------------------- one device: the parent's programs
@pytest.fixture(scope="module")
def one_device_programs():
    """Q1, Q6, Q3, Q5 on one device with the fused paths the chip
    takes forced on. For every program launched, at its first call:
    the text it lowers to, beside the text of the bare function its
    call site handed Executor._jit, jitted as _jit always did
    (Program(label, fn, donates, **jit_kwargs)): {query: [(label, key,
    text, bare text)]}."""
    made, lowered = {}, {}
    program_cls, launch = PG.Program, PG.launch

    class Recording(program_cls):
        __slots__ = ()

        def __init__(self, label, fn, donates=False, **kw):
            super().__init__(label, fn, donates=donates, **kw)
            made[id(self)] = (fn, kw)

    def recording_launch(sink, program, *args, **kwargs):
        if id(program) in made and id(program) not in lowered:
            fn, kw = made[id(program)]
            bare = program_cls(program.label, fn,
                               donates=program.donates, **kw)
            lowered[id(program)] = (
                program.label,
                program.jitted.lower(*args, **kwargs).as_text(),
                bare.jitted.lower(*args, **kwargs).as_text())
            current.append(id(program))
        return launch(sink, program, *args, **kwargs)

    PG.Program, PG.launch = Recording, recording_launch
    try:
        runner = LocalRunner({"tpch": TpchConnector(0.01)},
                             page_rows=1 << 13)
        runner.session.set("query_trace_enabled", True)
        runner.session.set("fused_partial_agg_enabled", "true")
        runner.session.set("split_batch_size", 8)
        out = {}
        for q in (1, 6, 3, 5):
            current = []
            runner.execute(QUERIES[q])
            out[q] = [lowered[i] for i in current]
    finally:
        PG.Program, PG.launch = program_cls, launch
    return out, runner.executor


@pytest.mark.parametrize("q", [1, 6, 3, 5])
def test_one_device_programs_lower_to_the_parents_text(
        q, one_device_programs):
    """Executor._jit hands jax.jit the function it was handed: no
    count rides in a one-device program, so the four one-chip cells
    ask the compile cache for the keys the parent wrote."""
    programs, ex = one_device_programs
    assert Executor.launch_counts_rows is False
    assert DistExecutor.launch_counts_rows is True
    assert len(programs[q]) >= 2, programs[q]
    assert {"fused_batch", "fused"} & {lab for lab, _t, _b in programs[q]}
    for label, text, bare in programs[q]:
        assert text == bare, label
    assert not any(key[-1] == "rows" for key in ex._jit_cache
                   if isinstance(key, tuple))


def test_a_counted_program_differs_only_by_its_count():
    """The same key on the mesh executor's side of a shared jit cache
    is another program (salted), with the count as its last output."""
    ex = Executor({"tpch": TpchConnector(SF)})
    mesh_ex = DistExecutor({"tpch": TpchConnector(SF)}, make_mesh(4))
    mesh_ex._jit_cache = ex._jit_cache

    def keep_even(page):
        return page.with_valid(
            page.valid & (page.block(0).data % 2 == 0))

    page = Page.from_arrays([[1, 2, 3, 4, 6]], [T.BIGINT])
    plain = ex._jit(("filter", "even"), keep_even)(page)
    counted = mesh_ex._jit(("filter", "even"), keep_even)(page)
    assert set(ex._jit_cache) == {("filter", "even"),
                                  ("filter", "even", "rows")}
    assert plain.rows is None
    assert counted.rows.dtype == jnp.int32
    assert np.asarray(counted.rows).tolist() == [3]
    assert int(counted.num_rows()) == 3
    sharded = mesh_ex._mesh_jit(("d_filter", "even"), keep_even)(
        Page.from_arrays([list(range(8))], [T.BIGINT]))
    # a chip's own count, one entry a chip: 0,1 | 2,3 | 4,5 | 6,7
    assert np.asarray(sharded.rows).tolist() == [1, 1, 1, 1]
    mesh_ex._jit_drop(("filter", "even"))
    assert not ex._jit_cache.keys() & {("filter", "even"),
                                       ("filter", "even", "rows")}
