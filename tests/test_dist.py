"""Ring-3 distributed tests: the full engine on a virtual 8-device mesh,
checked for exact result parity with single-device execution.

Reference: presto-tests tests/DistributedQueryRunner.java — a real
coordinator + N workers in one JVM running the shared correctness suites.
Our analog: DistExecutor over an 8-device CPU mesh (conftest forces
xla_force_host_platform_device_count=8) vs the single-stream Executor on
identical generated data. Two configurations:

  - default thresholds: small-SF plans broadcast/gather (the realistic
    shape at this scale),
  - forced thresholds: every join partitions both sides and every
    group-by repartitions its partial states — exercising the
    lax.all_to_all repartition exchange end to end.
"""

import collections

import jax
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.dist.executor import make_mesh
from presto_tpu.dist.fragmenter import add_exchanges
from presto_tpu.exec import plan as P
from presto_tpu.runner import LocalRunner, explain_text
from tests.tpch_queries import QUERIES

SF = 0.005


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def single(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must force 8 cpu devices"
    return make_mesh(8)


@pytest.fixture(scope="module")
def dist(conn, mesh):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13, mesh=mesh)


@pytest.fixture(scope="module")
def dist_repart(conn, mesh):
    """Thresholds forced low so joins partition and group-bys
    repartition — the all_to_all paths."""
    return LocalRunner(
        {"tpch": conn}, page_rows=1 << 13, mesh=mesh,
        dist_options=dict(broadcast_rows=64, gather_capacity=16),
    )


def _canon_row(row):
    # floats compare to 9 significant digits: single-stream and mesh
    # execution sum in different orders (and canonical page-shape
    # padding changes the reduction tree), so float aggregates agree
    # to ulps, not bit-exactly; everything else stays exact
    return tuple(
        f"{v:.9e}" if isinstance(v, float) else repr(v) for v in row
    )


def rows_equal(a, b):
    return collections.Counter(map(_canon_row, a)) == collections.Counter(
        map(_canon_row, b)
    )


# every query family: scan/agg (1, 6), joins (3, 5, 10), semi/anti (4,
# 21, 22), correlated decorrelation (2, 17, 20), outer joins (13)
DEFAULT_QUERIES = [1, 2, 3, 4, 5, 6, 10, 13, 17, 20, 21, 22]
REPART_QUERIES = [1, 3, 6, 10, 13]


@pytest.mark.parametrize("qnum", DEFAULT_QUERIES)
def test_dist_matches_single(qnum, single, dist):
    from tests.test_sql_tpch import ENGINE_SQL

    a = single.execute(ENGINE_SQL[qnum]).rows
    b = dist.execute(ENGINE_SQL[qnum]).rows
    assert rows_equal(a, b), (
        f"Q{qnum} dist != single\nsingle: {a[:3]}\ndist: {b[:3]}"
    )


@pytest.mark.parametrize("qnum", REPART_QUERIES)
def test_dist_repartition_matches_single(qnum, single, dist_repart):
    from tests.test_sql_tpch import ENGINE_SQL

    a = single.execute(ENGINE_SQL[qnum]).rows
    b = dist_repart.execute(ENGINE_SQL[qnum]).rows
    assert rows_equal(a, b), (
        f"Q{qnum} repart != single\nsingle: {a[:3]}\ndist: {b[:3]}"
    )


def test_fragmenter_inserts_expected_exchanges(dist_repart):
    from tests.test_sql_tpch import ENGINE_SQL

    txt = explain_text(dist_repart.plan(ENGINE_SQL[3]))
    assert "Exchange[repartition" in txt
    assert "Exchange[gather]" in txt
    assert "step=partial" in txt and "step=final" in txt


def _topn_stages(node, under_gather=False, out=None):
    """(source is sharded below a gather, node) of every TopN."""
    out = [] if out is None else out
    if isinstance(node, P.TopN):
        out.append((under_gather, node))
    for child in node.children():
        _topn_stages(
            child, isinstance(node, P.Exchange) and node.kind == "gather",
            out)
    return out


@pytest.mark.parametrize("sql,stages", [
    # sharded source (the final aggregation over repartitioned state):
    # a top-N on every chip below the gather, the final one above it
    (3, 2),
    ("select l_orderkey, l_extendedprice from lineitem "
     "order by l_extendedprice desc, l_orderkey limit 5", 2),
    # replicated source (inline rows): left alone, no gather
    ("values", 1),
], ids=["q3_final_agg", "scan", "replicated_values"])
def test_fragmenter_puts_topn_under_the_gather(sql, stages, dist_repart):
    from presto_tpu import types as T
    from presto_tpu.ops.sort import SortKey
    from tests.test_sql_tpch import ENGINE_SQL

    if sql == "values":
        plan, out_dist = add_exchanges(P.TopN(
            P.Values((T.BIGINT,), ((3,), (1,), (2,))),
            (SortKey(0),), 2), dist_repart.catalogs)
        assert out_dist == "replicated"
    else:
        plan = dist_repart.plan(ENGINE_SQL.get(sql, sql))
    found = _topn_stages(plan)
    assert len(found) == stages, explain_text(plan)
    final = found[0][1]
    assert not found[0][0]
    if stages == 1:
        assert "Exchange" not in explain_text(plan)
        return
    (under, partial), = found[1:]
    assert under, "the per-chip top-N has to sit right under the gather"
    assert isinstance(final.source, P.Exchange)
    assert final.source.kind == "gather" and final.source.source is partial
    assert (partial.keys, partial.limit) == (final.keys, final.limit)
    ex = dist_repart.executor
    assert ex.dist(partial) == "sharded" and ex.dist(final) == "replicated"
    # Sort keeps its whole-input gather: no sort stage below it
    sort_plan = dist_repart.plan(
        "select l_orderkey from lineitem where l_orderkey < 40 "
        "order by l_orderkey")
    txt = explain_text(sort_plan)
    assert txt.count("Sort[") == 1 and "TopN" not in txt
    assert txt.index("Sort[") < txt.index("Exchange[gather]")


def test_fragmenter_broadcast_small_build(dist):
    # nation/region builds are far below the broadcast threshold
    txt = explain_text(dist.plan(QUERIES[5]))
    assert "Exchange[broadcast]" in txt


def test_exchange_noop_single_device(single, conn):
    """A fragmented plan executes correctly on the single-stream Executor
    too (exchanges degrade to pass-through)."""
    from tests.test_sql_tpch import ENGINE_SQL

    plan = single.plan(ENGINE_SQL[6])
    frag, _ = add_exchanges(plan, single.catalogs)
    names, rows = single.executor.execute(frag)
    base = single.execute(ENGINE_SQL[6]).rows
    assert rows_equal(rows, base)


ROUND2_QUERIES = [
    # variance family through partial/final state merge across shards
    "select l_returnflag, stddev(l_quantity), var_samp(l_extendedprice),"
    " count(*) from lineitem group by l_returnflag",
    # global variance (gather of moment sums)
    "select stddev_pop(o_totalprice), variance(o_totalprice) from orders",
    # MarkDistinct: mixed DISTINCT/plain and multiple distinct columns
    "select count(distinct n_regionkey), count(distinct n_name), "
    "count(*) from nation",
    "select o_orderpriority, count(distinct o_custkey), sum(o_totalprice)"
    " from orders group by o_orderpriority",
]


@pytest.mark.parametrize("qi", range(len(ROUND2_QUERIES)))
def test_dist_round2_aggregates(qi, single, dist, dist_repart):
    """Round-2 aggregate features must hold on the mesh in both exchange
    configurations (broadcast/gather and forced all_to_all)."""
    q = ROUND2_QUERIES[qi]
    want = single.execute(q).rows
    assert rows_equal(dist.execute(q).rows, want)
    assert rows_equal(dist_repart.execute(q).rows, want)
