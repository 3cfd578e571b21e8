"""Scale validation: checksum-verified parity at SF well above the toy
test scale, exercising multi-page streams, capacity-boost retries, and
the verifier checksum harness.

On published answer sets: the TPC-H generator here is spec-shaped
(schemas, distributions, key structure follow TPC-H 4.2.3) but is NOT a
bit-exact dbgen clone — its value streams come from xxhash-keyed draws,
not dbgen's LCG streams — so the published SF1 answer set does not apply
to this data. Cross-engine validation instead runs the same queries over
the SAME generated rows in sqlite (tests/test_sql_tpch.py does this for
all 22 queries) and at SF0.1 here; single-vs-distributed parity is
checksum-verified below.
"""

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner
from presto_tpu.verifier import assert_same_results, checksum_rows
from tests.tpch_queries import QUERIES

SF = 0.1  # 20x the toy suite; ~600k lineitem slots


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def runner(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 15)


def test_checksum_utility_properties():
    rows = [(1, "a", 2.5), (2, "b", None), (3, "a", 0.0)]
    base = checksum_rows(rows)
    # order-insensitive
    assert checksum_rows(list(reversed(rows))) == base
    # value-sensitive
    assert checksum_rows([(1, "a", 2.5), (2, "b", None),
                          (3, "a", 1.0)]) != base
    # count-sensitive
    assert checksum_rows(rows[:2])["count"] == 2


@pytest.mark.parametrize("qid", [1, 3, 6])
def test_sf01_engine_vs_sqlite(qid, conn, runner):
    from tests.oracle import load_sqlite
    from tests.test_sql_tpch import ENGINE_SQL, ORACLE, compare

    tables = {
        1: ["lineitem"],
        3: ["customer", "orders", "lineitem"],
        6: ["lineitem"],
    }[qid]
    db = load_sqlite(conn, tables)
    got = runner.execute(ENGINE_SQL[qid]).rows
    want = db.execute(ORACLE[qid][0]).fetchall()
    compare(qid, got, want, ORACLE[qid][1])


def test_small_pages_force_capacity_paths(conn):
    """Tiny page_rows force multi-page streams, partial-agg capacity
    clipping, and the query-level boost retry; results must be identical
    to the comfortable configuration (checksum compare)."""
    wide = LocalRunner({"tpch": conn}, page_rows=1 << 15)
    tight = LocalRunner({"tpch": conn}, page_rows=1 << 10)
    for qid in (1, 6, 4):
        a = wide.execute(QUERIES[qid]).rows
        b = tight.execute(QUERIES[qid]).rows
        assert_same_results(a, b, label=f"Q{qid} page_rows 32k vs 1k")


def test_single_vs_distributed_checksum(conn):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from presto_tpu.dist.executor import make_mesh

    single = LocalRunner({"tpch": conn}, page_rows=1 << 15)
    dist = LocalRunner(
        {"tpch": conn}, page_rows=1 << 15, mesh=make_mesh(8)
    )
    for qid in (1, 6, 12):
        a = single.execute(QUERIES[qid]).rows
        b = dist.execute(QUERIES[qid]).rows
        assert_same_results(a, b, label=f"Q{qid} single vs dist @ SF{SF}")
