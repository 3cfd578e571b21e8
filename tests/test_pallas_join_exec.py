"""Executor wiring of the Pallas dim probe — the unique-key fast path
and the general equi-join (pallas_join_enabled session property), for
builds of at most DIM_MAX_BUILD rows (at SF0.01: nation, supplier,
customer, part); the kernel is covered by test_pallas_join.py — these
tests cover eligibility selection and end-to-end parity with the sort
join."""

import collections

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(0.01)


@pytest.fixture(scope="module")
def base(conn):
    return LocalRunner({"tpch": conn}, page_rows=1 << 13)


@pytest.fixture(scope="module")
def pallas(conn):
    r = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    r.session.set("pallas_join_enabled", "true")
    # these tests assert the PALLAS path engages; the build-free
    # generated join (default) would preempt it for generator tables
    r.session.set("generated_join_enabled", False)
    return r


def _same(a, b):
    return collections.Counter(map(repr, a)) == collections.Counter(
        map(repr, b)
    )


def test_inner_join_parity_and_engagement(base, pallas):
    q = ("select o_orderkey, o_totalprice, c_acctbal from orders, "
         "customer where o_custkey = c_custkey order by 1 limit 9")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used > before


def test_build_above_dim_max_takes_sort_join(base, pallas):
    # orders (15,000 rows) is above DIM_MAX_BUILD: the same unique-key
    # inner join under pallas_join_enabled=true takes the sort join —
    # no other kernel, no raise
    q = ("select o_orderkey, o_totalprice, l_extendedprice from orders, "
         "lineitem where o_orderkey = l_orderkey "
         "order by 1, 3 limit 9")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used == before


def test_left_join_null_extension(base, pallas):
    # lineitem pages are 7-aligned (capacity 8190, NOT a Pallas block
    # multiple — exercises probe padding); every lineitem matches a
    # supplier, so also check an artificial no-match band via a
    # filtered build side (unique s_suppkey survives a Filter)
    q = ("select count(*), sum(s_acctbal) from lineitem "
         "left join supplier on l_suppkey = s_suppkey")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used > before
    q2 = ("select count(*), count(s_suppkey) from lineitem left join "
          "(select * from supplier where s_suppkey < 50) t "
          "on l_suppkey = s_suppkey")
    before = pallas.executor.pallas_joins_used
    a, b = base.execute(q2).rows, pallas.execute(q2).rows
    assert _same(a, b)
    assert pallas.executor.pallas_joins_used > before
    # unmatched rows null-extended: count(*) > count(s_suppkey)
    assert b[0][0] > b[0][1] > 0


def test_non_unique_build_falls_back(base, pallas):
    # build side lineitem: l_orderkey is NOT declared unique — must
    # take the general join, not the Pallas path
    before = pallas.executor.pallas_joins_used
    q = ("select count(*) from orders where o_orderkey in "
         "(select l_orderkey from lineitem)")
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    # semi joins are ineligible regardless; counter must not move
    assert pallas.executor.pallas_joins_used == before


def test_aggregate_over_pallas_join(base, pallas):
    q = ("select c_mktsegment, count(*), sum(o_totalprice) from orders, "
         "customer where o_custkey = c_custkey group by c_mktsegment "
         "order by 1")
    assert _same(base.execute(q).rows, pallas.execute(q).rows)


# ----------------------------------------------------------- general join
# (program labels radix_build / radix_probe)


def test_radix_duplicate_key_self_join(base, pallas):
    # self-join on NON-unique c_nationkey: duplicate build keys fan out
    # — the kernel's (start, count) segment ranges, not the unique fast
    # path
    q = ("select count(*), sum(c1.c_acctbal) from customer c1, "
         "customer c2 where c1.c_nationkey = c2.c_nationkey")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used > before


def test_radix_multi_key_join(base, pallas):
    # composite (partkey, size) key: multi-key joins hash-combine into
    # one 64-bit row hash and verify per-column equality after
    # expansion
    q = ("select count(*), sum(l_quantity) from lineitem, part "
         "where l_partkey = p_partkey and l_linenumber = p_size")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used > before


def test_radix_outer_join(base, pallas):
    # unmatched-side emission (right/full) rides the match stats
    q = ("select count(*), count(o_orderkey), count(c_custkey) from "
         "(select * from orders where o_orderkey < 5000) o right join "
         "customer on o_custkey = c_custkey")
    before = pallas.executor.pallas_joins_used
    a, b = base.execute(q).rows, pallas.execute(q).rows
    assert _same(a, b)
    assert pallas.executor.pallas_joins_used > before


def test_radix_string_key_join(base, pallas):
    # dictionary-coded string keys canonicalize through the merged
    # universe before hashing — eligible for the general path (the
    # unique fast path refuses strings)
    q = ("select count(*), min(n1.n_nationkey) from nation n1, "
         "nation n2 where n1.n_name = n2.n_name")
    before = pallas.executor.pallas_joins_used
    assert _same(base.execute(q).rows, pallas.execute(q).rows)
    assert pallas.executor.pallas_joins_used > before
