"""SQL-level TPC-DS correctness (BASELINE rung 5): Q17 and Q64 run
through parse → plan → execute and are checked against sqlite3 running an
encoding-adapted oracle over the same generated rows (same pattern as
test_sql_tpch.py; reference analog: presto-tpcds + AbstractTestQueries).

Oracle adaptations: decimals are unscaled cents ints (64 -> 6400);
stddev_samp is registered as a Python aggregate UDF (sqlite has none).
"""

import collections
import math

import pytest

from presto_tpu.connectors.tpcds import TpcdsConnector
from presto_tpu.runner import LocalRunner
from tests.oracle import load_sqlite
from tests.tpcds_queries import QUERIES

SF = 0.01
# Q64's cross-channel chain (same item returned in consecutive years at
# the same store, within the qualified color/price band) is empty below
# SF ~0.025, and the 18-table plan takes many minutes of XLA compile on
# the 1-core CPU CI — so the Q64 correctness test runs at its own scale,
# opt-in via RUN_SLOW=1. It is part
# of the bench ladder on real hardware.
Q64_SF = 0.025


class _StddevSamp:
    """Welford accumulator registered as a sqlite aggregate UDF."""

    def __init__(self):
        self.n = 0
        self.mean = 0.0
        self.m2 = 0.0

    def step(self, v):
        if v is None:
            return
        self.n += 1
        d = v - self.mean
        self.mean += d / self.n
        self.m2 += d * (v - self.mean)

    def finalize(self):
        if self.n < 2:
            return None
        return math.sqrt(self.m2 / (self.n - 1))


@pytest.fixture(scope="module")
def conn():
    return TpcdsConnector(SF)


@pytest.fixture(scope="module")
def runner(conn):
    return LocalRunner({"tpcds": conn}, default_catalog="tpcds",
                       page_rows=1 << 16)


# only the tables this module's queries touch — inventory alone is
# ~940k rows at SF0.01 and would dominate fixture setup if loaded
# unconditionally
_ORACLE_TABLES = [
    "store_sales", "store_returns", "catalog_sales", "catalog_returns",
    "date_dim", "store", "item", "customer", "customer_address",
    "web_sales", "warehouse", "ship_mode", "web_site", "reason",
    "time_dim", "household_demographics", "inventory",
    "customer_demographics", "promotion",
]


@pytest.fixture(scope="module")
def db(conn):
    d = load_sqlite(conn, _ORACLE_TABLES)
    d.create_aggregate("stddev_samp", 1, _StddevSamp)
    return d


ORACLE_17 = QUERIES[17]  # integer quantities: no encoding adaptation

ORACLE_64 = QUERIES[64].replace(
    "between 64 and 74", "between 6400 and 7400"
).replace(
    "between 65 and 79", "between 6500 and 7900"
)

# Q82: i_current_price decimals are unscaled cents in both engines'
# shared rows; the literal band scales accordingly
ORACLE_82 = QUERIES[82].replace(
    "between 62 and 92", "between 6200 and 9200"
)

# float-tolerance columns of Q17: ave/stdev/cov per channel
Q17_FLOAT_COLS = {4, 5, 6, 8, 9, 10, 12, 13, 14}


# Q37 shares Q82's decimal-band adaptation
ORACLE_37 = QUERIES[37].replace(
    "between 68 and 98", "between 6800 and 9800"
)

# round-4 breadth queries: float cols (avg over ints -> sqlite float)
# and round cols (avg over cents decimals: engine yields round-half-up
# int cents, sqlite a float — bucket both to int, tpch "r" mode)
_DS_ORACLE = {
    3: (QUERIES[3], set(), set()),
    7: (QUERIES[7], {1}, {2, 3, 4}),
    17: (ORACLE_17, Q17_FLOAT_COLS, set()),
    19: (QUERIES[19], set(), set()),
    25: (QUERIES[25], set(), set()),
    26: (QUERIES[26], {1}, {2, 3, 4}),
    29: (QUERIES[29], set(), set()),
    37: (ORACLE_37, set(), set()),
    42: (QUERIES[42], set(), set()),
    52: (QUERIES[52], set(), set()),
    55: (QUERIES[55], set(), set()),
    62: (QUERIES[62], set(), set()),
    64: (ORACLE_64, set(), set()),
    82: (ORACLE_82, set(), set()),
    93: (QUERIES[93], set(), set()),
    96: (QUERIES[96], set(), set()),
}


def _norm(row, float_cols, round_cols=frozenset()):
    out = []
    for j, v in enumerate(row):
        if v is None:
            out.append(None)
        elif j in float_cols:
            out.append(round(float(v), 6))
        elif j in round_cols:
            # round-half-up (engine decimal avgs round half up; python
            # round() is banker's)
            out.append(math.floor(float(v) + 0.5))
        else:
            out.append(v)
    return tuple(out)


def _compare(engine_rows, oracle_rows, float_cols, label,
             round_cols=frozenset()):
    assert len(engine_rows) == len(oracle_rows), (
        f"{label}: row count {len(engine_rows)} vs {len(oracle_rows)}\n"
        f"engine: {engine_rows[:3]}\noracle: {oracle_rows[:3]}"
    )
    e_rows = [_norm(r, float_cols, round_cols) for r in engine_rows]
    o_rows = [_norm(tuple(r), float_cols, round_cols)
              for r in oracle_rows]
    for i, (er, orow) in enumerate(zip(e_rows, o_rows)):
        for j, (ev, ov) in enumerate(zip(er, orow)):
            if j in float_cols and ev is not None and ov is not None:
                assert abs(ev - ov) <= 1e-6 * max(1.0, abs(ov)), (
                    f"{label} row {i} col {j}: {ev} != {ov}"
                )
            else:
                assert ev == ov, (
                    f"{label} row {i} col {j}: {ev!r} != {ov!r}"
                )


def test_q17(runner, db):
    got = runner.execute(QUERIES[17]).rows
    want = db.execute(ORACLE_17).fetchall()
    assert len(want) > 0, "oracle returned no rows — fixture too sparse"
    _compare(got, want, Q17_FLOAT_COLS, "Q17")


@pytest.mark.parametrize(
    "qid", [3, 7, 19, 25, 26, 29, 37, 42, 52, 55, 62, 82, 93, 96]
)
def test_breadth_queries(qid, runner, db):
    """Rounds 3-4 breadth: store/catalog/web channels, inventory,
    demographics, promotion, reason, time_dim, warehouse, ship_mode,
    web_site — each vs the sqlite oracle over the same rows."""
    sql, float_cols, round_cols = _DS_ORACLE[qid]
    got = runner.execute(QUERIES[qid]).rows
    want = db.execute(sql).fetchall()
    if qid == 96:
        # bare count: non-zero or the fixture verified nothing
        assert want[0][0] > 0, "Q96: fixture too sparse"
    else:
        assert len(want) > 0, (
            f"Q{qid}: oracle returned no rows — fixture too sparse"
        )
    _compare(got, want, float_cols, f"Q{qid}", round_cols)


@pytest.mark.skipif(
    not __import__("os").environ.get("RUN_SLOW"),
    reason="Q64 needs SF 0.025 + ~10 min of 1-core XLA compile; "
    "set RUN_SLOW=1",
)
def test_q64():
    conn64 = TpcdsConnector(Q64_SF)
    runner = LocalRunner({"tpcds": conn64}, default_catalog="tpcds",
                         page_rows=1 << 17)
    db = load_sqlite(conn64, conn64.tables())
    db.create_aggregate("stddev_samp", 1, _StddevSamp)
    got = runner.execute(QUERIES[64]).rows
    want = db.execute(ORACLE_64).fetchall()
    assert len(want) > 0, "oracle returned no rows — fixture too sparse"
    _compare(got, want, set(), "Q64")


def test_generator_invariants(conn):
    """Structural sanity of the generator itself (cheap, no engine)."""
    import numpy as np

    # date_dim calendar parts agree with python's calendar
    import datetime

    page = next(conn.pages("date_dim"))
    rows = page.to_pylist()
    assert len(rows) == conn.row_count("date_dim")
    cols = conn.table_schema("date_dim").column_names()
    i_sk = cols.index("d_date_sk")
    i_year = cols.index("d_year")
    i_moy = cols.index("d_moy")
    i_dom = cols.index("d_dom")
    i_qn = cols.index("d_quarter_name")
    base = datetime.date(1900, 1, 1)
    for probe in (0, 1, 58, 36524, 73048, 40177):
        r = rows[probe]
        d = base + datetime.timedelta(days=probe)
        assert r[i_sk] == 2415022 + probe
        assert (r[i_year], r[i_moy], r[i_dom]) == (d.year, d.month, d.day)
        assert r[i_qn] == f"{d.year}Q{(d.month - 1) // 3 + 1}"

    # demographics cross product: sk decodes bijectively on a sample
    cd = list(conn.pages("customer_demographics"))[0].to_pylist()
    seen = set(tuple(r[1:]) for r in cd)
    assert len(seen) == len(cd), "cd decode must be injective"

    # returns reference their sale: same item/ticket multiset subset
    ss = [r for p in conn.pages("store_sales") for r in p.to_pylist()]
    sr = [r for p in conn.pages("store_returns") for r in p.to_pylist()]
    ss_cols = conn.table_schema("store_sales").column_names()
    sr_cols = conn.table_schema("store_returns").column_names()
    ss_keys = collections.Counter(
        (r[ss_cols.index("ss_item_sk")],
         r[ss_cols.index("ss_ticket_number")]) for r in ss
    )
    for r in sr:
        k = (r[sr_cols.index("sr_item_sk")],
             r[sr_cols.index("sr_ticket_number")])
        assert ss_keys[k] >= 1
    # return ratio near the spec's ~10%
    assert 0.05 < len(sr) / len(ss) < 0.15
    # return quantity bounded by sale quantity per matching line is
    # guaranteed by construction (rqty = u % qty + 1); spot-check ranges
    qty_i = sr_cols.index("sr_return_quantity")
    assert all(1 <= r[qty_i] <= 100 for r in sr)
