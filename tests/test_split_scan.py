"""Split-batched execution: the per-SPLIT driver loop of a fused scan
pipeline folds into XLA (exec/executor._fused_stream, split_batch_size
session property).

Three batched program shapes are pinned here against the unbatched
driver loop and the sqlite oracle:

  - grouped scan-agg (Q1 shape): lax.scan over split indices with the
    partial-aggregation state as carry;
  - global scan-agg (Q6 shape): lax.scan stacking the per-split state
    rows (bit-exact concat of the unbatched states);
  - page-emitting chains: the fused body vmapped over a [B, n_pad]
    stacked batch, emitted as one page.

Batching is auto = TPU-only (the win is the per-launch overhead);
every CPU test forces it on via the session property,
the same pattern as the Pallas-join / late-materialization suites.
"""

import dataclasses

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner
from tests.oracle import load_sqlite

Q1ISH = (
    "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
    "from lineitem where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus order by 1, 2"
)
Q6ISH = (
    "select sum(l_extendedprice * l_discount) from lineitem "
    "where l_discount between 0.05 and 0.07 and l_quantity < 24"
)


@pytest.fixture(scope="module")
def rig():
    conn = TpchConnector(0.01)
    # 8192-row pages over SF0.01 lineitem (~60k rows) = 13 live splits:
    # a NON-power-of-two count, so the single 16-bucket batch pads 3
    # tail slots with zero traced row counts every test exercises
    runner = LocalRunner({"tpch": conn}, page_rows=1 << 13)
    runner.session.set("fused_partial_agg_enabled", "true")
    return runner


def _run(runner, sql, batch):
    runner.session.set("split_batch_size", batch)
    try:
        rows = runner.execute(sql).rows
        ex = runner.executor
        return rows, {
            "launches": ex.program_launches,
            "splits": ex.splits_scanned,
            "fused": ex.fused_partial_aggs,
            "fallbacks": ex.split_batch_fallbacks,
        }
    finally:
        runner.session.unset("split_batch_size")


def test_q1_grouped_scan_carry_parity_and_launches(rig):
    """Q1 shape: the whole 13-split scan phase runs as ONE lax.scan
    program with the partial-agg state as carry — counter-verified,
    with exact parity against the unbatched driver loop AND sqlite."""
    on, c_on = _run(rig, Q1ISH, "64")
    off, c_off = _run(rig, Q1ISH, "false")
    assert c_on["fused"] >= 1 and c_on["fallbacks"] == 0
    assert c_on["launches"] <= 2  # acceptance bar: <= 2 for the phase
    assert c_on["splits"] == c_off["splits"]  # every real split ran
    assert c_off["launches"] == c_off["splits"]  # one per split before
    assert on == off
    db = load_sqlite(rig.catalogs["tpch"], ["lineitem"])
    want = db.execute(
        "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
        "from lineitem where l_shipdate <= 10471 "
        "group by l_returnflag, l_linestatus order by 1, 2"
    ).fetchall()
    assert [(r[0], r[1], int(r[2]), r[3]) for r in on] == [
        (w[0], w[1], int(w[2]), w[3]) for w in want
    ]


def test_q6_global_scan_stack_parity_and_launches(rig):
    """Q6 shape: global partial states stack inside one scanned
    program; decimal sums are exact integers, so batched == unbatched
    == sqlite with no tolerance."""
    on, c_on = _run(rig, Q6ISH, "64")
    off, c_off = _run(rig, Q6ISH, "false")
    assert c_on["launches"] <= 2 and c_on["fallbacks"] == 0
    assert c_off["launches"] == c_off["splits"]
    assert on == off
    db = load_sqlite(rig.catalogs["tpch"], ["lineitem"])
    # engine decimals are unscaled ints: discount 0.05 -> 5
    want = db.execute(
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_discount between 5 and 7 and l_quantity < 2400"
    ).fetchone()
    assert int(on[0][0]) == int(want[0])


def test_page_emitting_vmap_batch_parity(rig):
    """A fused filter->project chain with NO agg tail takes the vmap
    path: B splits stack into one [B, n_pad] launch emitted as one
    page, and downstream results match per-split execution exactly."""
    sql = (
        "select l_orderkey, l_extendedprice from lineitem "
        "where l_quantity < 3 order by 1, 2"
    )
    on, c_on = _run(rig, sql, "64")
    off, c_off = _run(rig, sql, "false")
    assert c_on["launches"] < c_off["launches"]
    assert c_on["launches"] <= 2 and c_on["fallbacks"] == 0
    assert on == off


def test_tail_batch_padding_masks_rows(rig):
    """Forcing a small batch size makes ceil(13/4) = 4 chunks whose
    tail chunk (1 split) takes the per-split program — and a batch
    size of 8 leaves a 5-split tail chunk padded to its own 8-bucket.
    Both paddings must be pure masking: parity is exact."""
    base, _ = _run(rig, Q1ISH, "false")
    for b in ("4", "8"):
        rows, c = _run(rig, Q1ISH, b)
        assert rows == base, f"batch={b}"
        assert c["splits"] == 13
        assert c["launches"] == -(-13 // int(b))


def test_overflow_retry_reenters_ladder(rig):
    """A scanned program whose partial-agg capacity overflows must
    OR-reduce the flag across the batch and re-enter the existing
    boosted-retry ladder — same final boost as the unbatched loop,
    same (correct) results."""
    sql = (
        "select l_quantity, count(*) from lineitem "
        "group by l_quantity order by 1"
    )
    ex = rig.executor
    rig.session.set("agg_optimistic_rows", 8)  # 50 groups overflow 8
    try:
        on, c_on = _run(rig, sql, "64")
        boost_on = ex._capacity_boost
        off, _ = _run(rig, sql, "false")
        boost_off = ex._capacity_boost
    finally:
        rig.session.unset("agg_optimistic_rows")
    assert boost_on > 1 and boost_on == boost_off
    assert on == off and len(on) == 50


def test_worker_fragment_batches(rig):
    """The shipped-plan worker path (SplitFilterConnector declares
    fused_scan_ok): a worker's round-robin share of the splits folds
    into one launch too."""
    from presto_tpu.connectors.split_filter import SplitFilterConnector
    from presto_tpu.dist import plan_serde
    from presto_tpu.server.worker import find_partial_cut

    conn = rig.catalogs["tpch"]
    plan = rig.plan(Q1ISH)
    cut = find_partial_cut(plan)
    assert cut is not None
    fragment = plan_serde.loads(
        plan_serde.dumps(dataclasses.replace(cut, step="partial"))
    )
    worker = LocalRunner(
        {"tpch": SplitFilterConnector(conn, "lineitem", 0, 2)},
        page_rows=1 << 13,
    )
    worker.session.set("fused_partial_agg_enabled", "true")
    worker.session.set("split_batch_size", "64")
    worker.apply_session()
    ex = worker.executor
    pages = ex.stream_fragment(fragment, lambda p: p)
    assert pages and ex.fused_partial_aggs >= 1
    assert ex.program_launches == 1 and ex.splits_scanned == 7


def test_counters_in_explain_analyze(rig):
    """program_launches / splits_per_launch ride EXPLAIN ANALYZE's
    counters line (the observability contract of the acceptance
    criteria)."""
    rig.session.set("split_batch_size", "64")
    try:
        rig.apply_session()
        plan = rig.plan(Q6ISH)
        _n, _r, stats = rig.executor.execute_with_stats(plan)
    finally:
        rig.session.unset("split_batch_size")
    ctr = stats["counters"]
    assert ctr["program_launches"] >= 1
    assert ctr["splits_per_launch"] > 1
    from presto_tpu.runner import explain_text

    text = explain_text(plan, stats=stats)
    assert "program_launches" in text and "splits_per_launch" in text


def test_auto_is_tpu_only(rig):
    """auto = TPU-only (the pallas_joins_used policy): on this CPU
    suite the resolved max batch is 0 and nearby split counts share
    the per-split programs they always had."""
    rig.apply_session()  # default: auto
    ex = rig.executor
    assert ex.split_batch == "auto"
    assert ex._split_batch_max(8192, scanned=True) == 0
    assert ex._split_batch_max(8192, scanned=False) == 0
    # explicit int engages anywhere, floored to a ladder power of two
    ex.split_batch = 48
    assert ex._split_batch_max(8192, scanned=True) == 32
    # vmapped page batches bound B * n_pad under the kernel fault line
    ex.split_batch = 64
    assert ex._split_batch_max(1 << 20, scanned=False) == 4
    ex.split_batch = "auto"


def test_batch_buckets_share_programs(rig):
    """Nearby split counts land on the same batch bucket: re-running
    with the same shapes must compile nothing new (the shapes.py
    ladder composing with the persistent compile cache)."""
    _run(rig, Q6ISH, "64")  # warm the batched program
    ex = rig.executor
    jit_keys = set(ex._jit_cache)
    rows, c = _run(rig, Q6ISH, "64")
    assert set(ex._jit_cache) == jit_keys  # no new canonical programs
    assert c["launches"] <= 2
