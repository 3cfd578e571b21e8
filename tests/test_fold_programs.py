"""What the constant fold (ISSUE 42, expr/fold.py) leaves of Q6 in the
programs and on the served path: the scan step's lowered text holds no
civil-calendar arithmetic for the filter's upper bound, and the
statement says how many calls it lost: ``constants_folded`` on its
``plan`` phase, ``plan_constants_folded`` on /metrics."""

import collections
import re

import pytest

from benchmarks.harness import manifest, serve
from presto_tpu.connectors.cached import ResidentConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import programs as PG
from presto_tpu.runner import LocalRunner
from presto_tpu.sql import planner as PL

SF = 0.01
SCAN = manifest.load_cell("scan_sf10_solo")
JOIN = manifest.load_cell("join_sf1_solo")
MIXED = manifest.load_cell("mixed_sf1_sf10_c8")
STATEMENTS = {st.key: st for st in SCAN.every + JOIN.every}
Q6 = STATEMENTS["q6_sf10#0"]
# Q6 with its three constant expressions written out by hand
Q6_BY_HAND = (
    Q6.sql
    .replace("date '1994-01-01' + interval '1' year", "date '1995-01-01'")
    .replace("0.06 - 0.01", "0.05").replace("0.06 + 0.01", "0.07"))
DIVISIONS = ("divide", "remainder")


def _scan_step_ops(conn, sql, label):
    """StableHLO operations, counted, of the first ``label`` program a
    statement launches on the chip's drivers (the batched fused scan
    step with the partial aggregation in it)."""
    runner = LocalRunner({"tpch": conn}, default_catalog="tpch",
                         page_rows=4096)
    runner.session.set("fused_partial_agg_enabled", "true")
    runner.session.set("split_batch_size", 8)
    texts = []
    launch = PG.launch

    def lowering(sink, program, *args, **kwargs):
        if program.label == label and not texts:
            texts.append(program.jitted.lower(*args, **kwargs).as_text())
        return launch(sink, program, *args, **kwargs)

    PG.launch = lowering
    try:
        runner.execute(sql)
    finally:
        PG.launch = launch
    (text,) = texts
    return collections.Counter(re.findall(r"stablehlo\.(\w+)", text))


def test_by_hand_is_q6():
    assert Q6_BY_HAND != Q6.sql and "interval" not in Q6_BY_HAND


def test_stored_q6_scan_step_divides_nothing(monkeypatch):
    """Over a stored table nothing is generated, so the step's only
    divisions were the filter's: none is left. The raw tree's program
    (the pass taken out by hand, no switch) has them."""
    conn = ResidentConnector(TpchConnector(SF), tables=["lineitem"])
    ops = _scan_step_ops(conn, Q6.sql, "stored_batch")
    assert ops["while"] >= 1 and ops["compare"] >= 4
    assert not [op for op in DIVISIONS if ops[op]], ops
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    raw = _scan_step_ops(
        ResidentConnector(TpchConnector(SF), tables=["lineitem"]),
        Q6.sql, "stored_batch")
    assert raw["divide"] >= 1 and raw["remainder"] >= 1, raw


def test_generated_q6_scan_step_divides_only_to_generate(monkeypatch):
    """The generator divides on its own account (dates, keys): the
    folded Q6 holds exactly the divisions of Q6 written with its
    constants by hand, fewer than the raw tree's."""
    conn = TpchConnector(SF)
    ops = _scan_step_ops(conn, Q6.sql, "fused_batch")
    by_hand = _scan_step_ops(conn, Q6_BY_HAND, "fused_batch")
    assert {op: ops[op] for op in DIVISIONS} == \
        {op: by_hand[op] for op in DIVISIONS}
    monkeypatch.setattr(PL, "fold_constants", lambda e: (e, 0))
    raw = _scan_step_ops(conn, Q6.sql, "fused_batch")
    assert all(raw[op] > ops[op] for op in DIVISIONS), (raw, ops)


# ----------------------------------------------------------------- served

@pytest.fixture(scope="module", params=["serial", "concurrent"])
def served(request, tmp_path_factory):
    """The join cell's deployment (the serial path: /metrics reads the
    bootstrap executor) and the mixed cell's (query.max-memory-bytes:
    a runner a query, /metrics adds the finished queries' counts, and
    a statement is planned twice: for admission's estimate_memory and
    for the execution)."""
    cell = JOIN if request.param == "serial" else MIXED
    etc = str(tmp_path_factory.mktemp(f"fold_{request.param}") / "etc")
    serve.write_etc(etc, cell.config, rehearse=True)
    srv = serve.Served(etc, cell.chips)
    srv.plans_a_statement = 1 if request.param == "serial" else 2
    yield srv
    srv.stop()


@pytest.mark.parametrize("key,want", [
    ("q6_sf10#0", 3), ("q1_sf10#0", 1), ("q5_sf1#0", 1), ("q3_sf1#0", 0)])
def test_served_statement_says_what_it_folded(served, key, want):
    st = STATEMENTS[key]
    before = served.metrics()["plan_constants_folded"]
    client = served.client(st.catalog)
    client.session_properties["query_trace_enabled"] = "true"
    res = client.execute(st.sql)
    assert res.state == "FINISHED", res.error
    phases = served.query_info(res.query_id)["phases"]
    (plan,) = [p for p in phases if p["kind"] == "plan"]
    assert plan["attrs"]["constants_folded"] == want
    assert [p["attrs"].get("constants_folded") for p in phases
            if p["kind"] != "plan"] == [None] * 4
    assert served.metrics()["plan_constants_folded"] - before == \
        want * served.plans_a_statement


def test_metrics_count_every_statement_planned(served):
    """A counter on both paths: two statements add what each of their
    planning passes folded, whatever was planned before them."""
    import urllib.request

    before = served.metrics()["plan_constants_folded"]
    for key in ("q6_sf10#0", "q1_sf10#0"):
        st = STATEMENTS[key]
        res = served.client(st.catalog).execute(st.sql)
        assert res.state == "FINISHED", res.error
    assert served.metrics()["plan_constants_folded"] - before == \
        (3 + 1) * served.plans_a_statement
    text = urllib.request.urlopen(
        served.url + "/metrics").read().decode()
    assert "# TYPE presto_tpu_plan_constants_folded_total counter" in text
    assert "presto_tpu_plan_constants_folded " not in text
