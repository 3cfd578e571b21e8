"""The benchmark's own arithmetic, comparison, traffic generator and
trace reduction (benchmarks/harness), on the CPU. Nothing here describes
a TPU topology or times anything."""

import json
import math
import os
import statistics

import numpy as np
import pytest

from benchmarks.harness import manifest, reference, stats, traffic
from benchmarks.harness import trace as tracing

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
# every cell the manifest has, so that a cell a later PR adds is tested
# by the tests that are here
CELLS = tuple(w["name"] for w in MANIFEST["workloads"])
# what has been accepted and may not go or loosen: the one place that
# pins the manifest
ACCEPTED_CELLS = {"scan_sf10_solo", "join_sf1_solo", "mixed_sf1_sf10_c8"}
ACCEPTED_BOUNDS = {"query_geomean_ms": 0.015, "contended_geomean_ms": 0.15,
                   "queries_per_s": 0.2, "setup_s": 0.25}


# ------------------------------------------------------------------ stats
def test_geomean_of_group_medians():
    got = stats.geomean_of_group_medians(
        {"q6": [600.0, 640.0, 700.0], "q1": [1000.0, 1100.0],
         "empty": []})
    assert got == pytest.approx(math.sqrt(640.0 * 1050.0))


def test_geomean_is_not_moved_by_how_many_samples_a_group_has():
    few = stats.geomean_of_group_medians({"a": [10.0], "b": [1000.0]})
    many = stats.geomean_of_group_medians(
        {"a": [10.0] * 50, "b": [1000.0]})
    assert few == pytest.approx(many) == pytest.approx(100.0)


def test_a_tail_needs_ten_samples_beyond_it():
    assert stats.min_tail_samples(0.90) == 100
    assert stats.min_tail_samples(0.95) == 200
    xs = [float(i) for i in range(1, 201)]
    p95, n = stats.tail(xs, 0.95)
    assert n == 200 and p95 == pytest.approx(190.05)
    assert stats.tail(xs[:199], 0.95) == (None, 199)
    p90, n = stats.tail(xs[:100], 0.90)
    assert n == 100 and p90 == pytest.approx(90.1)
    assert stats.tail(xs[:99], 0.90) == (None, 99)


def test_throughput_is_the_windows_work_over_the_windows_time():
    # three statements, the last in flight when a 10 s window closed
    assert stats.throughput([1.0, 5.0, 12.0], 10.0) == \
        pytest.approx(2 / 10.0)
    assert stats.throughput([], 10.0) == 0.0


def test_spread_is_the_drivers():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


# ------------------------------------------------------------- comparison
Q1_COLUMNS = [{"name": "f", "type": "varchar(1)"},
              {"name": "s", "type": "decimal(38,2)"},
              {"name": "d", "type": "date"},
              {"name": "n", "type": "bigint"}]
WIRE = [["A", "533383160.69", "1995-03-15", 14826],
        ["N", "12808797.20", "1995-03-16", 372]]
WANT = [("A", 53338316069, 9204, 14826), ("N", 1280879720, 9205, 372)]


def test_wire_rows_decode_to_the_engine_encoding():
    assert reference.engine_encoding(Q1_COLUMNS, WIRE) == WANT
    assert reference.mismatch(WANT, WANT) == ""


@pytest.mark.parametrize("got, what", [
    ([WANT[0], ("N", 1280879721, 9205, 372)], "row 1 col 1"),   # wrong row
    ([WANT[0]], "1 rows served"),                              # missing row
    ([WANT[1], WANT[0]], "row 0 col 0"),                       # order
    # a decimal that went through float32 on its way
    ([(WANT[0][0], int(np.float32(53338316069)), 9204, 14826), WANT[1]],
     "row 0 col 1"),
])
def test_comparison_fails(got, what):
    assert what in reference.mismatch(got, WANT)


def test_doubles_compare_to_1e9_relative_and_nothing_else_does():
    assert reference.mismatch([(1.0 + 5e-10,)], [(1.0,)]) == ""
    assert reference.mismatch([(1.0 + 5e-9,)], [(1.0,)])
    # a float where the reference is exact is a wrong answer
    assert reference.mismatch([(5.0,)], [(5,)])


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("name", CELLS)
def test_every_seed_gives_the_same_work_in_another_order(name):
    cell = manifest.load_cell(name)

    def first_passes(seed):
        return [[st.key for st in next(p.passes())]
                for p in traffic.plan_clients(cell, seed)]

    a, b = first_passes(3000000019), first_passes(3000000019)
    assert a == b
    orders = set()
    for seed in range(2 ** 31, 2 ** 31 + 12):
        got = first_passes(seed)
        assert [len(d) for d in got] == [len(d) for d in a]
        if not any(g["variants"] == "one_per_run"     # the seed picks
                   for g in cell.traffic["clients"]):
            assert [sorted(d) for d in got] == [sorted(d) for d in a]
        orders.add(json.dumps(got))
    assert len(orders) > 1


def test_join_cell_takes_one_variant_of_each_template():
    cell = manifest.load_cell("join_sf1_solo")
    picked = set()
    for seed in range(40):
        (plan,) = traffic.plan_clients(cell, seed)
        assert sorted(st.sid for st in plan.deck) == [
            "q3_sf1", "q5_sf1", "q5_sf1", "q5_sf1"]
        assert len({st.key for st in plan.deck}) == 2
        picked.update(st.key for st in plan.deck)
    assert picked == {"q3_sf1#0", "q3_sf1#1", "q5_sf1#0", "q5_sf1#1"}


def test_mixed_cell_has_six_interactive_and_two_batch_clients():
    plans = traffic.plan_clients(
        manifest.load_cell("mixed_sf1_sf10_c8"), 7)
    classes = [{st.klass for st in p.deck} for p in plans]
    assert classes == [{"interactive"}] * 6 + [{"batch"}] * 2
    assert len(traffic.statements_used(plans)) == 8


# --------------------------------------------------------------- manifest
def test_manifest_names_what_the_issue_names():
    """What has to hold of any accepted manifest, however many cells and
    metrics later PRs have appended to it."""
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in m["workloads"]]
    assert ACCEPTED_CELLS <= set(cells)
    assert len(cells) == len(set(cells)) <= 24
    configs = {c["name"]: c for c in m["configs"]}
    for w in m["workloads"]:
        body = manifest.load_json(
            os.path.join(manifest.ROOT, configs[w["config"]]["file"]))
        assert w["chips"] in (1, 4) and w["chips"] == body["chips"], w
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    for name, bound in ACCEPTED_BOUNDS.items():
        assert end_to_end[name]["bound"] == bound, name
    for cell in cells:
        reported = {n for n, e in end_to_end.items()
                    if cell in e.get("workloads", cells)}
        assert "setup_s" in reported and len(reported) >= 2, cell
    for metric in m["per_layer"]:
        manifest.load_module("layer_metrics", metric["name"]).read
        assert metric["moves"] in end_to_end, metric["name"]
    for cfg in m["configs"]:
        body = manifest.load_json(os.path.join(manifest.ROOT, cfg["file"]))
        assert body["source"] == cfg["source"]
        assert sorted(body["reduced"]) == sorted(cfg["reduced"])
        assert body["guarantees"]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(name):
    cell = manifest.load_cell(name)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert all(m["moves"] in names for m in cell.per_layer)


def test_unknown_device_is_an_error_not_a_default():
    assert manifest.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in benchmarks/peaks.json"):
        manifest.load_peaks("TPU v9 imaginary")


# ------------------------------------------------------------------ trace
def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4), (6.0, 6.5)]
    assert tracing.union_seconds(iv) == pytest.approx(3.5)
    assert tracing.gaps(iv) == [(2.0, 3.0), (4.0, 6.0)]
    assert tracing.union_seconds([]) == 0.0


FIXTURE = os.path.join(HERE, "data", "q6_sf1_one_statement.xplane.pb.gz")


def test_reduction_of_a_trace_recorded_on_the_chip():
    """The device trace of one Q6 on tpch_sf1 through /v1/statement,
    recorded on the v5e in PR 24 (client wall 107.9 ms). The numbers are
    what the reduction gave on the chip that day; the relations are what
    has to hold of any trace."""
    lines = {(d["plane"], d["line"]): d["events"]
             for d in tracing.describe(FIXTURE)}
    assert lines[("/device:TPU:0", "XLA Ops")] == 2549
    assert lines[("/device:TPU:0", "XLA Modules")] == 9
    assert any(plane == "/host:CPU" for plane, _line in lines)

    labelled = []
    red = tracing.reduce(FIXTURE, 0.0492, 0.1571,
                         lambda lo, n: labelled.append(lo) or "gap")
    assert red["busy_s"] == pytest.approx(0.096776767, rel=1e-9)
    assert red["busy_s_by_device"] == [red["busy_s"]]
    assert red["op_events"] == 2549
    assert red["window_s"] == pytest.approx(0.1079)
    assert 0 < red["busy_s"] < red["window_s"]
    # the fused scan step is one program, and nearly all of the time
    name, seconds = red["programs"][0]
    assert name.startswith("jit_run_batch(")
    assert seconds == pytest.approx(0.096760921, rel=1e-9)
    # operations are named by what stands before the HLO's equals sign;
    # the loop holds the fusions it runs, so the union is not their sum
    assert red["device_ops"][0][0] == "%while.20"
    assert all(" = " not in n for n, _s in red["device_ops"])
    assert sum(s for _n, s in red["device_ops"]) > red["busy_s"]
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    # gaps come longest first, on the trace's clock: before the first
    # operation (submit, parse, plan, dispatch) and after the last (the
    # result's way back), then microseconds between operations
    gaps = [s for _n, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert gaps[0] == pytest.approx(0.1571 - 0.150197, abs=1e-4)
    assert gaps[1] == pytest.approx(0.053420543 - 0.0492, abs=1e-6)
    assert gaps[2] < 1e-5
    assert all(0.0492 <= lo < 0.1571 for lo in labelled)
    assert red["first_op_offset_s"] == pytest.approx(0.053420543)
    # what lies outside the stretch is cut off: a stretch inside the
    # fused step's loop is all busy, and never more than its length
    inside = tracing.reduce(FIXTURE, 0.06, 0.10)
    assert inside["busy_s"] == pytest.approx(0.04, rel=1e-3)
    assert inside["busy_s"] <= inside["window_s"]
