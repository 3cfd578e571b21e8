"""tools/trace_account.py joins the two planes of a profiler recording
(ISSUE 41): the device's idle gaps are put down to the innermost of
the program's annotations in flight on the host plane. The attribution
is a pure function of intervals, tried here on synthetic ones; the
whole account on a recording made on the CPU (a host plane and no
device plane) and on the TPU recording the benchmark's tests keep."""

import glob
import os

import jax
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runner import LocalRunner
from tests.tpch_queries import QUERIES
from tools import trace_account as TA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a statement of 100 ms: two launches, an eager dispatch nested in
# nothing, the flags' pull, a phase on another thread
NOTES = [
    ("execute:q1", 0.000, 0.100),
    ("attempt:a0", 0.001, 0.099),
    ("launch:fused_batch", 0.010, 0.030),
    ("xfer:h2d:batch-starts", 0.012, 0.014),
    ("eager:num-rows", 0.040, 0.050),
    ("wait:overflow-flag", 0.060, 0.090),
    ("encode", 0.100, 0.101),
]
SITES = [n for n in NOTES
         if TA._kind(n[0]) in TA.SITE_KINDS + TA.PHASES]


def _by_span(rows):
    return {r["span"]: (pytest.approx(r["idle_s"]), r["gaps"])
            for r in rows}


IDLE = {
    # gaps -> {annotation: (idle seconds, gaps it has a part of)}
    "one_gap_inside_one_annotation": (
        [(0.062, 0.070)], {"wait:overflow-flag": (0.008, 1)}),
    "a_gap_split_across_two_annotations": (
        [(0.045, 0.065)],
        {"eager:num-rows": (0.005, 1), "execute": (0.010, 1),
         "wait:overflow-flag": (0.005, 1)}),
    "nested_annotations_give_the_innermost": (
        [(0.011, 0.016)],
        {"launch:fused_batch": (0.003, 1),
         "xfer:h2d:batch-starts": (0.002, 1)}),
    "no_annotation_is_execute": (
        [(0.031, 0.039), (0.091, 0.099)], {"execute": (0.016, 2)}),
    "a_phase_past_the_statements_end": (
        [(0.095, 0.1005)], {"execute": (0.005, 1), "encode": (0.0005, 1)}),
    "many_gaps_sum_by_annotation": (
        [(0.000, 0.012), (0.013, 0.0135), (0.020, 0.041),
         (0.0605, 0.061), (0.070, 0.100)],
        {"execute": (0.010 + 0.010 + 0.010, 3),
         "launch:fused_batch": (0.002 + 0.010, 2),
         "xfer:h2d:batch-starts": (0.0005, 1),
         "eager:num-rows": (0.001, 1),
         "wait:overflow-flag": (0.0005 + 0.020, 2)}),
    "no_gap": ([], {}),
}


@pytest.mark.parametrize("case", sorted(IDLE))
def test_attribute_idle_on_synthetic_intervals(case):
    gaps, want = IDLE[case]
    rows = TA.attribute_idle(gaps, SITES)
    assert {r["span"]: (r["idle_s"], r["gaps"]) for r in rows} == {
        k: (pytest.approx(s), n) for k, (s, n) in want.items()}
    # the parts are the gaps, and the costliest comes first
    assert sum(r["idle_s"] for r in rows) == pytest.approx(
        sum(b - a for a, b in gaps))
    assert [r["idle_s"] for r in rows] == sorted(
        (r["idle_s"] for r in rows), reverse=True)


def test_the_container_is_not_a_site():
    """``attempt:a0`` covers nearly the whole statement: were it a
    candidate, every bare stretch would be put down to it. The sites
    are the recorder's interval kinds."""
    from presto_tpu.obs.trace import INTERVAL_KINDS

    assert set(TA.SITE_KINDS) == set(INTERVAL_KINDS)
    assert ("attempt:a0", 0.001, 0.099) not in SITES
    with_container = TA.attribute_idle([(0.031, 0.039)], NOTES)
    assert [r["span"] for r in with_container] == ["attempt:a0"]


@pytest.mark.parametrize("lo,hi,want", [
    (0.0, 0.100, ["execute", "launch:fused_batch",
                  "xfer:h2d:batch-starts", "launch:fused_batch",
                  "execute", "eager:num-rows", "execute",
                  "wait:overflow-flag", "execute"]),
    (0.013, 0.045, ["xfer:h2d:batch-starts", "launch:fused_batch",
                    "execute", "eager:num-rows"]),
    (0.031, 0.035, ["execute"]),
])
def test_innermost_cuts_a_stretch_into_pieces(lo, hi, want):
    pieces = TA.innermost(SITES, lo, hi, "execute")
    assert [n for n, _a, _b in pieces] == want
    # the pieces tile the stretch
    assert pieces[0][1] == lo and pieces[-1][2] == hi
    assert all(a[2] == b[1] for a, b in zip(pieces, pieces[1:]))


def test_annotations_begun_together_give_the_shorter():
    notes = [("launch:a", 0.0, 0.010), ("wait:b", 0.0, 0.004)]
    assert [n for n, _a, _b in TA.innermost(notes, 0.0, 0.010, "x")] == [
        "wait:b", "launch:a"]


def test_statement_idle_counts_the_ends_and_the_bare_stretches():
    ops = [("%fusion.1", 0.015, 0.040), ("%while.2", 0.035, 0.058),
           ("%fusion.3", 0.058, 0.088),
           # of another statement: outside this one
           ("%fusion.9", 0.150, 0.160)]
    line = NOTES + [
        ("PjitFunction(convert_element_type)", 0.0315, 0.0335),
        ("PjitFunction(_reduce_sum)", 0.041, 0.046),   # under eager
        ("PjitFunction(fused_batch)", 0.011, 0.029),   # under launch
        ("DevicePut", 0.092, 0.093),
        ("DevicePut", 0.095, 0.0955)]
    got = TA.statement_idle(0.0, 0.100, ops, NOTES, line)
    assert got["idle_gaps"] == 2
    assert got["idle_s"] == pytest.approx(0.015 + 0.012)
    assert got["idle_before_first_op_s"] == pytest.approx(0.015)
    assert got["idle_after_last_op_s"] == pytest.approx(0.012)
    assert _by_span(got["idle_by_host_span"]) == {
        "execute": (0.010 + 0.010, 2),
        "launch:fused_batch": (0.003, 1),
        "xfer:h2d:batch-starts": (0.002, 1),
        "wait:overflow-flag": (0.002, 1)}
    # 100 ms less the launch's 20, the eager's 10 and the wait's 30
    assert got["uncovered_s"] == pytest.approx(0.040)
    assert got["uncovered_by_event"] == [
        {"event": "PjitFunction(convert_element_type)",
         "s": pytest.approx(0.002), "events": 1},
        {"event": "DevicePut", "s": pytest.approx(0.0015), "events": 2}]
    # a statement in which the device did nothing is one gap
    none = TA.statement_idle(0.0, 0.100, [], NOTES, ())
    assert (none["idle_gaps"], none["idle_s"]) == (1, pytest.approx(0.1))
    assert none["idle_before_first_op_s"] == pytest.approx(0.1)
    assert sum(r["idle_s"] for r in none["idle_by_host_span"]) == \
        pytest.approx(0.1)


SPANS = [
    {"kind": "launch", "name": "fused", "startUs": 100, "endUs": 1100},
    {"kind": "launch", "name": "project", "startUs": 1200, "endUs": 1500},
    {"kind": "eager", "name": "num-rows", "startUs": 1500, "endUs": 1600},
    {"kind": "xfer", "name": "h2d:batch-starts", "startUs": 50,
     "endUs": 90},
    {"kind": "xfer", "name": "d2h:array", "startUs": 2000, "endUs": 2450},
    {"kind": "wait", "name": "drain", "startUs": 2500, "endUs": 2600},
]


@pytest.mark.parametrize("phases,want", [
    ([{"kind": "plan", "startUs": 0, "endUs": 40},
      {"kind": "execute", "startUs": 40, "endUs": 3000, "spans": SPANS}],
     (2, 1300, 550)),
    ([{"kind": "execute", "startUs": 40, "endUs": 3000}], (0, 0, 0)),
    (None, (0, 0, 0)),
])
def test_spans_against_counters(phases, want):
    got = TA.spans_against_counters(
        phases, {"device_launches": 2.0, "dispatch_wall_us": 1301.0})
    assert (got["launch_spans"], got["launch_span_us"],
            got["wait_span_us"]) == want
    assert got["device_launches"] == 2.0
    assert got["dispatch_wall_us"] == 1301.0
    assert got["device_wait_us"] is None


@pytest.fixture(scope="module")
def cpu_recording(tmp_path_factory):
    """A traced Q6 at SF0.01 recorded with the harness's profiler
    options: on the CPU the recording has the host plane alone."""
    out = str(tmp_path_factory.mktemp("recording"))
    runner = LocalRunner({"tpch": TpchConnector(0.01)},
                         page_rows=1 << 13)
    runner.session.set("query_trace_enabled", True)
    runner.execute(QUERIES[6])  # compiled before the recording
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        runner.execute(QUERIES[6])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb"))
    return path, runner.last_trace, runner.executor


def test_account_of_a_cpu_recording_names_the_hosts_sites(cpu_recording):
    path, trace, ex = cpu_recording
    acc = TA.account(path)
    (line,) = acc["host_lines_with_annotations"]
    assert line["annotations"]["launch"] == ex.device_launches
    # the num_rows() pair of every kept count, and the states' concat
    assert line["annotations"]["eager"] >= ex.row_counts_eager > 0
    (st,) = acc["statements"]
    assert st["annotation"] == f"execute:{trace.query_id}"
    assert st["launches"] == ex.device_launches
    # no device plane: the statement is one gap, put down to what the
    # host was doing, and the parts are the whole
    assert st["first_chip"] is None and st["idle_gaps"] == 1
    length = st["end_s"] - st["start_s"]
    assert st["idle_s"] == pytest.approx(length)
    parts = {r["span"]: r for r in st["idle_by_host_span"]}
    assert sum(r["idle_s"] for r in parts.values()) == \
        pytest.approx(length, rel=1e-6)
    labels = {f"launch:{sp.name}" for sp in trace.spans()
              if sp.kind == "launch"}
    assert labels and labels <= set(parts)
    assert {"eager:num-rows", "execute"} <= set(parts)
    assert any(name.startswith("wait:") for name in parts)
    assert not any(name.startswith("attempt") for name in parts)
    # the bare stretches are what no site annotation covers
    assert st["uncovered_s"] == pytest.approx(
        parts["execute"]["idle_s"], rel=1e-6)
    assert all(not e["event"].startswith(("launch:", "wait:", "eager:"))
               for e in st["uncovered_by_event"])


def test_the_recordings_annotations_are_the_traces_spans(cpu_recording):
    """The two planes hold the same intervals: each ``launch`` span of
    the query trace lies inside the statement's ``execute:<id>``
    annotation as its ``launch:<label>`` annotation does, and is at
    least as long (it is timed around the annotation)."""
    from benchmarks.harness import trace as tracing

    path, trace, _ex = cpu_recording
    host = next(p for p in tracing.load(path).planes
                if p.name == "/host:CPU")
    notes = sorted((e.start_ns, e.name, e.duration_ns)
                   for ln in host.lines for e in ln.events
                   if e.name.startswith("launch:"))
    spans = sorted((sp.t0, sp.name, sp.t1 - sp.t0)
                   for sp in trace.spans() if sp.kind == "launch")
    assert [n for _t, n, _d in notes] == [
        f"launch:{n}" for _t, n, _d in spans]
    for (_t, _n, ns), (_t0, name, s) in zip(notes, spans):
        assert -1e-6 < s - ns / 1e9 < 2e-3, (name, ns, s)


def test_account_reads_a_tpu_recording_without_annotations():
    """The recording of PR 24 (before the annotations): device time by
    program, no statement, and nothing raised."""
    acc = TA.account(os.path.join(
        REPO, "tests", "benchmark", "data",
        "q6_sf1_one_statement.xplane.pb.gz"))
    assert acc["program_runs"] > 0 and acc["op_events"] > 0
    assert acc["statements"] == []
