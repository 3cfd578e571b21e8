"""``execute`` is tiled like the statement is (ISSUE 41): every program
launch, every wait on the device and every eager dispatch of the driver
thread is a span of its attempt in microseconds, cut from the clock
readings the counters ``dispatch_wall_us`` and ``device_wait_us`` sum,
and /v1/query/{id} lists them under the ``execute`` phase. A served Q6
on one device and a served Q5 over four (the benchmark's cells at
rehearsal scale), then the recorder and the choke points alone."""

import pytest

from benchmarks.harness import manifest, serve
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import xfer as XF
from presto_tpu.exec.executor import Executor
from presto_tpu.obs import SPAN_KINDS, attach, detach
from presto_tpu.obs.trace import INTERVAL_KINDS, QueryTrace

DEPLOYMENTS = {
    # served statement id by cell: one device, and a mesh of four
    "q6_one_device": ("scan_sf10_solo", "q6_sf10"),
    "q5_mesh_of_four": ("mesh4_join_solo", "q5_sf1"),
}


@pytest.fixture(scope="module", params=sorted(DEPLOYMENTS))
def statement(request, tmp_path_factory):
    """The cell's coordinator at rehearsal scale; the statement served
    twice (loaded, then steady) with its /v1/query/{id} and the serial
    path's /metrics after it, then once with tracing off."""
    cell_name, sid = DEPLOYMENTS[request.param]
    cell = manifest.load_cell(cell_name)
    etc = str(tmp_path_factory.mktemp(request.param) / "etc")
    serve.write_etc(etc, cell.config, rehearse=True)
    srv = serve.Served(etc, cell.chips)
    try:
        st = cell.statements[sid][0]
        client = srv.client(st.catalog)
        for _ in range(2):
            res = client.execute(st.sql)
            assert res.state == "FINISHED", res.error
        info, metrics = srv.query_info(res.query_id), srv.metrics()
        client.session_properties["query_trace_enabled"] = "false"
        off = client.execute(st.sql)
        assert off.state == "FINISHED", off.error
        yield {"mesh": cell.chips > 1, "info": info, "metrics": metrics,
               "off_info": srv.query_info(off.query_id),
               "off_metrics": srv.metrics()}
    finally:
        srv.stop()


def _execute(info):
    (phase,) = [p for p in info["phases"] if p["kind"] == "execute"]
    return phase


def _of(spans, kind):
    return [s for s in spans if s["kind"] == kind]


def _us(spans):
    return sum(s["endUs"] - s["startUs"] for s in spans)


def _pulls_and_waits(spans):
    return [s for s in spans if s["kind"] == "wait" or (
        s["kind"] == "xfer" and s["name"].startswith("d2h:"))]


def test_one_launch_span_a_device_launch(statement):
    spans = _execute(statement["info"])["spans"]
    launches = _of(spans, "launch")
    assert len(launches) == statement["metrics"]["device_launches"] > 0
    attempt = _tree(statement["info"], "attempt")[-1]
    by_label = {}
    for s in launches:
        by_label[s["name"]] = by_label.get(s["name"], 0) + 1
    assert by_label == attempt["attrs"]["launches"]


def test_launch_spans_sum_to_the_dispatch_wall(statement):
    """Each span is the call's own clock readings, in nanoseconds; the
    counter rounds each to a microsecond and so does the span's end and
    start: a microsecond a launch at most."""
    launches = _of(_execute(statement["info"])["spans"], "launch")
    assert _us(launches) == pytest.approx(
        statement["metrics"]["dispatch_wall_us"], abs=len(launches) + 1)


def test_pull_and_wait_spans_sum_to_the_device_wait(statement):
    waits = _pulls_and_waits(_execute(statement["info"])["spans"])
    assert len(waits) >= 3   # the result's pulls and the counts'
    assert _us(waits) == pytest.approx(
        statement["metrics"]["device_wait_us"], abs=len(waits) + 1)
    names = {s["name"] for s in waits}
    assert "d2h:row-counts" in names
    # a statement that deferred a flag reads them all in one pull
    assert ("d2h:overflow-flag" in names) == statement["mesh"]


def test_the_execute_phase_lists_real_intervals_in_microseconds(statement):
    phase = _execute(statement["info"])
    spans = phase["spans"]
    assert {s["kind"] for s in spans} <= set(INTERVAL_KINDS)
    assert not _of(spans, "attempt") and not _of(spans, "operator")
    assert all(set(s) == {"kind", "name", "startUs", "endUs"}
               for s in spans)
    assert [s["startUs"] for s in spans] == sorted(
        s["startUs"] for s in spans)
    for s in spans:
        assert phase["startUs"] <= s["startUs"] <= s["endUs"] \
            <= phase["endUs"], (s, phase)
    # the driver thread's spans do not overlap: what they leave bare is
    # the phase's self time, and it is a part of the phase
    for prev, nxt in zip(spans, spans[1:]):
        assert prev["endUs"] <= nxt["startUs"] + 1, (prev, nxt)
    bare = phase["endUs"] - phase["startUs"] - _us(spans)
    assert 0 < bare < phase["endUs"] - phase["startUs"]


def test_eager_spans_are_the_row_counts_one_device_keeps(statement):
    """Over a mesh every kept row count rides in a launch (ISSUE 37):
    no eager dispatch; one device calls page.num_rows() a boundary."""
    eager = _of(_execute(statement["info"])["spans"], "eager")
    counts = [s for s in eager if s["name"] == "num-rows"]
    assert len(counts) == statement["metrics"]["row_counts_eager"]
    assert bool(counts) != statement["mesh"]
    assert {s["name"] for s in eager} <= {"num-rows", "concat-states"}


def _tree(info, kind):
    """The spans of this kind in stages[*].tasks[*].spans."""
    return [sp for stage in info["stages"] for task in stage["tasks"]
            for sp in task["spans"] if sp["kind"] == kind]


def test_every_span_lies_inside_its_attempt_in_the_tree(statement):
    """stages[*].tasks[*].spans carries the same spans beside the
    attempt and the operators, with startUs / endUs beside the whole
    milliseconds; the tree counts from the instant planning begins,
    the phases from submission."""
    info = statement["info"]
    (attempt,) = _tree(info, "attempt")
    listed = _execute(info)["spans"]
    inside = [sp for k in INTERVAL_KINDS for sp in _tree(info, k)]
    assert len(inside) == len(listed) > 0
    for sp in inside:
        assert attempt["startUs"] <= sp["startUs"] <= sp["endUs"] \
            <= attempt["endUs"], (sp, attempt)
        assert abs(sp["startMs"] - sp["startUs"] / 1e3) <= 0.501
        assert abs(sp["endMs"] - sp["endUs"] / 1e3) <= 0.501
    # the two clocks differ by one offset: the tree's origin
    offsets = {a["startUs"] - b["startUs"] for a, b in zip(
        sorted(listed, key=lambda s: (s["startUs"], s["endUs"])),
        sorted(inside, key=lambda s: (s["startUs"], s["endUs"])))}
    assert max(offsets) - min(offsets) <= 1, offsets
    assert info["spanCount"] >= len(inside) + 3


def test_tracing_off_records_nothing(statement):
    info = statement["off_info"]
    assert info["spanCount"] == 0 and "phases" not in info
    assert statement["off_metrics"]["trace_spans"] == 0
    assert statement["off_metrics"]["device_launches"] == \
        statement["metrics"]["device_launches"]


# --------------------------------------------- the recorder, by itself
def _traced_executor():
    ex = Executor({"tpch": TpchConnector(0.01)})
    tr = QueryTrace("q-spans")
    attach(ex, tr)
    return ex, tr


def _program(label="fused", donates=False):
    from presto_tpu.exec import programs as PG

    return PG.Program(label, lambda x: x + 1, donates=donates)


def test_count_launch_records_one_span_under_the_open_attempt():
    import jax.numpy as jnp

    from presto_tpu.exec import programs as PG

    ex, tr = _traced_executor()
    phase = tr.phase("execute", "Output")
    ex._attempt_span = tr.begin("attempt", "a0", parent=phase)
    prog = _program()
    PG.launch(ex, prog, jnp.arange(4))
    PG.launch(ex, prog, jnp.arange(4))
    tr.end(ex._attempt_span)
    tr.end(phase)
    launches = [sp for sp in tr.spans() if sp.kind == "launch"]
    assert [sp.name for sp in launches] == ["fused", "fused"]
    assert all(sp.parent_id == ex._attempt_span.span_id
               for sp in launches)
    assert ex.trace_spans == 2 and ex.device_launches == 2
    assert sum(int(round(sp.dur() * 1e6)) for sp in launches) == \
        pytest.approx(ex.dispatch_wall_us, abs=3)
    (listed,) = [p["spans"] for p in tr.phases()
                 if p["kind"] == "execute"]
    assert [s["kind"] for s in listed] == ["launch", "launch"]


def test_count_launch_with_tracing_off_allocates_nothing(monkeypatch):
    import jax.numpy as jnp

    from presto_tpu.exec import programs as PG

    ex = Executor({"tpch": TpchConnector(0.01)})
    assert ex.trace is None

    def boom(*a, **kw):
        raise AssertionError("a span with tracing off")

    monkeypatch.setattr(Executor, "span_ending_now", boom)
    by_label = ex._launches_by_label
    PG.launch(ex, _program(), jnp.arange(4))
    prev = XF.swap_sink(ex)
    try:
        with XF.device_wait("drain"):
            pass
        with XF.eager("num-rows"):
            pass
        XF.to_host(jnp.arange(4), label="array")
    finally:
        XF.swap_sink(prev)
    assert ex.trace_spans == 0 and ex.device_launches == 1
    assert ex._launches_by_label is by_label and not by_label
    assert ex.device_wait_us >= 0 and ex.d2h_transfers == 1


@pytest.mark.parametrize("site,kind,counted", [
    (XF.device_wait, "wait", True),
    (XF.eager, "eager", False),
])
def test_a_timed_site_is_a_span_of_its_kind(site, kind, counted):
    import time

    ex, tr = _traced_executor()
    ex._attempt_span = tr.begin("attempt", "a0")
    prev = XF.swap_sink(ex)
    try:
        with site("here"):
            time.sleep(0.002)
    finally:
        XF.swap_sink(prev)
    (sp,) = [s for s in tr.spans() if s.kind == kind]
    assert sp.name == "here" and sp.parent_id == ex._attempt_span.span_id
    assert 0.002 <= sp.dur() < 0.5
    assert (ex.device_wait_us >= 2000) == counted
    if counted:
        assert int(round(sp.dur() * 1e6)) == pytest.approx(
            ex.device_wait_us, abs=1)
    assert ex.trace_spans == 1
    # with no executor bound to the thread: annotated, nothing recorded
    with site("nowhere"):
        pass
    assert len(tr.spans()) == 3   # the root, the attempt, the one span


def test_transfer_spans_hang_under_the_open_attempt():
    import jax.numpy as jnp
    import numpy as np

    ex, tr = _traced_executor()
    ex._attempt_span = tr.begin("attempt", "a0")
    prev = XF.swap_sink(ex)
    try:
        XF.to_device(np.arange(8), label="batch-starts")
        XF.to_host(jnp.arange(8), label="array")
    finally:
        XF.swap_sink(prev)
    xfers = [s for s in tr.spans() if s.kind == "xfer"]
    assert [s.name for s in xfers] == ["h2d:batch-starts", "d2h:array"]
    assert all(s.parent_id == ex._attempt_span.span_id for s in xfers)
    assert [s.attrs["bytes"] for s in xfers] == [64, 8 * jnp.arange(
        8).dtype.itemsize]
    # outside an attempt a crossing hangs under the root, and the
    # execute phase does not list it
    ex._attempt_span = None
    prev = XF.swap_sink(ex)
    try:
        XF.to_host(jnp.arange(8), label="array")
    finally:
        XF.swap_sink(prev)
    assert tr.spans()[-1].parent_id == tr.root.span_id


def test_phases_list_the_execute_phases_descendants_only():
    """Spans of a plan-time subquery's run (an ``execute`` span nested
    under ``plan``) are no part of the statement's ``execute`` phase;
    the container and the per-node operator totals are not listed; an
    open span ends now."""
    tr = QueryTrace("q-tree", anchor_mono=None)
    tr.phase("queue", at=0.0)
    plan = tr.phase("plan")
    sub = tr.begin("execute", "Aggregate", parent=plan)
    sub_att = tr.begin("attempt", "a0", parent=sub)
    tr.complete("launch", "gagg_final", tr.now(), tr.now(),
                parent=sub_att)
    tr.end(sub_att)
    tr.end(sub)
    phase = tr.phase("execute", "Output")
    att = tr.begin("attempt", "a0", parent=phase)
    t = tr.now()
    tr.complete("launch", "fused", t, t + 0.001, parent=att)
    tr.complete("xfer", "d2h:array", t + 0.002, t + 0.003, parent=att,
                bytes=8)
    tr.complete("operator", "TableScan", att.t0, att.t0 + 0.5,
                parent=att)
    tr.complete("resident_load", "lineitem", t, t + 0.0005, parent=att)
    tr.complete("xfer", "d2h:array", t, t + 0.001)   # under the root
    still_open = tr.begin("eager", "num-rows", parent=att)
    by_kind = {p["kind"]: p for p in tr.phases()}
    assert "spans" not in by_kind["plan"] and "spans" not in \
        by_kind["queue"]
    listed = by_kind["execute"]["spans"]
    assert sorted((s["kind"], s["name"]) for s in listed) == [
        ("eager", "num-rows"), ("launch", "fused"),
        ("resident_load", "lineitem"), ("xfer", "d2h:array")]
    (open_one,) = [s for s in listed if s["kind"] == "eager"]
    assert open_one["endUs"] >= open_one["startUs"] == int(round(
        still_open.t0 * 1e6))
    (launch,) = [s for s in listed if s["kind"] == "launch"]
    assert launch["endUs"] - launch["startUs"] in (999, 1000, 1001)


def test_the_new_kinds_are_declared():
    assert {"launch", "wait", "eager"} <= set(SPAN_KINDS)
    assert set(INTERVAL_KINDS) <= set(SPAN_KINDS)
    assert not {"attempt", "operator"} & set(INTERVAL_KINDS)


def test_a_run_hands_back_the_attempt_it_found():
    """execute() leaves no attempt span behind: the next statement's
    trace numbers its spans anew, and a span recorded between
    statements must not hang under a stale one."""
    from presto_tpu.runner import LocalRunner

    runner = LocalRunner({"tpch": TpchConnector(0.01)},
                         page_rows=1 << 13)
    runner.session.set("query_trace_enabled", True)
    runner.execute("select count(*) from nation")
    assert runner.executor._attempt_span is None
    tr = runner.last_trace
    assert [sp.kind for sp in tr.spans()].count("launch") == \
        runner.executor.device_launches
    detach(runner.executor, tr)
