"""The per-layer readers that read the program's own spans, counters and
program names (ISSUE 25's, and ISSUE 28's ``agg_device_ms_per_query`` in
the place of ``join_device_ms_per_query``), each on a synthetic ``ctx``:
what it reads where the program has the span or counter, and that it
gives nothing, without raising, where the program has not (an older
commit, on which the driver lays these files too)."""

import os
import types

import pytest

from benchmarks.harness import manifest

NEW = {
    "admission_wait_ms": ("contended_geomean_ms", ["mixed_sf1_sf10_c8"]),
    "frontend_ms": ("query_geomean_ms", None),
    "device_launches_per_query": ("query_geomean_ms", None),
    "dispatch_ms_per_query": ("query_geomean_ms", None),
    "device_wait_ms_per_query": ("query_geomean_ms", None),
    "agg_device_ms_per_query": ("query_geomean_ms", ["join_sf1_solo"]),
    "program_build_s": ("setup_s", None),
    "program_fetch_s": ("setup_s", None),
}


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _phases(queue_us, parse_us, plan_us, execute_us, encode_us):
    out, t = [], 0
    for kind, us in (("queue", queue_us), ("parse", parse_us),
                     ("plan", plan_us), ("execute", execute_us),
                     ("encode", encode_us)):
        out.append({"kind": kind, "startUs": t, "endUs": t + us,
                    "attrs": {}})
        t += us
    return out


def _sample(query_info=None, metrics_after=None, latency_s=1.0):
    return types.SimpleNamespace(
        query_info=query_info, metrics_after=metrics_after,
        latency_s=latency_s)


def _ctx(samples, concurrent=False, start=None, end=None, trace=None,
         shares=()):
    return {"samples": samples, "concurrent": concurrent,
            "metrics_start": start or {}, "metrics_end": end or {},
            "trace": trace,
            "traced_statements": [(None, s) for s in shares],
            "peaks": {}, "scan_bytes": lambda st: 0}


def test_the_manifest_has_the_eight_readers_at_the_end():
    """Each of these readers that the manifest still has is there with
    its ``moves`` and its cells, wherever it stands: later PRs append
    metrics, and may append a cell to a list."""
    m = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entries = {e["name"]: e for e in m["per_layer"]}
    assert len(entries) == len(m["per_layer"])
    for name in set(NEW) & set(entries):
        e = entries[name]
        moves, workloads = NEW[name]
        assert e["moves"] == moves
        assert ("workloads" in e) == (workloads is not None)
        assert set(workloads or ()) <= set(e.get("workloads", ()))
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = {w["name"]: {m_["name"] for m_ in
                         manifest.load_cell(w["name"]).per_layer}
             for w in m["workloads"]}
    assert "admission_wait_ms" in cells["mixed_sf1_sf10_c8"]
    assert "admission_wait_ms" not in cells["scan_sf10_solo"]
    assert "agg_device_ms_per_query" in cells["join_sf1_solo"]
    assert "agg_device_ms_per_query" not in cells["scan_sf10_solo"]
    for name in ("scan_sf10_solo", "join_sf1_solo"):
        assert {"frontend_ms", "device_launches_per_query",
                "dispatch_ms_per_query",
                "device_wait_ms_per_query"} <= cells[name]
    for name in cells:
        assert {"program_build_s", "program_fetch_s"} <= cells[name]


def test_span_readers_take_the_median_of_the_phases():
    samples = [
        _sample({"phases": _phases(4000, 300, 1200, 90000, 500)}),
        _sample({"phases": _phases(9000, 500, 2000, 80000, 400)}),
        _sample({"phases": _phases(6500, 400, 1600, 70000, 450)}),
        _sample(None),                       # its info was never read
    ]
    ctx = _ctx(samples)
    assert _read("admission_wait_ms", ctx) == pytest.approx(6.5)
    assert _read("frontend_ms", ctx) == pytest.approx(2.0)


def test_frontend_sums_every_plan_phase_of_a_statement():
    # a DML rewrite plans twice: two plan phases in one statement
    phases = _phases(0, 200, 1000, 5000, 100) + [
        {"kind": "plan", "startUs": 6300, "endUs": 6800, "attrs": {}}]
    assert _read("frontend_ms", _ctx([_sample({"phases": phases})])) == \
        pytest.approx(1.7)


def test_counter_readers_serial_path_reads_the_gauge_per_statement():
    after = [{"device_launches": 10.0, "dispatch_wall_us": 2000.0,
              "device_wait_us": 800000.0},
             {"device_launches": 30.0, "dispatch_wall_us": 4000.0,
              "device_wait_us": 600000.0}]
    ctx = _ctx([_sample(metrics_after=m) for m in after])
    assert _read("device_launches_per_query", ctx) == pytest.approx(20.0)
    assert _read("dispatch_ms_per_query", ctx) == pytest.approx(3.0)
    assert _read("device_wait_ms_per_query", ctx) == pytest.approx(700.0)


def test_counter_readers_concurrent_path_reads_the_total_over_n():
    start = {"device_launches": 100.0, "dispatch_wall_us": 1e4,
             "device_wait_us": 1e6}
    end = {"device_launches": 180.0, "dispatch_wall_us": 5e4,
           "device_wait_us": 9e6}
    ctx = _ctx([_sample() for _ in range(4)], concurrent=True,
               start=start, end=end)
    assert _read("device_launches_per_query", ctx) == pytest.approx(20.0)
    assert _read("dispatch_ms_per_query", ctx) == pytest.approx(10.0)
    assert _read("device_wait_ms_per_query", ctx) == pytest.approx(2000.0)


def test_agg_device_time_is_the_agg_familys_programs():
    trace = {"busy_s": 4.9, "window_s": 5.0, "programs": [
        ["jit_fused_batch(14)", 3.0], ["jit_agg_final(11)", 0.3],
        ["jit_agg_partial(12)", 0.25], ["jit_partfilter(13)", 0.5],
        ["jit_gagg_final(17)", 0.05], ["jit_topn_local(18)", 0.1],
        ["jit_gather(15)", 0.2], ["jit__unknown(16)", 0.1]]}
    ctx = _ctx([_sample()], trace=trace, shares=(0.1, 0.15))
    assert _read("agg_device_ms_per_query", ctx) == \
        pytest.approx(600.0 / 0.25)
    assert _read("agg_device_ms_per_query",
                 _ctx([_sample()], trace=None, shares=(1.0,))) is None
    assert _read("agg_device_ms_per_query",
                 _ctx([_sample()], trace=trace, shares=())) is None
    # a stretch in which no program of the family ran: nothing, not 0
    scan_only = dict(trace, programs=trace["programs"][:1])
    assert _read("agg_device_ms_per_query",
                 _ctx([_sample()], trace=scan_only, shares=(1.0,))) is None


def test_a_program_ranked_below_the_tenth_still_counts(monkeypatch):
    """The reduction hands the readers every program of the stretch, not
    the ten with most time (the retired ``join_device_ms_per_query`` saw
    those only): aggregation programs that rank twelfth and thirteenth
    are read, while the breakdown's lists stay at ten."""
    from benchmarks.harness import trace as tracing

    modules, t = [], 0.0
    for i in range(11):             # eleven scan variants, 0.10-0.20 s
        modules.append((f"jit_fused_batch({i})", t, t + 0.1 + i / 100))
        t += 0.25
    modules += [("jit_agg_final(99)", t, t + 0.002),
                ("jit_agg_partial(98)", t + 0.01, t + 0.011)]
    ops = [(f"%fusion.{i}", a, b) for i, (_n, a, b) in enumerate(modules)]
    monkeypatch.setattr(
        tracing, "read_planes",
        lambda path: {0: {tracing.OPS_LINE: ops,
                          tracing.MODULES_LINE: modules}})
    red = tracing.reduce("recorded.xplane.pb", 0.0, 3.0)
    assert len(red["programs"]) == 13 > tracing.TOP_N
    seconds = [s for _n, s in red["programs"]]
    assert seconds == sorted(seconds, reverse=True)
    assert [n for n, _s in red["programs"][-2:]] == [
        "jit_agg_final(99)", "jit_agg_partial(98)"]
    assert len(red["device_ops"]) == tracing.TOP_N
    ctx = _ctx([_sample()], trace=red, shares=(1.0, 0.5))
    assert _read("agg_device_ms_per_query", ctx) == pytest.approx(2.0)


def test_setup_split_reads_the_totals_at_the_windows_start():
    start = {"program_trace_wall_s": 40.0, "program_lower_wall_s": 9.5,
             "program_retrieval_wall_s": 12.25, "compile_wall_s": 0.0}
    end = {k: v * 2 for k, v in start.items()}
    ctx = _ctx([_sample()], start=start, end=end)
    assert _read("program_build_s", ctx) == pytest.approx(49.5)
    assert _read("program_fetch_s", ctx) == pytest.approx(12.25)


@pytest.mark.parametrize("name", list(NEW))
@pytest.mark.parametrize("concurrent", [False, True])
def test_a_program_without_the_span_or_counter_gives_nothing(
        name, concurrent):
    """The parent commit: /v1/query/{id} has stages and no phases,
    /metrics has the older counters only, programs are unnamed."""
    older = {"program_launches": 7.0, "d2h_bytes": 457.0}
    info = {"elapsedTimeMillis": 812, "stages": [
        {"startMs": 1, "endMs": 806, "tasks": []}]}
    trace = {"busy_s": 4.9, "window_s": 5.0, "programs": [
        ["jit__unknown(5456584955919556897)", 4.39],
        ["jit_gather(1)", 0.19], ["jit_argsort(2)", 0.17]]}
    ctx = _ctx([_sample(info, dict(older)), _sample(info, dict(older))],
               concurrent=concurrent, start=dict(older), end=dict(older),
               trace=trace, shares=(0.16,))
    value = _read(name, ctx)
    assert value is None
