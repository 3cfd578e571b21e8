"""The three readers of the ``execute`` phase's spans (ISSUE 41):
``execute_self_ms_per_query``, ``first_launch_ms`` and
``execute_tail_ms``, each on hand-built ``query_info``: what it reads
from ``phases[*].spans`` of /v1/query/{id}, and that it gives nothing,
without raising, where the statement launched nothing or the program is
one from before the spans (the parent commit, on which the driver lays
these files too)."""

import os
import types

import pytest

from benchmarks.harness import manifest

READERS = {
    "execute_self_ms_per_query": "executor",
    "first_launch_ms": "executor",
    "execute_tail_ms": "transfer",
}
SOLO = ("scan_sf10_solo", "join_sf1_solo", "mesh4_join_solo",
        "scan_sf10_resident_solo")


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _span(kind, name, start, end):
    return {"kind": kind, "name": name, "startUs": start, "endUs": end}


def _info(execute, spans, with_spans=True):
    """/v1/query/{id} of a statement whose ``execute`` phase is
    ``execute`` = (start, end) microseconds from submission."""
    lo, hi = execute
    phase = {"kind": "execute", "startUs": lo, "endUs": hi, "attrs": {}}
    if with_spans:
        phase["spans"] = list(spans)
    return {"elapsedTimeMillis": (hi + 500) // 1000, "phases": [
        {"kind": "queue", "startUs": 0, "endUs": 300, "attrs": {}},
        {"kind": "parse", "startUs": 300, "endUs": 800, "attrs": {}},
        {"kind": "plan", "startUs": 800, "endUs": lo, "attrs": {}},
        phase,
        {"kind": "encode", "startUs": hi, "endUs": hi + 90, "attrs": {}}]}


def _ctx(infos):
    return {"samples": [types.SimpleNamespace(
        query_info=i, metrics_after=None, latency_s=1.0) for i in infos],
        "concurrent": False, "metrics_start": {}, "metrics_end": {},
        "trace": None, "traced_statements": [], "peaks": {},
        "scan_bytes": lambda st: 0}


# a mesh Q5 as PERF.md's recording has it, rounded: first dispatch
# 1.3 ms in, seven launches, the flags' pull waits for the device, then
# five pulls and the counts
Q5 = _info((2000, 42000), [
    _span("launch", "d_fused_batch", 3300, 5300),
    _span("xfer", "h2d:round-starts", 3000, 3200),
    _span("launch", "d_agg_partial", 5400, 7000),
    _span("launch", "d_repartition", 7100, 9000),
    _span("launch", "sort_page", 9100, 17000),
    _span("xfer", "d2h:overflow-flag", 17100, 34700),
    _span("xfer", "d2h:decode-valid", 34800, 35300),
    _span("xfer", "d2h:array", 35400, 35900),
    _span("xfer", "d2h:row-counts", 38000, 40100),
])

CASES = {
    # name: (query_info, self ms, first launch ms, tail ms)
    "a_mesh_q5": (Q5, 40.0 - (0.2 + 2.0 + 1.6 + 1.9 + 7.9 + 17.6 + 0.5
                              + 0.5 + 2.1), 1.3, 7.3),
    "nested_children_count_once": (_info((1000, 11000), [
        _span("launch", "fused_batch", 2000, 6000),
        # an eager dispatch and a staging inside the launch's interval
        _span("eager", "num-rows", 2500, 3000),
        _span("xfer", "h2d:batch-starts", 3000, 3500),
        _span("xfer", "d2h:overflow-flag", 7000, 9000)]),
        10.0 - 4.0 - 2.0, 1.0, 2.0),
    "overlapping_children_are_a_union": (_info((0, 10000), [
        _span("launch", "fused", 1000, 5000),
        _span("eager", "num-rows", 4000, 7000),
        _span("wait", "drain", 6500, 8000)]),
        10.0 - 7.0, 1.0, 2.0),
    "a_launch_on_another_thread": (_info((0, 10000), [
        # the cross-query batch leader's launch, timed on its thread
        # while this statement's driver waits: it overlaps the wait
        # and reaches past the phase's start
        _span("launch", "xq_batch", -500, 4000),
        _span("wait", "drain", 1000, 6000),
        _span("xfer", "d2h:array", 6000, 6400)]),
        10.0 - 6.4, -0.5, 3.6),
    "spans_past_the_phases_end_are_clipped": (_info((1000, 5000), [
        _span("launch", "fused", 2000, 3000),
        _span("xfer", "d2h:array", 4500, 5600)]),
        4.0 - 1.0 - 0.5, 1.0, 0.0),
    "an_empty_span_covers_nothing": (_info((0, 4000), [
        _span("launch", "project", 1000, 1000),
        _span("xfer", "d2h:array", 2000, 3000)]),
        3.0, 1.0, 1.0),
    "staging_after_the_last_launch_is_no_read": (_info((0, 9000), [
        _span("launch", "fused", 500, 2500),
        _span("xfer", "h2d:cache-replay", 3000, 3500),
        _span("xfer", "d2h:overflow-flag", 4000, 6000),
        _span("xfer", "d2h:array", 6500, 7000)]),
        9.0 - 2.0 - 0.5 - 2.0 - 0.5, 0.5, 3.0),
    "a_pull_between_launches_is_not_the_end": (_info((0, 20000), [
        _span("launch", "fused", 1000, 2000),
        _span("xfer", "d2h:skew-count", 2000, 9000),
        _span("launch", "agg_final", 9500, 10000),
        _span("xfer", "d2h:overflow-flag", 10000, 15000)]),
        20.0 - 1.0 - 7.0 - 0.5 - 5.0, 1.0, 5.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_three_readers_on_one_statement(case):
    info, self_ms, first_ms, tail_ms = CASES[case]
    ctx = _ctx([info])
    assert _read("execute_self_ms_per_query", ctx) == \
        pytest.approx(self_ms)
    assert _read("first_launch_ms", ctx) == pytest.approx(first_ms)
    assert _read("execute_tail_ms", ctx) == pytest.approx(tail_ms)


NOTHING = {
    "no_launch_at_all": (_info((1000, 3000), [
        _span("xfer", "d2h:array", 1500, 2000)]), 1.5),
    "launches_and_no_read_after_them": (_info((1000, 3000), [
        _span("xfer", "d2h:array", 1200, 1300),
        _span("launch", "project", 1500, 2000)]), 1.4),
    "no_span_of_any_kind": (_info((1000, 3000), []), 2.0),
}


@pytest.mark.parametrize("case", sorted(NOTHING))
def test_a_statement_without_the_spans_a_reader_needs(case):
    """A whole-plan cache hit launches nothing: the phase's self time
    is still read; the launch and the tail have nothing to read."""
    info, self_ms = NOTHING[case]
    ctx = _ctx([info])
    assert _read("execute_self_ms_per_query", ctx) == \
        pytest.approx(self_ms)
    assert _read("execute_tail_ms", ctx) is None
    if case != "launches_and_no_read_after_them":
        assert _read("first_launch_ms", ctx) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_median_over_the_windows_statements(name):
    def one(shift):
        return _info((1000, 9000 + shift), [
            _span("launch", "fused", 2000 + shift, 3000 + shift),
            _span("xfer", "d2h:overflow-flag", 3000 + shift,
                  6000 + shift),
            _span("xfer", "d2h:array", 7000 + shift, 7500 + shift)])

    infos = [one(0), one(400), one(1000), None,
             _info((0, 5000), [], with_spans=False)]
    want = {"execute_self_ms_per_query": 8.4 - 4.5,
            "first_launch_ms": 1.4, "execute_tail_ms": 3.0}
    assert _read(name, _ctx(infos)) == pytest.approx(want[name])


OLDER = {
    "phases_without_spans": _info((800, 90000), [], with_spans=False),
    "stages_and_no_phases": {"elapsedTimeMillis": 812, "stages": [
        {"startMs": 1, "endMs": 806, "tasks": []}]},
    "tracing_off": {"elapsedTimeMillis": 45, "stages": [],
                    "spanCount": 0},
    "info_never_read": None,
}


@pytest.mark.parametrize("program", sorted(OLDER))
@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_spans_gives_nothing(name, program):
    info = OLDER[program]
    assert _read(name, _ctx([info, info])) is None
    assert _read(name, _ctx([])) is None


def test_the_manifest_has_the_three_readers_for_the_solo_cells():
    m = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entries = {e["name"]: e for e in m["per_layer"]}
    for name, layer in READERS.items():
        e = entries[name]
        assert e == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "query_geomean_ms"}
    for w in m["workloads"]:
        cell = {p["name"] for p in
                manifest.load_cell(w["name"]).per_layer}
        assert (set(READERS) <= cell) == (w["name"] in SOLO), w["name"]
        assert set(READERS) <= cell or not set(READERS) & cell
