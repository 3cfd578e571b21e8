"""The five per-layer readers of the stored-join cell (ISSUE 44), each
on a synthetic ``ctx``: what it reads where the program has the span,
the counter or the program labels, and that it gives nothing, without
raising, where the program has not (the parent commit, on which the
driver lays these files too) or where no stored join ran."""

import os
import types

import pytest

from benchmarks.harness import manifest

CELL = "join_sf1_resident_solo"
READERS = {
    "join_build_ms_per_query": ("program_span", "executor", "lower"),
    "join_build_rows_per_query": ("program_counter", "executor", "lower"),
    "join_build_device_ms_per_query": ("device_trace", "kernels", "lower"),
    "join_probe_device_ms_per_query": ("device_trace", "kernels", "lower"),
    "join_roofline": ("device_trace", "kernels", "higher"),
}
PEAKS = {"hbm_bytes_per_s": 819e9}


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _info(build_spans, with_spans=True):
    phase = {"kind": "execute", "startUs": 1000, "endUs": 900000,
             "attrs": {}}
    if with_spans:
        phase["spans"] = [
            {"kind": "launch", "name": "stored_build",
             "startUs": 1500, "endUs": 1900}] + [
            {"kind": "join_build", "name": table, "startUs": lo,
             "endUs": hi} for table, lo, hi in build_spans]
    return {"phases": [{"kind": "plan", "startUs": 0, "endUs": 1000,
                        "attrs": {}}, phase]}


def _ctx(infos=(), after=(), programs=None, shares=(), scan_bytes=0):
    samples = [types.SimpleNamespace(query_info=i, metrics_after=None,
                                     latency_s=1.0) for i in infos]
    samples += [types.SimpleNamespace(query_info=None, metrics_after=m,
                                      latency_s=1.0) for m in after]
    trace = None if programs is None else {
        "programs": programs, "busy_s": 9.0, "busy_s_by_device": [9.0]}
    return {"samples": samples, "concurrent": False,
            "metrics_start": {}, "metrics_end": {}, "trace": trace,
            "traced_statements": [(f"st{i}", s)
                                  for i, s in enumerate(shares)],
            "peaks": PEAKS, "scan_bytes": lambda st: scan_bytes}


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_the_reader_for_the_stored_join_cell(name):
    m = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    source, layer, better = READERS[name]
    assert entry == {
        "name": name, "unit": entry["unit"], "better": better,
        "source": source, "layer": layer, "moves": "query_geomean_ms",
        "workloads": [CELL]}
    assert entry["unit"] == {"join_roofline": "%",
                             "join_build_rows_per_query": "rows"}.get(
        name, "ms")
    reported = {w["name"] for w in m["workloads"]
                if name in {p["name"] for p in
                            manifest.load_cell(w["name"]).per_layer}}
    assert reported == {CELL}


def test_join_build_ms_is_the_spans_sum_a_statement_averaged():
    q5 = _info([("supplier", 2000, 2400), ("nation", 2500, 2700),
                ("orders", 3000, 4400)])
    q3 = _info([("orders", 2000, 3000)])
    assert _read("join_build_ms_per_query", _ctx([q5, q3])) == \
        pytest.approx((2.0 + 1.0) / 2)
    # a statement without a stored join is left out, not counted as 0
    assert _read("join_build_ms_per_query",
                 _ctx([q3, _info([])])) == pytest.approx(1.0)


@pytest.mark.parametrize("infos", [
    [], [None], [{}], [_info([], with_spans=False)], [_info([])]])
def test_join_build_ms_gives_nothing_without_the_span(infos):
    assert _read("join_build_ms_per_query", _ctx(infos)) is None


def test_join_build_rows_reads_the_gauge_per_statement():
    after = [{"join_build_rows": 1660030.0},
             {"join_build_rows": 1650000.0}]
    assert _read("join_build_rows_per_query", _ctx(after=after)) == \
        pytest.approx(1655015.0)
    assert _read("join_build_rows_per_query",
                 _ctx(after=[{"device_launches": 9.0}])) is None
    assert _read("join_build_rows_per_query",
                 _ctx(after=[{"join_build_rows": 0.0}])) is None


PROGRAMS = [("jit_stored_probe_batch(123)", 6.0),
            ("jit_stored_probe(5)", 0.5),
            ("jit_stored_build(77)", 0.25), ("jit_stored_build(78)", 0.15),
            ("jit_agg_final(9)", 1.0), ("jit_stream_compact1(3)", 0.5),
            ("jit_partfilter(4)", 0.0), ("jit_gather", 0.25)]


def test_build_and_probe_device_time_split_the_join_family_by_label():
    ctx = _ctx(programs=PROGRAMS, shares=[1.0, 1.0, 0.5])
    assert _read("join_build_device_ms_per_query", ctx) == \
        pytest.approx(0.4 * 1e3 / 2.5)
    assert _read("join_probe_device_ms_per_query", ctx) == \
        pytest.approx(6.5 * 1e3 / 2.5)


def test_join_roofline_is_the_touched_bytes_over_build_and_probe_time():
    ctx = _ctx(programs=PROGRAMS, shares=[1.0, 1.0, 0.5],
               scan_bytes=819e6)
    # 2.5 statements x 819 MB at 819 GB/s = 2.5 ms, over 6.9 s
    assert _read("join_roofline", ctx) == pytest.approx(
        100 * 2.5e-3 / 6.9)
    assert 0 < _read("join_roofline", ctx) < 100


@pytest.mark.parametrize("name", [
    "join_build_device_ms_per_query", "join_probe_device_ms_per_query",
    "join_roofline"])
@pytest.mark.parametrize("ctx", [
    _ctx(),                                             # no trace
    _ctx(programs=PROGRAMS),                            # no statement
    _ctx(programs=[("jit_fused_batch(1)", 3.0),         # generated joins
                   ("jit_agg_final(9)", 1.0)], shares=[1.0],
         scan_bytes=1e9),
], ids=["untraced", "no_statement_in_the_stretch", "no_join_program"])
def test_device_readers_give_nothing_where_nothing_ran(name, ctx):
    assert _read(name, ctx) is None
