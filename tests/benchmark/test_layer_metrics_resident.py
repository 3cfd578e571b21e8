"""The two per-layer readers of the stored-table cell (ISSUE 33), each
on a synthetic ``ctx``: what it reads where the program has the counter,
and that it gives nothing, without raising, where the program has not
(the parent commit, on which the driver lays these files too) or where
no table is stored."""

import os
import types

import pytest

from benchmarks.harness import manifest

CELL = "scan_sf10_resident_solo"
READERS = {
    "resident_bytes_scanned_per_query": ("query_geomean_ms", "kernels"),
    "resident_load_s": ("setup_s", "storage"),
}


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _ctx(after=(), concurrent=False, start=None, end=None):
    samples = [types.SimpleNamespace(metrics_after=m, query_info=None,
                                     latency_s=1.0) for m in after]
    return {"samples": samples, "concurrent": concurrent,
            "metrics_start": start or {}, "metrics_end": end or {},
            "trace": None, "traced_statements": [], "peaks": {},
            "scan_bytes": lambda st: 0}


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_lists_the_reader_for_the_resident_cell(name):
    m = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    moves, layer = READERS[name]
    assert (entry["moves"], entry["layer"]) == (moves, layer)
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert entry["workloads"] == [CELL]
    reported = {w["name"] for w in m["workloads"]
                if name in {p["name"] for p in
                            manifest.load_cell(w["name"]).per_layer}}
    assert reported == {CELL}


def test_the_resident_cell_is_the_scan_cell_but_for_its_catalog():
    """The controlled pair: same traffic file, statements, properties
    and end-to-end metrics; the catalog alone differs."""
    scan = manifest.load_cell("scan_sf10_solo")
    res = manifest.load_cell(CELL)
    assert res.traffic == scan.traffic
    assert [st.sql for st in res.every] == [st.sql for st in scan.every]
    assert res.config["config_properties"] == \
        scan.config["config_properties"]
    assert [m["name"] for m in res.end_to_end] == \
        [m["name"] for m in scan.end_to_end]
    assert res.config["catalogs"]["tpch"] == {
        "connector.name": "resident", "resident.inner": "tpch",
        "tpch.scale-factor": "10", "resident.tables": "lineitem"}
    assert set(scan.config["guarantees"]) < set(res.config["guarantees"])
    assert "scan_roofline" in {m["name"] for m in res.per_layer}


def test_resident_bytes_scanned_reads_the_gauge_per_statement():
    after = [{"resident_bytes_scanned": 4.7e9},
             {"resident_bytes_scanned": 3.0e9}]
    assert _read("resident_bytes_scanned_per_query",
                 _ctx(after)) == pytest.approx(3.85e9)
    # the concurrent server's is a process total over the window
    got = _read("resident_bytes_scanned_per_query", _ctx(
        after, concurrent=True,
        start={"resident_bytes_scanned": 1e9},
        end={"resident_bytes_scanned": 9e9}))
    assert got == pytest.approx(4e9)


@pytest.mark.parametrize("after", [
    [{"device_launches": 9.0}],                 # the parent: no counter
    [{"resident_bytes_scanned": 0.0}],          # a generated scan
    [None],                                     # an untraced run
    [],
])
def test_resident_bytes_scanned_gives_nothing_without_the_counter(after):
    assert _read("resident_bytes_scanned_per_query", _ctx(after)) is None


def test_resident_load_s_reads_the_total_at_the_windows_start():
    assert _read("resident_load_s", _ctx(
        start={"resident_load_wall_us": 12_500_000.0,
               "resident_loads": 1.0},
        end={"resident_load_wall_us": 99e6})) == pytest.approx(12.5)


@pytest.mark.parametrize("start", [
    {},                                         # the parent: no counter
    {"resident_load_wall_us": 0.0},             # nothing stored
])
def test_resident_load_s_gives_nothing_without_a_load(start):
    assert _read("resident_load_s", _ctx(start=start)) is None
