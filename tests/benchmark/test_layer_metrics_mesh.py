"""The three per-layer metrics of the mesh cell (ISSUE 29), each reader
on a hand-made ``ctx``: programs summed over four chips give a per-chip
number, uneven chips give their excess, and a run with nothing to read
(no trace, one chip, a program that names no mesh program or lacks the
counter) gives None and no error."""

import os
import types

import pytest

from benchmarks.harness import manifest

MESH = {"exchange_device_ms_per_query": ("device_trace", "exchange"),
        "exchange_launches_per_query": ("program_counter", "exchange"),
        "busiest_chip_excess": ("device_trace", "device")}


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


def _ctx(samples=(), concurrent=False, start=None, end=None, trace=None,
         shares=()):
    return {"samples": list(samples), "concurrent": concurrent,
            "metrics_start": start or {}, "metrics_end": end or {},
            "trace": trace,
            "traced_statements": [(None, s) for s in shares],
            "peaks": {}, "scan_bytes": lambda st: 0}


def _trace(programs, busy):
    return {"busy_s": sum(busy) / len(busy), "busy_s_by_device": busy,
            "window_s": 5.0, "programs": programs}


def test_the_manifest_lists_the_mesh_cell_alone_for_each():
    m = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    entries = {e["name"]: e for e in m["per_layer"]}
    for name, (source, layer) in MESH.items():
        e = entries[name]
        assert (e["source"], e["layer"], e["moves"], e["better"]) == (
            source, layer, "query_geomean_ms", "lower")
        assert e["workloads"] == ["mesh4_join_solo"]
    cell = manifest.load_cell("mesh4_join_solo")
    assert cell.chips == 4 and cell.config["chips"] == 4
    assert set(MESH) <= {p["name"] for p in cell.per_layer}
    assert {e["name"] for e in cell.end_to_end} == {
        "query_geomean_ms", "setup_s"}
    one_chip = {p["name"] for p in
                manifest.load_cell("join_sf1_solo").per_layer}
    assert not set(MESH) & one_chip
    # the same statements as the one-chip join cell, the same guarantees
    serial = manifest.load_cell("join_sf1_solo")
    assert cell.traffic == serial.traffic
    assert cell.config["guarantees"] == serial.config["guarantees"]
    for key in ("config_properties", "catalogs"):
        assert cell.config[key] == serial.config[key]


def test_exchange_time_is_one_chips_share_of_the_familys_programs():
    # XLA Modules events summed by name over FOUR chips: 0.4 s and
    # 0.08 s of exchange programs are 0.12 s on one chip; two whole
    # statements and half of a third ran in the stretch
    programs = [["jit_d_scan(11)", 2.0],
                ["jit_d_repartition(12)", 0.4],
                ["jit_d_agg_final(13)", 0.3],
                ["jit_d_gather(14)", 0.08],
                ["jit_topn_local(15)", 0.01],
                ["jit_gather(16)", 0.5]]   # an eager jnp gather: not it
    ctx = _ctx(trace=_trace(programs, [1.0, 1.0, 1.0, 1.0]),
               shares=(1.0, 1.0, 0.5))
    assert _read("exchange_device_ms_per_query", ctx) == pytest.approx(
        (0.4 + 0.08) / 4 * 1e3 / 2.5)


@pytest.mark.parametrize("ctx", [
    _ctx(),                                                  # no trace
    _ctx(trace=_trace([["jit_d_gather(1)", 0.1]], [1.0] * 4)),  # no share
    _ctx(trace=_trace([["jit_fused_batch(1)", 2.0]], [1.0]),
         shares=(1.0,)),                          # one chip, no exchange
    _ctx(trace=_trace([["jit_body(1)", 2.0], ["jit_program(2)", 1.0]],
                      [1.0] * 4), shares=(1.0,)),  # unnamed mesh programs
], ids=["no_trace", "no_statement", "no_exchange", "unnamed"])
def test_exchange_time_with_nothing_to_read(ctx):
    assert _read("exchange_device_ms_per_query", ctx) is None


def test_exchange_launches_read_the_gauge_per_statement():
    after = [{"device_launches": 40.0, "exchange_launches": 7.0},
             {"device_launches": 36.0, "exchange_launches": 5.0}]
    samples = [types.SimpleNamespace(metrics_after=m) for m in after]
    assert _read("exchange_launches_per_query",
                 _ctx(samples)) == pytest.approx(6.0)
    totals = _ctx(samples, concurrent=True,
                  start={"exchange_launches": 10.0},
                  end={"exchange_launches": 34.0})
    assert _read("exchange_launches_per_query",
                 totals) == pytest.approx(12.0)
    # a program without the counter (the parent commit)
    old = [types.SimpleNamespace(metrics_after={"device_launches": 1.0})]
    assert _read("exchange_launches_per_query", _ctx(old)) is None
    assert _read("exchange_launches_per_query", _ctx()) is None


def test_busiest_chip_excess_is_max_over_mean():
    uneven = _ctx(trace=_trace([], [4.0, 2.0, 2.0, 2.0]))
    assert _read("busiest_chip_excess", uneven) == pytest.approx(60.0)
    even = _ctx(trace=_trace([], [4.3638] * 4))
    assert _read("busiest_chip_excess", even) == pytest.approx(0.0)


@pytest.mark.parametrize("ctx", [
    _ctx(),
    _ctx(trace=_trace([], [4.8])),
    _ctx(trace=_trace([], [0.0, 0.0, 0.0, 0.0])),
    _ctx(trace={"busy_s": 1.0, "window_s": 5.0, "programs": []}),
], ids=["no_trace", "one_chip", "all_idle", "no_per_chip_list"])
def test_busiest_chip_excess_with_nothing_to_read(ctx):
    assert _read("busiest_chip_excess", ctx) is None
