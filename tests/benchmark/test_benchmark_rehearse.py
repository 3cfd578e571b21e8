"""The benchmark's command end to end under --rehearse (SF0.01, CPU):
every cell, the refusal without a TPU, a cell added as files only, a
four-device configuration, and the controls that have to come out as
not correct. A rehearsal prints counts and no metric."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness import manifest, reference

ROOT = manifest.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(args, cwd=ROOT, devices=1, pythonpath=None, cache_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PRESTO_TPU_LOCK_SANITIZER", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", [
    "scan_sf10_solo", "join_sf1_solo", "mixed_sf1_sf10_c8"])
def test_cell_rehearses(name):
    proc = run_cli(["--workload", name, "--seed", "3000000001",
                    "--seconds", "2", "--trace", "0", "--rehearse",
                    "--control"])
    result = last_line(proc)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # a CPU run writes nothing under a metric's name
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    checks = {l["check"]: l for l in lines if l.get("phase") == "check"}
    assert checks["statements_differing_from_reference"]["value"] == 0
    assert checks["statements_differing_from_reference"]["compared"] == \
        result["attempted"]
    assert checks["programs_compiled_in_window"]["value"] == 0
    # the control (float32 sums; a dropped grace partition) in the served
    # rows' place is not correct
    (control,) = [l for l in lines if l.get("phase") == "control"]
    assert control["control_correct"] is False and control["differing"]


def test_a_first_run_compiles_in_a_child_process(tmp_path):
    """With an empty persistent cache the cell's programs compile in a
    child process that has ended before the serving process looks for a
    device, and the serving process compiles nothing; the next run
    starts no child."""
    args = ["--workload", "mixed_sf1_sf10_c8", "--seed", "2600000003",
            "--seconds", "1", "--trace", "0", "--rehearse"]
    phases = []
    for _ in range(2):
        proc = run_cli(args, cache_dir=str(tmp_path / "cache"))
        assert last_line(proc)["correct"] is True
        phases.append([json.loads(l) for l in
                       proc.stdout.strip().splitlines()])
    first, second = ({l["phase"]: l for l in lines if "phase" in l}
                     for lines in phases)
    assert len(first["start"]["compile_in_child"]) == 8
    assert first["compile_child"]["rc"] == 0
    assert first["compile"]["programs_compiled"] > 0
    order = [l.get("phase") for l in phases[0]]
    assert order.index("compile_child") < order.index("device")
    assert first["warm"]["programs_compiled"] == 0
    assert first["warm"]["program_cache_hits"] > 0
    assert second["start"]["compile_in_child"] == []
    assert "compile_child" not in second
    assert second["warm"]["programs_compiled"] == 0


def test_no_tpu_no_result():
    proc = run_cli(["--workload", "scan_sf10_solo", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_rehearsal_refuses_a_trace():
    proc = run_cli(["--workload", "scan_sf10_solo", "--seed", "1",
                    "--seconds", "1", "--trace", "1", "--rehearse"])
    assert proc.returncode != 0 and '"metrics"' not in proc.stdout


NEW_MANIFEST = {
    "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"],
    "run_seconds": 2,
    "configs": [{"name": "tiny_mesh4", "source": "a test's own",
                 "file": "benchmarks/configs/tiny_mesh4.json",
                 "reduced": [], "why": "four devices, one process"}],
    "workloads": [{"name": "count_mesh4", "config": "tiny_mesh4",
                   "traffic": "count_two_clients", "chips": 4,
                   "why": "a cell added as files only"}],
    "end_to_end": [
        {"name": "query_geomean_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "rows_per_query", "unit": "rows", "better": "lower",
         "source": "program_counter", "layer": "client and protocol",
         "moves": "query_geomean_ms"}],
}
NEW_FILES = {
    "configs/tiny_mesh4.json": json.dumps({
        "name": "tiny_mesh4", "source": "a test's own", "chips": 4,
        "config_properties": {"default-catalog": "t", "page-rows": "4096"},
        "catalogs": {"t": {"connector.name": "tpch",
                           "tpch.scale-factor": "0.01"}},
        "reduced": {}, "assumed": {}, "guarantees": ["exact"]}),
    "traffic/count_two_clients.json": json.dumps({
        "loop": "closed", "stop": "statement", "traced_seconds": 1,
        "statements": {"big_orders": {
            "template": "big_orders", "catalog": "t", "class": "batch",
            "variants": [{"price": "100000"}, {"price": "200000"}]}},
        "clients": [{"count": 2, "deck": ["big_orders"],
                     "variants": "all", "order": "shuffle"}]}),
    "statements/big_orders.sql":
        "select o_orderpriority, count(*) as n, sum(o_totalprice) as t\n"
        "from orders where o_totalprice > {price}\n"
        "group by o_orderpriority order by o_orderpriority\n",
    "references/big_orders.py":
        'KIND = "sqlite"\n'
        'TABLES = {"orders": ("o_orderpriority", "o_totalprice")}\n'
        'INDEXES = ()\n\n\n'
        'def oracle_sql(params, control=False):\n'
        '    cut = int(params["price"]) * 100\n'
        '    return ("SELECT o_orderpriority, COUNT(*), "\n'
        '            "SUM(o_totalprice) FROM orders WHERE o_totalprice > "\n'
        '            f"{cut} GROUP BY 1 ORDER BY 1")\n',
    "layer_metrics/rows_per_query.py":
        'def read(ctx):\n'
        '    return sum(len(s.rows) for s in ctx["samples"]) / '
        'len(ctx["samples"])\n',
}


def test_a_cell_is_added_as_files_only(tmp_path):
    """A configuration (four devices), a traffic mix, a statement, its
    reference and a per-layer metric are added to a copy of the
    benchmark as new files and entries; no file that was there changes,
    and the harness runs them."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for rel, body in NEW_FILES.items():
        assert not (bench / rel).exists()
        (bench / rel).write_text(body)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(NEW_MANIFEST))
    assert all(p.read_bytes() == b for p, b in before.items())

    proc = run_cli(["--workload", "count_mesh4", "--seed", "2200000001",
                    "--seconds", "2", "--trace", "0", "--rehearse"],
                   cwd=str(tmp_path), devices=4, pythonpath=ROOT)
    result = last_line(proc)
    assert result["correct"] is True and result["attempted"] >= 2
    assert result["device"]["count"] == 4
    start = json.loads(proc.stdout.splitlines()[0])
    assert start["work_dir"].startswith(str(tmp_path))

    # the new per-layer metric's reader, found by its name
    script = (
        "import types\n"
        "from benchmarks.harness import layers, manifest\n"
        "cell = manifest.load_cell('count_mesh4')\n"
        "s = types.SimpleNamespace(rows=[[1], [2], [3]])\n"
        "print(layers.read_all(cell, {'samples': [s]}, print))\n")
    env = dict(os.environ, PYTHONPATH=f"{tmp_path}{os.pathsep}{ROOT}")
    out = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "'rows_per_query': {'value': 3.0, 'unit': 'rows'}" in out.stdout


def test_a_broken_timed_path_is_not_correct(monkeypatch, tmp_path):
    """Everything but the look for a chip, in this process, with an
    answer altered where the server renders it: correct comes out
    false."""
    from presto_tpu.server import http_server

    real = http_server._json_row

    def off_by_one(row, types=None):
        out = real(row, types)
        for i, v in enumerate(out):
            if isinstance(v, int) and not isinstance(v, bool):
                out[i] = v + 1
                break
        return out

    monkeypatch.setattr(cell_mod, "WORK_DIR", str(tmp_path))
    sound = cell_mod.run("scan_sf10_solo", 2100000011, 1.0, False,
                         rehearse=True)
    assert sound["correct"] is True and sound["failed"] == 0
    monkeypatch.setattr(http_server, "_json_row", off_by_one)
    broken = cell_mod.run("scan_sf10_solo", 2100000011, 1.0, False,
                          rehearse=True)
    assert broken["correct"] is False
    assert 0 < broken["failed"] <= broken["attempted"]


def test_a_run_that_compiles_in_its_window_is_not_correct(
        monkeypatch, tmp_path):
    from presto_tpu import compilecache

    monkeypatch.setattr(cell_mod, "WORK_DIR", str(tmp_path))
    real = compilecache.delta
    monkeypatch.setattr(
        compilecache, "delta",
        lambda since: dict(real(since), programs_compiled=1))
    result = cell_mod.run("scan_sf10_solo", 5, 1.0, False, rehearse=True)
    assert result["correct"] is False and result["failed"] == 0


def test_controls_differ_from_the_exact_references(tmp_path):
    """The control of every template (float32 sums for the scans, a
    dropped grace partition for the joins) differs from the exact
    reference over the same generated rows."""
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(0.01)
    props = {"t": {"tpch.scale-factor": "0.01"}}
    statements = []
    for name in ("scan_sf10_solo", "join_sf1_solo"):
        for variants in manifest.load_cell(name).statements.values():
            statements += [
                manifest.Statement(s.sid, s.template, "t", s.klass,
                                   s.variant, s.params, s.sql)
                for s in variants]
    got = {c: reference.answers(statements, {"t": conn}, props,
                                str(tmp_path), control=c,
                                log=lambda **kw: None)
           for c in (False, True)}
    assert len(got[False]) == 8
    for key, rows in got[False].items():
        # Q3 keeps ten rows of many: a dropped partition need not hold
        # one of them. Every pass of the join cell has a Q5, which sums
        # over every row and always differs.
        differs = reference.mismatch(got[True][key], rows)
        assert rows and (differs or key.startswith("q3_")), key
    # and the cache gives the same answers back
    again = reference.answers(statements, {}, props, str(tmp_path),
                              log=lambda **kw: None)
    assert again == got[False]
