"""The benchmark's command end to end under --rehearse (SF0.01, CPU):
every cell of the manifest, the refusal without a TPU, a cell added as
files only, the repository's own manifest grown by such a cell with no
edit to a file that is there, a four-device configuration whose first
run compiles over the mesh in its child, and the controls that have to
come out as not correct. A rehearsal prints counts and no metric."""

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness import manifest, reference, serve

ROOT = manifest.ROOT
# the driver's five, and last what was compared beside its limit
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]
CELL_CHIPS = {w["name"]: w["chips"] for w in manifest.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]}


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


@pytest.mark.parametrize("name", list(CELL_CHIPS))
def test_cell_rehearses(name):
    proc = run_cli(["--workload", name, "--seed", "3000000001",
                    "--seconds", "2", "--trace", "0", "--rehearse",
                    "--control"], devices=CELL_CHIPS[name])
    result = last_line(proc)
    assert list(result) == RESULT_KEYS
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
    # each number compared beside its limit: in the result's line, and
    # the last lines of standard error
    assert set(result["checks"]) == set(checks)
    assert all(c["ok"] for c in result["checks"].values())
    said = proc.stderr.strip().splitlines()[-len(checks):]
    assert [l.split(":")[0] for l in said] == [
        f"check {name}" for name in result["checks"]]
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


def test_a_child_that_a_signal_ended_is_started_once_more(tmp_path):
    """The TPU compiler has segfaulted in a compile child (PERF.md,
    PR 28): the child is started a second time, and only a second death
    or a plain failure ends the run."""
    flag = tmp_path / "died_once"
    dies_once = (
        "import os, signal, sys\n"
        f"flag = {str(flag)!r}\n"
        "if os.path.exists(flag):\n"
        "    sys.exit(0)\n"
        "open(flag, 'w').close()\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    lines = []
    serve.compile_in_child([sys.executable, "-c", dies_once],
                           lambda **kw: lines.append(kw))
    assert [(l["attempt"], l["rc"]) for l in lines] == [(1, -9), (2, 0)]
    for body, rcs in (("import sys; sys.exit(3)", [3]),
                      ("import os; os.kill(os.getpid(), 9)", [-9, -9])):
        lines.clear()
        with pytest.raises(SystemExit, match=f"code {rcs[-1]}"):
            serve.compile_in_child([sys.executable, "-c", body],
                                   lambda **kw: lines.append(kw))
        assert [l["rc"] for l in lines] == rcs


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


def _add_the_new_cell(tmp_path, new_manifest, *more_dirs):
    """A copy of ``benchmarks/`` (and of ``more_dirs``) under tmp_path
    with NEW_FILES beside what was there and ``new_manifest`` as its
    BENCHMARK.json; no copied file may have changed."""
    ignore = shutil.ignore_patterns("__pycache__")
    for rel in ("benchmarks",) + more_dirs:
        shutil.copytree(os.path.join(ROOT, rel), tmp_path / rel,
                        ignore=ignore)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*")
              if p.is_file()}
    for rel, body in NEW_FILES.items():
        assert not (tmp_path / "benchmarks" / rel).exists()
        (tmp_path / "benchmarks" / rel).write_text(body)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new_manifest))
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_cell_is_added_as_files_only(tmp_path):
    """A configuration (four devices), a traffic mix, a statement, its
    reference and a per-layer metric are added to a copy of the
    benchmark as new files and entries; no file that was there changes,
    and the harness runs them."""
    _add_the_new_cell(tmp_path, NEW_MANIFEST)

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


def _pytest_in(copy, *args):
    """pytest over the copy's own tests/benchmark files, importing the
    copy's ``benchmarks`` and the repository's program."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{copy}{os.pathsep}{ROOT}")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *args,
         os.path.join("tests", "benchmark", "test_benchmark_harness.py"),
         os.path.join("tests", "benchmark",
                      "test_layer_metrics_program_spans.py")],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    return proc.stdout


def test_the_manifest_grows_with_no_edit_to_a_file_that_is_there(tmp_path):
    """What a later PR does: to the repository's own BENCHMARK.json it
    appends a four-chip configuration, a cell on it, a traffic mix, a
    statement with its reference and a per-layer metric, as entries and
    new files. Every test of the manifest and of the readers passes on
    the result, unedited, and tests the new cell too. A test that pins
    the manifest's cells, their chips or the order of its metrics fails
    here."""
    grown = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _add_the_new_cell(tmp_path, grown, "tests/benchmark")
    collected = re.search(r"(\d+) tests? collected",
                          _pytest_in(tmp_path, "--collect-only"))
    for key in ("configs", "workloads", "per_layer"):
        grown[key] += NEW_MANIFEST[key]
    (cell,) = NEW_MANIFEST["workloads"]
    next(e for e in grown["end_to_end"] if e["name"] ==
         "query_geomean_ms")["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))
    passed = re.search(r"(\d+) passed", _pytest_in(tmp_path))
    assert int(passed.group(1)) > int(collected.group(1))
    found = manifest.load_json(str(tmp_path / "BENCHMARK.json"))
    assert [w["name"] for w in found["workloads"]][-1] == cell["name"]


def test_a_four_device_first_run_compiles_over_the_mesh_in_its_child(
        tmp_path):
    """The four-device cell with an empty persistent cache: the child
    compiles what a server over the mesh loads, so the serving process
    compiles nothing and finds every program in the cache; a mesh
    program did run; the next run starts no child. (A child that builds
    its runners without the mesh compiles one chip's programs, and the
    server then compiles every mesh program itself.)"""
    _add_the_new_cell(tmp_path, NEW_MANIFEST)
    args = ["--workload", "count_mesh4", "--seed", "2800000007",
            "--seconds", "1", "--trace", "0", "--rehearse"]
    phases = []
    for _ in range(2):
        proc = run_cli(args, cwd=str(tmp_path), devices=4,
                       pythonpath=ROOT, cache_dir=str(tmp_path / "cache"))
        result = last_line(proc)
        assert result["correct"] is True and result["device"]["count"] == 4
        phases.append([json.loads(l) for l in
                       proc.stdout.strip().splitlines()])
    first, second = ({l["phase"]: l for l in lines if "phase" in l}
                     for lines in phases)
    assert first["start"]["compile_in_child"] == [
        "big_orders#0", "big_orders#1"]
    assert first["compile_child"]["rc"] == 0
    assert first["compile"]["programs_compiled"] > 0
    order = [l.get("phase") for l in phases[0]]
    assert order.index("compile_child") < order.index("device")
    assert first["warm"]["programs_compiled"] == 0
    assert first["warm"]["program_cache_hits"] > 0
    # the statements went over the mesh: an exchange compiled onto it
    # ran, and none fell back to the spool
    for run in (first, second):
        assert run["mesh"]["mesh_local_exchanges"] + \
            run["mesh"]["ici_exchanges"] > 0
        assert run["mesh"]["mesh_exchange_fallbacks"] == 0
    assert second["start"]["compile_in_child"] == []
    assert "compile_child" not in second
    assert second["warm"]["programs_compiled"] == 0


def test_markers_tell_a_mesh_from_one_chip(tmp_path, monkeypatch):
    """Two configurations equal but for their chips share no marker (the
    second would otherwise find the first's and start no child); the
    same configuration finds its own again."""
    from presto_tpu import compilecache

    monkeypatch.setattr(compilecache, "enable_persistent_cache",
                        lambda *a, **kw: None)
    monkeypatch.setattr(compilecache, "cache_dir", lambda: str(tmp_path))
    one = manifest.load_cell("join_sf1_solo")
    four = dataclasses.replace(one, chips=4)
    every = one.every
    at_one = serve._warm_markers(one, every, False)
    at_four = serve._warm_markers(four, every, False)
    assert set(at_one) == set(at_four) == {st.key for st in every}
    assert len(set(at_one.values()) | set(at_four.values())) == \
        2 * len(every)
    assert serve._warm_markers(one, every, False) == at_one
    assert all(os.path.dirname(p) == str(tmp_path)
               for p in at_four.values())
    # and the child is asked for exactly the statements without a marker
    for path in list(at_four.values())[:2]:
        with open(path, "w") as f:
            f.write("warm\n")
    assert serve.uncompiled(four, False) == every[2:]
    assert serve.uncompiled(one, False) == every


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
