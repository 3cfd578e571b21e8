"""The device-program registry and launch point (ISSUE 25,
presto_tpu/exec/programs.py): every program the executor makes has a
declared label and is jitted under it, every call of one is counted and
annotated at one place, on the executor that made the call."""

import ast
import glob
import os

import jax
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import programs as PG
from presto_tpu.exec.counters import QUERY_COUNTERS
from presto_tpu.exec.executor import Executor
from presto_tpu.runner import LocalRunner
from tests.tpch_queries import QUERIES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.01


ENGINE_FILES = sorted(glob.glob(
    os.path.join(REPO, "presto_tpu", "**", "*.py"), recursive=True))
# the two ways a program is made: Executor._jit, and over a mesh
# DistExecutor._mesh_jit, which hands its key on to _jit
JIT_CALLS = ("_jit", "_mesh_jit")


def _jit_key_labels():
    """(file, line, label) of every ``<x>._jit(key, ...)`` and
    ``<x>._mesh_jit(key, ...)`` call in the engine whose key is a tuple
    literal beginning with a string, or a name bound to one in the same
    function. A helper that hands its own ``key`` parameter on is no
    site: its callers are."""
    out = []
    for path in ENGINE_FILES:
        with open(path) as f:
            tree = ast.parse(f.read())
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.Lambda)):
                continue
            params = {a.arg for a in fn.args.args}
            bound = {
                n.targets[0].id: n.value for n in ast.walk(fn)
                if isinstance(n, ast.Assign) and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)}
            for call in ast.walk(fn):
                if not (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)
                        and call.func.attr in JIT_CALLS and call.args):
                    continue
                key = call.args[0]
                if isinstance(key, ast.Name) and key.id in params \
                        and key.id not in bound:
                    continue
                if isinstance(key, ast.Name):
                    key = bound.get(key.id, key)
                first = key.elts[0] if isinstance(key, ast.Tuple) else key
                assert isinstance(first, ast.Constant) and isinstance(
                    first.value, str), (
                    f"{path}:{call.lineno}: a _jit key has to begin "
                    "with its label, a string literal")
                out.append((os.path.relpath(path, REPO), call.lineno,
                            first.value))
    return out


def _program_labels():
    """Labels of the programs made without a jit cache of an executor:
    ``Program("<label>", ...)`` (the connector's generator program, the
    process-level ICI exchange program)."""
    out = set()
    for path in ENGINE_FILES:
        if path.endswith(os.path.join("exec", "executor.py")):
            continue  # _jit itself: the label comes from the key
        with open(path) as f:
            tree = ast.parse(f.read())
        for call in ast.walk(tree):
            if (isinstance(call, ast.Call)
                    and getattr(call.func, "attr", None) == "Program"
                    and call.args
                    and isinstance(call.args[0], ast.Constant)):
                out.add(call.args[0].value)
    return out


def test_jax_jit_is_called_in_one_place():
    """exec/programs.Program is the only caller of jax.jit in the
    engine, the mesh executor's shard_map programs included: a program
    jitted anywhere else has no label, no family, no count."""
    offenders = []
    for path in ENGINE_FILES:
        rel = os.path.relpath(path, REPO)
        if rel == os.path.join("presto_tpu", "exec", "programs.py"):
            continue
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if "jax.jit(" in line.split("#", 1)[0]:
                    offenders.append(f"{rel}:{n}")
    assert not offenders, offenders


def test_every_jit_key_label_is_declared():
    sites = _jit_key_labels()
    assert len({(f, n) for f, n, _ in sites}) >= 45
    mesh_sites = {lab for f, _n, lab in sites
                  if f == os.path.join("presto_tpu", "dist", "executor.py")}
    assert len(mesh_sites) >= 18 and all(
        lab.startswith("d_") for lab in mesh_sites), sorted(mesh_sites)
    undeclared = sorted({(f, n, lab) for f, n, lab in sites
                         if lab not in PG.PROGRAM_LABELS})
    assert not undeclared, (
        "a _jit key's label is missing from "
        f"exec/programs.PROGRAM_LABELS: {undeclared}")
    direct = _program_labels()
    assert {"scan_gen", "d_ici_exchange"} <= direct
    used = {lab for _f, _n, lab in sites} | direct
    assert set(PG.PROGRAM_LABELS) == used, (
        "stale labels", sorted(set(PG.PROGRAM_LABELS) - used))
    assert set(PG.PROGRAM_LABELS.values()) <= set(PG.FAMILIES)


def test_family_of_reads_a_trace_program_name():
    assert PG.family_of("jit_join_probe(5456584955919556897)") == "join"
    assert PG.family_of("jit_fused_batch(12)") == "scan"
    assert PG.family_of("jit_sort_page") == "sort_topn"
    # what this registry did not name: an eager jnp call, the old name
    assert PG.family_of("jit_gather(77)") is None
    assert PG.family_of("jit__unknown(5456584955919556897)") is None
    assert PG.family_of("jit_sort(3)") is None
    assert PG.label_of(("agg_merge", 1, 2)) == "agg_merge"
    # over a mesh: the programs that move rows between chips are a
    # family of their own, the shard-local ones fall into their own
    for label in ("d_repartition", "d_residue", "d_gather",
                  "d_ici_exchange"):
        assert PG.family_of(f"jit_{label}(5456584955919556897)") == \
            "exchange", label
    assert PG.family_of("jit_d_scan(1)") == "scan"
    assert PG.family_of("jit_d_fused(1)") == "scan"
    assert PG.family_of("jit_d_fused_batch(1)") == "scan"
    assert PG.family_of("jit_d_agg_final(1)") == "agg"
    assert PG.family_of("jit_d_topn_local(1)") == "sort_topn"
    assert PG.family_of("jit_d_genjoin(1)") == "join"
    assert {lab: fam for lab, fam in PG.PROGRAM_LABELS.items()
            if fam == "exchange"}.keys() == {
        "d_repartition", "d_residue", "d_gather", "d_ici_exchange"}
    assert PG.label_of((("agg_merge", 1), "donate")) == "program"


COMPACTIONS = ("stream_compact1", "stream_compact2")


@pytest.mark.parametrize("label", [
    lab for one_chip in COMPACTIONS for lab in (one_chip, "d_" + one_chip)])
def test_compaction_is_filter_project_on_one_chip_and_over_a_mesh(label):
    """The compaction before the aggregation moves no row between
    chips: over a mesh it is no exchange, and exchange_launches and the
    exchange family's device time must not count it."""
    assert PG.family_of(f"jit_{label}(1)") == "filter_project"
    assert not PG.Program(label, lambda x: x).exchange


def test_the_lint_finds_the_mesh_compactions():
    sites = {(f, lab) for f, _n, lab in _jit_key_labels()}
    mesh = os.path.join("presto_tpu", "dist", "executor.py")
    one = os.path.join("presto_tpu", "exec", "executor.py")
    for label in COMPACTIONS:
        assert (mesh, "d_" + label) in sites and (one, label) in sites


def _compaction_page(valid):
    import jax.numpy as jnp

    from presto_tpu import types as T
    from presto_tpu.page import Block, Page

    return Page(
        blocks=(Block(data=jnp.arange(len(valid), dtype=jnp.int64),
                      type=T.BIGINT, nulls=None, dictionary=None),),
        valid=jnp.asarray(valid))


def test_one_chip_compaction_keeps_its_two_canonical_programs():
    """The rolling buffer's programs stay the bare kernels with a
    static capacity under the keys ("stream_compact1",) and
    ("stream_compact2",): what the one-chip cells' traces name and
    fingerprint them by cannot drift with how the loop gets them."""
    from presto_tpu.exec.executor import (
        _compact_with_flag, _merge_compact_flag)

    ex = Executor({"tpch": TpchConnector(SF)})
    first, merge = ex._stream_compact_fns(None, 4)
    assert set(ex._jit_cache) == {(lab,) for lab in COMPACTIONS}
    page = _compaction_page([True, False] * 4)
    acc, dropped = first(page)
    assert acc.block(0).data.tolist() == [0, 2, 4, 6] and not dropped
    both, dropped = merge(acc, page)
    assert both.block(0).data.tolist() == [0, 2, 4, 6] and dropped
    assert ex.device_launches == 2
    for label, kernel, args, static in (
            ("stream_compact1", _compact_with_flag, (page, 4), (1,)),
            ("stream_compact2", _merge_compact_flag, (acc, page, 4),
             (2,))):
        bare = PG.Program(label, kernel, static_argnums=static)
        assert ex._jit_cache[(label,)].jitted.lower(*args).as_text() \
            == bare.jitted.lower(*args).as_text()


def test_mesh_compaction_is_shard_local_with_one_flag():
    """Over four devices each chip compacts into its C // D slots and
    no row changes chips; one chip past its share raises the one
    replicated flag."""
    import types

    from presto_tpu.dist.executor import DistExecutor, make_mesh
    from presto_tpu.exec import plan as P

    ex = DistExecutor({"tpch": TpchConnector(SF)}, make_mesh(4))
    node = types.SimpleNamespace(
        source=P.TableScan("tpch", "lineitem", ("l_orderkey",)))
    first, merge = ex._stream_compact_fns(node, 8)  # 2 slots a chip
    assert {k[0] for k in ex._jit_cache} == {
        "d_" + lab for lab in COMPACTIONS}
    # chip 0 holds rows 0..3, chip 1 rows 4..7, ...
    page = _compaction_page(
        [False, True, False, False] + [True, False, False, True]
        + [False] * 4 + [False, False, True, True])
    acc, dropped = first(page)
    assert acc.capacity == 8 and not bool(dropped)
    assert acc.valid.tolist() == [True, False, True, True,
                                  False, False, True, True]
    assert acc.block(0).data.tolist()[:4] == [1, 0, 4, 7]
    assert acc.block(0).data.tolist()[6:] == [14, 15]
    both, dropped = merge(acc, page)  # chip 0: 2 rows, the others more
    assert bool(dropped)
    assert both.block(0).data.tolist()[:2] == [1, 1]
    assert (ex.device_launches, ex.exchange_launches) == (2, 0)
    # a replicated source takes the one-chip pair
    ex._stream_compact_fns(
        types.SimpleNamespace(source=P.Values((), ())), 8)
    assert {("stream_compact1", "rows"),
            ("stream_compact2", "rows")} <= set(ex._jit_cache)


@pytest.fixture(scope="module")
def traced_tpch():
    """Q1/Q3/Q5/Q6 at SF0.01 on a fresh runner with the fused paths the
    chip takes forced on, traced: the names of every program XLA was
    asked for meanwhile, and the launches by label of each attempt."""
    from jax import monitoring

    runner = LocalRunner({"tpch": TpchConnector(SF)}, page_rows=1 << 13)
    runner.session.set("query_trace_enabled", True)
    runner.session.set("fused_partial_agg_enabled", "true")
    runner.session.set("split_batch_size", 8)
    names = []

    def on(event, _duration, **kw):
        if event.endswith("backend_compile_duration"):
            names.append(kw.get("fun_name", "?"))

    monitoring.register_event_duration_secs_listener(on)
    launches, counters = {}, {}
    try:
        for q in (1, 3, 5, 6):
            runner.execute(QUERIES[q])
            ex = runner.executor
            counters[q] = (ex.device_launches, ex.program_launches,
                           ex.dispatch_wall_us, ex.device_wait_us)
            for sp in runner.last_trace.spans():
                if sp.kind == "attempt" and sp.attrs.get("launches"):
                    for lab, n in sp.attrs["launches"].items():
                        launches[lab] = launches.get(lab, 0) + n
    finally:
        monitoring.unregister_event_duration_listener(on)
    return names, launches, counters


def test_no_program_of_tpch_is_unnamed(traced_tpch):
    names, launches, _ = traced_tpch
    assert names, "no program was requested: the listener saw nothing"
    unnamed = [n for n in names if "unknown" in n or "lambda" in n]
    assert not unnamed, unnamed
    assert launches and set(launches) <= set(PG.PROGRAM_LABELS), launches
    # the executor's own programs are there under their labels
    requested = {n[len("jit("):-1] for n in names if n.startswith("jit(")}
    assert set(launches) <= requested, (sorted(launches),
                                        sorted(requested))
    assert {"fused_batch", "join_probe"} & set(launches)


def test_launch_counters_per_statement(traced_tpch):
    _names, launches, counters = traced_tpch
    for q, (device, fused, dispatch_us, wait_us) in counters.items():
        assert device >= fused >= 1, (q, device, fused)
        assert dispatch_us > 0 and wait_us > 0, (q, dispatch_us, wait_us)
    # the joins launch more than their fused scans
    assert counters[3][0] > counters[3][1]
    for name in ("device_launches", "dispatch_wall_us", "device_wait_us"):
        assert QUERY_COUNTERS[name][0] == "gauge"


def test_launches_count_on_the_calling_executor():
    """The concurrent server shares one jit cache between per-query
    executors: a program counts its call, and its donation, on the
    executor that called it, not on the one that built it."""
    import jax.numpy as jnp

    catalogs = {"tpch": TpchConnector(SF)}
    builder, caller = Executor(catalogs), Executor(catalogs)
    caller._jit_cache = builder._jit_cache
    for ex in (builder, caller):
        ex.buffer_donation = "true"

    def bump(x):
        return x + 1

    first = builder._jit(("agg_merge", "t"), bump, donate_argnums=(0,))
    first(jnp.arange(4))
    assert (builder.device_launches, builder.buffers_donated) == (1, 1)
    again = caller._jit(("agg_merge", "t"), bump, donate_argnums=(0,))
    assert len(builder._jit_cache) == 1, "the program was built twice"
    again(jnp.arange(4))
    again(jnp.arange(4))
    assert (caller.device_launches, caller.buffers_donated) == (2, 2)
    assert (builder.device_launches, builder.buffers_donated) == (1, 1)
    assert caller.program_launches == 0  # no fused scan among them
    fused = caller._jit(("fused", "t"), make=lambda: bump)
    fused(jnp.arange(4))
    assert (caller.device_launches, caller.program_launches) == (3, 1)
    assert builder.program_launches == 0
    assert caller.dispatch_wall_us > 0


def test_profiler_recording_holds_the_statements_own_account(tmp_path):
    """A jax.profiler recording with the harness's options: the host
    plane holds execute:<query id> and, nested inside it, the launches
    and the waits, on the recording's own clock."""
    from jax.profiler import ProfileData

    runner = LocalRunner({"tpch": TpchConnector(SF)}, page_rows=1 << 13)
    runner.session.set("query_trace_enabled", True)
    sql = QUERIES[6]
    runner.execute(sql)  # compiled before the recording
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        runner.execute(sql)
    finally:
        jax.profiler.stop_trace()
    query_id = runner.last_trace.query_id
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for line in host.lines for e in line.events]
    by_name = {}
    for name, lo, hi in events:
        by_name.setdefault(name, []).append((lo, hi))
    assert {"parse", "plan", f"execute:{query_id}"} <= set(by_name), (
        sorted(n for n in by_name if ":" in n or n in ("parse", "plan")))
    ((x_lo, x_hi),) = by_name[f"execute:{query_id}"]
    launches = [(n, lo, hi) for n, lo, hi in events
                if n.startswith("launch:")]
    waits = [(n, lo, hi) for n, lo, hi in events if n.startswith("wait:")]
    assert launches and waits
    for _name, lo, hi in launches + waits:
        assert x_lo <= lo and hi <= x_hi
    assert {n for n, _lo, _hi in launches} == {
        f"launch:{lab}" for sp in runner.last_trace.spans()
        if sp.kind == "attempt" for lab in sp.attrs["launches"]}
    assert by_name["plan"][0][1] <= x_lo + 1000  # plan ends as it begins


# ------------------------------------------------ program load, split
def test_nested_trace_events_count_once():
    """A jitted function traced while another is traced reports its
    event inside the outer one's interval: the total is the union."""
    import threading

    from presto_tpu import compilecache as CC

    out = {}

    def feed():  # a thread of its own: the account is per thread
        base = CC.snapshot()
        CC._on_duration(CC._JAXPR_TRACE, 0.2)   # inner, ends now
        CC._on_duration(CC._JAXPR_TRACE, 0.1)   # its sibling
        CC._on_duration(CC._JAXPR_TRACE, 0.5)   # the outer one
        out["nested"] = CC.delta(base)
        base = CC.snapshot()
        CC._on_duration(CC._JAXPR_TO_MLIR, 0.25)
        CC._on_duration(CC._CACHE_RETRIEVAL, 0.125)
        out["rest"] = CC.delta(base)

    t = threading.Thread(target=feed)
    t.start()
    t.join()
    assert out["nested"]["programs_traced"] == 3
    # 0.2 and 0.1 overlap as fed (both end "now"), the outer holds both
    assert out["nested"]["program_trace_wall_s"] == pytest.approx(
        0.5, abs=0.01)
    assert out["rest"]["programs_lowered"] == 1
    assert out["rest"]["program_lower_wall_s"] == pytest.approx(0.25)
    assert out["rest"]["program_retrieval_wall_s"] == pytest.approx(0.125)


def test_a_real_first_call_is_split_into_its_parts():
    import time

    import jax.numpy as jnp

    from presto_tpu import compilecache as CC

    inner = jax.jit(lambda y: y * 3.0 + 25.0)
    outer = jax.jit(lambda x: inner(x) - 25.0)
    base = CC.snapshot()
    t0 = time.perf_counter()
    outer(jnp.arange(8.0)).block_until_ready()
    wall = time.perf_counter() - t0
    d = CC.delta(base)
    assert d["programs_traced"] >= 2 and d["programs_lowered"] >= 1
    assert 0 < d["program_trace_wall_s"] <= wall
    assert 0 < d["program_lower_wall_s"] <= wall
    parts = (d["program_trace_wall_s"] + d["program_lower_wall_s"]
             + d["program_retrieval_wall_s"] + d["compile_wall_s"])
    assert parts <= wall + 0.005, (d, wall)


def _scraped(runner):
    """/metrics of a server over ``runner``, as the benchmark reads it."""
    from benchmarks.harness.serve import _METRIC_LINE
    from presto_tpu.server.http_server import QueryManager

    text = QueryManager(lambda s: runner).metrics_text(
        1.0, executor=runner.executor)
    matches = (_METRIC_LINE.match(line) for line in text.splitlines())
    return {m.group(1): float(m.group(2)) for m in matches if m}


def test_metrics_expose_what_the_benchmark_scrapes(traced_tpch):
    runner = LocalRunner({"tpch": TpchConnector(SF)}, page_rows=1 << 13)
    runner.execute(QUERIES[6])
    scraped = _scraped(runner)
    assert scraped["device_launches"] == runner.executor.device_launches
    assert scraped["device_launches"] >= scraped["program_launches"] >= 1
    assert scraped["dispatch_wall_us"] > 0 and scraped["device_wait_us"] > 0
    for name in ("program_trace_wall_s", "program_lower_wall_s",
                 "program_retrieval_wall_s", "compile_wall_s"):
        assert scraped[name] >= 0.0, name
    assert scraped["program_trace_wall_s"] > 0
    assert scraped["programs_traced"] >= scraped["programs_lowered"] >= 1
    assert "process_programs_compiled" in scraped
    assert "process_program_cache_hits" in scraped


# ------------------------------------------------------------ the mesh
@pytest.fixture(scope="module")
def mesh_runner():
    from presto_tpu.dist.executor import make_mesh

    runner = LocalRunner(
        {"tpch": TpchConnector(SF)}, page_rows=1 << 13,
        mesh=make_mesh(4), dist_options=dict(gather_capacity=16))
    runner.session.set("query_trace_enabled", True)
    runner.execute(QUERIES[3])  # compiled before any recording
    return runner


def test_a_mesh_statement_counts_every_program_it_launches(
        mesh_runner, tmp_path):
    """Q3 over four devices: every program the mesh executor calls goes
    through the one launch point, so device_launches is the number of
    launch: annotations of the statement on the profiler's host plane,
    and the programs that move rows between chips are counted apart."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        mesh_runner.execute(QUERIES[3])
    finally:
        jax.profiler.stop_trace()
    ex = mesh_runner.executor
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    noted = [e.name for line in host.lines for e in line.events
             if e.name.startswith("launch:")]
    assert len(noted) == ex.device_launches > ex.program_launches >= 1
    (attempt,) = [sp for sp in mesh_runner.last_trace.spans()
                  if sp.kind == "attempt"]
    by_label = attempt.attrs["launches"]
    assert sum(by_label.values()) == ex.device_launches
    assert set(by_label) <= set(PG.PROGRAM_LABELS), by_label
    assert {f"launch:{lab}" for lab in by_label} == set(noted)
    exchange = sum(n for lab, n in by_label.items()
                   if PG.PROGRAM_LABELS[lab] == "exchange")
    assert ex.exchange_launches == exchange >= 2  # repartition + gather
    assert attempt.attrs["exchange_launches"] == exchange
    # a scan round is ONE program (generator, both generated joins,
    # filter, project), and it is the round's fused-scan launch: once
    assert {"d_fused", "d_agg_partial", "d_repartition",
            "d_agg_final", "d_topn_local", "d_gather",
            "topn_local"} <= set(by_label)
    assert not {"d_scan", "d_genjoin", "d_filter"} & set(by_label)
    assert ex.program_launches == by_label["d_fused"] \
        == attempt.attrs["mesh_fused_rounds"] == ex.mesh_fused_rounds
    assert QUERY_COUNTERS["exchange_launches"][0] == "gauge"


def test_exchange_launches_count_on_the_calling_executor():
    import jax.numpy as jnp

    from presto_tpu.dist.executor import DistExecutor, make_mesh

    catalogs, mesh = {"tpch": TpchConnector(SF)}, make_mesh(4)
    builder = DistExecutor(catalogs, mesh)
    caller = DistExecutor(catalogs, mesh)
    caller._jit_cache = builder._jit_cache
    builder._gather_fn()(jnp.arange(8))
    assert (builder.device_launches, builder.exchange_launches) == (1, 1)
    out = caller._gather_fn()(jnp.arange(8))
    assert len(builder._jit_cache) == 1, "the program was built twice"
    assert out.tolist() == list(range(8))
    assert (caller.device_launches, caller.exchange_launches) == (1, 1)
    assert (builder.device_launches, builder.exchange_launches) == (1, 1)
    # a shard-local program is a launch and no exchange
    caller._mesh_jit(("d_filter", "t"), lambda x: x + 1)(jnp.arange(8))
    assert (caller.device_launches, caller.exchange_launches) == (2, 1)
    # a mesh's scan round is the fused-scan launch of its statement,
    # counted where every launch is, fused chain or bare scan
    assert caller.program_launches == 0
    for label in ("d_fused", "d_scan", "d_fused_batch"):
        caller._mesh_jit((label, "t"), lambda x: x + 1)(jnp.arange(8))
    assert (caller.device_launches, caller.program_launches) == (5, 3)
    caller.mesh_fused_rounds = caller.mesh_batched_rounds = 3
    caller._begin_attempt()
    assert (caller.device_launches, caller.exchange_launches,
            caller.program_launches, caller.mesh_fused_rounds,
            caller.mesh_batched_rounds) == (0, 0, 0, 0, 0)


def test_metrics_expose_exchange_launches(mesh_runner):
    from presto_tpu.server.http_server import QueryManager

    mesh_runner.execute(QUERIES[3])
    scraped = _scraped(mesh_runner)
    ex = mesh_runner.executor
    assert scraped["exchange_launches"] == ex.exchange_launches >= 2
    assert scraped["device_launches"] == ex.device_launches
    assert scraped["device_launches"] > scraped["exchange_launches"]
    assert "exchange_launches" in QueryManager._EXEC_TOTAL_SUMS
    assert scraped["mesh_fused_rounds"] == ex.mesh_fused_rounds >= 1
    assert "mesh_fused_rounds" in QueryManager._EXEC_TOTAL_SUMS
    assert QUERY_COUNTERS["mesh_fused_rounds"][0] == "gauge"
    assert scraped["mesh_batched_rounds"] == 0  # auto: off on a CPU


@pytest.mark.parametrize("size,batches,batched", [
    (2, 2, 4), (16, 1, 4), ("false", 4, 0)])
def test_metrics_and_the_attempt_span_expose_batched_rounds(
        size, batches, batched, mesh_runner):
    """Q3's four scan rounds as two launches and as one (ISSUE 40):
    program_launches counts a batch once, mesh_fused_rounds its rounds, and
    mesh_batched_rounds says they shared a launch: on /metrics, on the
    attempt span, summed over per-query executors like its twin."""
    from presto_tpu.server.http_server import QueryManager

    mesh_runner.session.set("split_batch_size", str(size))
    try:
        mesh_runner.execute(QUERIES[3])
    finally:
        mesh_runner.session.set("split_batch_size", "auto")
    ex = mesh_runner.executor
    (attempt,) = [sp for sp in mesh_runner.last_trace.spans()
                  if sp.kind == "attempt"]
    scraped = _scraped(mesh_runner)
    assert scraped["mesh_batched_rounds"] == ex.mesh_batched_rounds \
        == attempt.attrs["mesh_batched_rounds"] == batched
    assert scraped["mesh_fused_rounds"] == ex.mesh_fused_rounds == 4
    assert scraped["program_launches"] == ex.program_launches == batches
    by_label = attempt.attrs["launches"]
    assert by_label.get("d_fused_batch", 0) == (batches if batched else 0)
    assert by_label.get("d_fused", 0) == (0 if batched else 4)
    assert set(by_label) <= set(PG.PROGRAM_LABELS)
    assert "mesh_batched_rounds" in QueryManager._EXEC_TOTAL_SUMS
    assert QUERY_COUNTERS["mesh_batched_rounds"][0] == "gauge"
