"""ISSUE 6: the static-analysis layer — plan verifier + engine lint.

Reference: presto-main's PlanSanityChecker tests (every validation
pass has a seeded-broken-plan test proving it rejects) and the
build-time config/doc validations. Three groups:

  1. the repo itself is lint-clean (the rules run in tier-1, so a PR
     that un-documents a session property or adds an unsurfaced
     counter fails here, not in review);
  2. rule sensitivity: each lint rule catches a seeded violation in a
     synthetic file (a rule that cannot fail is not a check);
  3. the plan-verifier mutation suite: deliberately broken plans —
     schema-mismatched edges, off-ladder capacities, over-fault-line
     buffers, non-canonical jit keys, missing split-determinism
     fields, mismatched exchange partitioning — each rejected with a
     POINTED, actionable message.

The lint group needs no JAX; plan checks use tiny CPU plans.
"""

import dataclasses
import textwrap

import pytest

from presto_tpu import types as T
from presto_tpu.exec import plan as P
from presto_tpu.exec import plan_check as PC
from presto_tpu.exec import shapes as SH
from presto_tpu.expr import ir as E

# --------------------------------------------------------------- lint


def test_repo_is_lint_clean():
    """THE gate: zero findings across every rule on the repo itself.
    A finding here is a real plumbing gap — fix the engine (or, for a
    legitimately-broad except, annotate WHY), don't relax the rule."""
    from tools.lint import run_lint

    findings = run_lint()
    assert not findings, "\n".join(str(f) for f in findings)


def _tmp_py(tmp_path, body: str) -> str:
    p = tmp_path / "seeded.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_excepts_rule_catches_bare_and_broad(tmp_path):
    from tools.lint import check_excepts

    path = _tmp_py(tmp_path, """
        def f():
            try:
                pass
            except:
                pass
            try:
                pass
            except Exception:
                pass
            try:
                pass
            except Exception:  # noqa: BLE001 - explained, allowed
                pass
            try:
                pass
            except Exception as e:
                raise RuntimeError("x") from e
    """)
    found = check_excepts([path])
    msgs = [f.message for f in found]
    assert len(found) == 2, msgs
    assert any("bare" in m for m in msgs)
    assert any("broad" in m for m in msgs)


def test_locks_rule_catches_undeclared_and_unlocked(tmp_path):
    from tools.lint import check_locks

    path = _tmp_py(tmp_path, """
        import threading

        class Undeclared:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
            def bump(self):
                with self._lock:
                    self.n += 1

        class Racy:
            _shared_attrs = ("n",)
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
            def bump(self):
                self.n += 1  # write OUTSIDE the lock
    """)
    found = check_locks(paths=[path])
    msgs = [f.message for f in found]
    assert any("declares no `_shared_attrs`" in m for m in msgs), msgs
    assert any("OUTSIDE" in m for m in msgs), msgs


def test_purity_rule_catches_impure_keys_and_traced_code(tmp_path):
    from tools.lint import check_purity

    path = _tmp_py(tmp_path, """
        import time
        import jax

        class X:
            def _jit(self, key, fn):
                return fn
            def bad_key(self, node, fn):
                return self._jit(("agg", id(node)), fn)
            def bad_traced(self):
                def kern(x):
                    return x * time.time()
                return jax.jit(kern)
    """)
    found = check_purity(paths=[path])
    msgs = [f.message for f in found]
    assert any("id()" in m and "key" in m for m in msgs), msgs
    assert any("time.time" in m and "traced" in m for m in msgs), msgs


def test_purity_rule_covers_direct_cache_stores(tmp_path):
    """The dist executor's `self._jit_cache[key] = jax.jit(body)`
    pattern: the key variable resolves in the ENCLOSING function (an
    unrelated `key = id(...)` in another method must not bleed in),
    and shard_map bodies count as traced entry points."""
    from tools.lint import check_purity

    path = _tmp_py(tmp_path, """
        import time
        import jax

        class X:
            def impure_store(self, node):
                key = ("d_repart", id(node))
                self._jit_cache[key] = jax.jit(lambda x: x)
            def unrelated_memo(self, node):
                key = id(node)          # NOT a jit cache — no finding
                self._memo[key] = node
            def traced_shard_body(self):
                def body(x):
                    return x + time.time()
                self._jit_cache["k"] = jax.jit(
                    jax.shard_map(body, mesh=None))
    """)
    found = check_purity(paths=[path])
    msgs = [f.message for f in found]
    assert any("id()" in m and "key" in m for m in msgs), msgs
    assert any("time.time" in m and "'body'" in m for m in msgs), msgs
    assert len([m for m in msgs if "id()" in m]) == 1, msgs


def test_counters_registry_matches_executor():
    """Every registry counter exists on a bare Executor (the snapshot
    never fabricates attributes) and is an int."""
    from presto_tpu.exec import counters as CTRS
    from presto_tpu.exec.executor import Executor

    ex = Executor({})
    for name in CTRS.QUERY_COUNTERS:
        assert isinstance(getattr(ex, name), int), name
    snap = CTRS.snapshot(ex)
    assert set(snap) == set(CTRS.QUERY_COUNTERS)


# ------------------------------------------- counter surfacing contract


@pytest.fixture(scope="module")
def tiny_runner():
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runner import LocalRunner

    r = LocalRunner({"tpch": TpchConnector(scale=0.001)},
                    default_catalog="tpch")
    r.apply_session()
    return r


def test_every_registry_counter_reaches_explain_analyze(tiny_runner):
    from presto_tpu.exec import counters as CTRS
    from presto_tpu.runner import explain_text

    plan = tiny_runner.plan(
        "select count(*), sum(n_nationkey) from nation")
    _n, _r, stats = tiny_runner.executor.execute_with_stats(plan)
    ctr = stats["counters"]
    missing = set(CTRS.QUERY_COUNTERS) - set(ctr)
    assert not missing, f"counters dict missing {missing}"
    for name in CTRS.COMPUTED_COUNTERS:
        assert name in ctr, f"computed entry {name} missing"
    text = explain_text(plan, stats=stats)
    # the EXPLAIN ANALYZE text renders the whole dict — spot-check the
    # counters the pre-registry wiring dropped (ISSUE 6 satellite)
    for name in ("split_batch_fallbacks", "release_skips",
                 "spill_partitions_used", "gathers_deferred"):
        assert name in text, f"{name} not rendered in EXPLAIN ANALYZE"


def test_every_registry_counter_reaches_metrics_surfaces(tiny_runner):
    """/metrics exposition and the system.metrics table render the
    full registry (the wiring iterates QUERY_COUNTERS — this pins the
    contract so a revert to hand-listing fails)."""
    from presto_tpu.exec import counters as CTRS
    from presto_tpu.server.http_server import QueryManager

    mgr = QueryManager(lambda s: tiny_runner)
    text = mgr.metrics_text(1.0, executor=tiny_runner.executor)
    for name, (kind, _h) in CTRS.QUERY_COUNTERS.items():
        suffix = "_total" if kind == "counter" else ""
        assert f"presto_tpu_{name}{suffix} " in text, name
    rows = dict(
        (name, val) for name, val in
        [("device_memory_budget_bytes", 0)] +
        list(CTRS.snapshot(tiny_runner.executor).items())
    )
    assert set(CTRS.QUERY_COUNTERS) <= set(rows)


# ------------------------------------------------------ tools and docs
def _repo_text(rel: str) -> str:
    import os

    from tools.lint import REPO

    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _section(text: str, start: str, stop: str) -> str:
    """``text`` from the heading line starting with ``start`` up to
    the next line starting with ``stop`` (or the end)."""
    import re

    m = re.search(rf"^{re.escape(start)}.*$", text, re.M)
    assert m, f"no heading {start!r}"
    rest = text[m.end():]
    n = re.search(rf"^{re.escape(stop)}", rest, re.M)
    return rest[:n.start()] if n else rest


def test_every_tool_has_a_caller_or_a_readme_line():
    """A program under tools/ is run by tools/ci_static.sh, imported
    by a tier-1 test, or named in README's Tools section — a tool
    nothing runs and nothing documents is the debt ISSUE 31 removed —
    and every file ci_static.sh names exists."""
    import glob
    import os
    import re

    from tools.lint import REPO

    ci = _repo_text("tools/ci_static.sh")
    for rel in set(re.findall(r"\b(?:tools|tests)/[\w/]+\.py\b", ci)):
        assert os.path.isfile(os.path.join(REPO, rel)), rel
    ran = set(re.findall(r"\btools/(\w+)\.py\b", ci))
    for mod in re.findall(r"-m tools\.(\w+)", ci):
        assert os.path.exists(os.path.join(REPO, "tools", mod)) or \
            os.path.isfile(os.path.join(REPO, "tools", mod + ".py")), mod
    imported = set()
    for path in glob.glob(os.path.join(REPO, "tests", "*.py")):
        with open(path) as f:
            imported |= set(re.findall(
                r"^\s*from tools\.(\w+) import", f.read(), re.M))
    documented = _section(_repo_text("README.md"), "## Tools", "## ")
    for path in glob.glob(os.path.join(REPO, "tools", "*.py")):
        name = os.path.basename(path)[:-3]
        if name == "__init__":
            continue
        assert (name in ran or name in imported
                or f"`tools/{name}.py`" in documented), (
            f"tools/{name}.py is run by nothing, imported by no test "
            f"and absent from README's Tools section")


@pytest.mark.parametrize("doc,bounds", [
    ("README.md", None),
    ("PERF.md", ("## 3.", "## 5.")),
], ids=["README", "PERF-3-4"])
def test_documented_programs_exist(doc, bounds):
    """Every backticked ``tools/...`` path and bare ``name.py`` (a
    file at the repo root or the package root) in README.md and in
    PERF.md's layer and cell sections exists in the tree."""
    import os
    import re

    from tools.lint import REPO

    text = _repo_text(doc)
    if bounds:
        text = _section(text, *bounds)
    missing = []
    for tok in re.findall(r"`([^`\n]+)`", text):
        words = tok.split()
        if words and words[0] in ("python", "python3") and len(words) > 1:
            words = words[1:]
        if not words:
            continue
        w = words[0]
        if w.startswith("tools/"):
            ok = os.path.exists(os.path.join(REPO, w))
        elif re.fullmatch(r"\w+\.py", w):
            ok = any(os.path.isfile(os.path.join(REPO, d, w))
                     for d in ("", "presto_tpu"))
        else:
            continue
        if not ok:
            missing.append(w)
    assert not missing, f"{doc} names programs not in the tree: {missing}"


# --------------------------------------------------- plan_check wiring


def test_plan_check_auto_on_under_pytest(tiny_runner):
    ex = tiny_runner.executor
    assert ex.plan_check == "auto"
    assert ex._plan_check_on()  # PYTEST_CURRENT_TEST is set
    ex.plan_check = "false"
    try:
        assert not ex._plan_check_on()
    finally:
        ex.plan_check = "auto"


def test_plan_check_session_prop_plumbs(tiny_runner):
    tiny_runner.session.set("plan_check", "false")
    try:
        tiny_runner.apply_session()
        assert tiny_runner.executor.plan_check == "false"
    finally:
        tiny_runner.session.unset("plan_check")
        tiny_runner.apply_session()


def test_execute_rejects_broken_plan_before_compile(tiny_runner):
    """The wiring, end to end: a broken plan handed to execute() fails
    with PlanCheckError (pre-compile), not a downstream shape error."""
    scan = P.TableScan("tpch", "nation", ("n_nationkey", "n_name"))
    bad = P.Output(
        source=P.Filter(source=scan,
                        predicate=E.input_ref(9, T.BOOLEAN)),
        names=("a", "b"),
    )
    with pytest.raises(PC.PlanCheckError, match="channel #9"):
        tiny_runner.executor.execute(bad)


# ------------------------------------------------------ mutation suite
# Each seeded-broken plan must be rejected with a message pointing at
# the exact invariant — these are the drifts round 5 lost
# correctness gates to.

_VALUES2 = P.Values(types=(T.BIGINT, T.DOUBLE), rows=((1, 2.0),))


def _verify(ex, plan, **kw):
    with pytest.raises(PC.PlanCheckError) as ei:
        PC.verify(ex, plan, **kw)
    return ei.value


def test_mutation_schema_mismatched_edge(tiny_runner):
    plan = P.Filter(source=_VALUES2,
                    predicate=E.input_ref(5, T.BOOLEAN))
    err = _verify(tiny_runner.executor, plan)
    assert "channel #5" in str(err) and "2 channels" in str(err)


def test_mutation_project_stale_channel(tiny_runner):
    plan = P.Project(source=_VALUES2,
                     exprs=(E.input_ref(3, T.BIGINT),))
    err = _verify(tiny_runner.executor, plan)
    assert "expr #0" in str(err) and "stale channel mapping" in str(err)


def test_mutation_join_key_arity_mismatch(tiny_runner):
    plan = P.HashJoin(left=_VALUES2, right=_VALUES2,
                      left_keys=(0, 1), right_keys=(0,))
    err = _verify(tiny_runner.executor, plan)
    assert "arity mismatch" in str(err)


def test_mutation_join_key_type_mismatch(tiny_runner):
    strings = P.Values(types=(T.VARCHAR,), rows=(("x",),))
    plan = P.HashJoin(left=_VALUES2, right=strings,
                      left_keys=(0,), right_keys=(0,))
    err = _verify(tiny_runner.executor, plan)
    assert "type mismatch" in str(err) and "never match" in str(err)


def test_mutation_mismatched_exchange_partitioning(tiny_runner):
    left = P.Exchange(source=_VALUES2, kind="repartition", keys=(0,))
    right = P.Exchange(source=_VALUES2, kind="repartition", keys=(1,))
    plan = P.HashJoin(left=left, right=right,
                      left_keys=(0,), right_keys=(0,))
    err = _verify(tiny_runner.executor, plan)
    assert "partitioning disagrees" in str(err)
    assert "co-locate" in str(err)


def test_mutation_broadcast_exchange_with_keys(tiny_runner):
    plan = P.Exchange(source=_VALUES2, kind="broadcast", keys=(0,))
    err = _verify(tiny_runner.executor, plan)
    assert "only repartition partitions by key" in str(err)


def test_mutation_off_ladder_capacity():
    """A buffer capacity that bypassed SH.bucket is flagged as
    off-ladder (the program-shape canonicalization invariant)."""
    from presto_tpu.exec import membudget as MB

    report = MB.AuditReport(
        budget=1 << 34, fault_rows=None,
        buffers=[MB.BufferPlan("join build inner (1/1 pass)",
                               rows=3000, row_bytes=16)],
    )
    violations = []
    PC.check_buffers(report, violations)
    assert violations and "OFF the shapes.py bucket ladder" in \
        violations[0]
    assert "3000" in violations[0]


def test_mutation_over_fault_line_buffer():
    """A plan whose blocking merge exceeds the governed fault line is
    rejected in strict (audit-gate) mode with the chunking hint."""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.ops.sort import SortKey
    from presto_tpu.runner import LocalRunner

    r = LocalRunner({"tpch": TpchConnector(scale=0.1)},
                    default_catalog="tpch")
    ex = r.executor
    scan = P.TableScan("tpch", "lineitem", ("l_orderkey",))
    plan = P.Sort(source=scan, keys=(SortKey(channel=0),))
    ex.fault_rows = 1 << 12  # lineitem@SF0.1 ~600k rows >> line
    err = _verify(ex, plan, strict=True)
    assert "past the governed device fault line" in str(err)
    assert "chunk" in str(err)


def test_mutation_over_budget_buffer():
    from presto_tpu.exec import membudget as MB

    report = MB.AuditReport(
        budget=1 << 20, fault_rows=None,
        buffers=[MB.BufferPlan("agg state", rows=1 << 20,
                               row_bytes=64)],
    )
    violations = []
    PC.check_buffers(report, violations)
    assert violations and "past the device-memory budget" in \
        violations[0]


def test_mutation_non_canonical_jit_key_dict(tiny_runner):
    """A dict smuggled into plan content (= jit-key material) is
    rejected for iteration-order dependence. (A dict in a scan
    CONSTRAINT is caught even earlier, by the malformed-constraint
    schema check — also pinned here.)"""
    bad = P.Values(types=(T.BIGINT,), rows=(({"a": 1},),))
    err = _verify(tiny_runner.executor, bad)
    assert "non-canonical jit-key material" in str(err)
    assert "dict" in str(err)
    scan = P.TableScan("tpch", "nation", ("n_nationkey",))
    bad2 = dataclasses.replace(scan, constraint={"n_nationkey": 1})
    err2 = _verify(tiny_runner.executor, bad2)
    assert "constraint" in str(err2)


def test_mutation_non_canonical_jit_key_object():
    violations = []

    class Opaque:
        pass

    PC.check_canonical_key_material(
        P.Values(types=(T.BIGINT,), rows=((Opaque(),),)), violations)
    assert violations and "id() leaks" in violations[0]


def test_canonical_rekey_is_byte_identical(tiny_runner):
    """The positive half of invariant 3: a real plan re-keys
    byte-identically across a serde roundtrip."""
    plan = tiny_runner.plan(
        "select n_name, count(*) from nation group by 1")
    violations = []
    PC.check_canonical_key_material(plan, violations)
    assert violations == []


def test_mutation_remote_source_schema_mismatch(tiny_runner):
    agg = P.Aggregation(
        source=_VALUES2, group_channels=(0,),
        aggregates=(P.AggSpec("sum", channel=1),), step="partial")
    remote = P.RemoteSource(types=(T.BIGINT,), key="k", origin=agg)
    err = _verify(tiny_runner.executor, remote)
    assert "schema-inconsistent fragment edge" in str(err)


def test_mutation_output_names_arity(tiny_runner):
    plan = P.Output(source=_VALUES2, names=("only_one",))
    err = _verify(tiny_runner.executor, plan)
    assert "1 output names for 2 channels" in str(err)


def test_mutation_bad_agg_step_and_capacity(tiny_runner):
    plan = P.Aggregation(
        source=_VALUES2, group_channels=(0,),
        aggregates=(P.AggSpec("sum", channel=1),),
        capacity=-4, step="both")
    err = _verify(tiny_runner.executor, plan)
    assert "unknown step" in str(err)
    assert "negative group capacity" in str(err)


def test_mutation_unknown_scan_column(tiny_runner):
    plan = P.TableScan("tpch", "nation", ("n_nationkey", "bogus"))
    err = _verify(tiny_runner.executor, plan)
    assert "'bogus'" in str(err) and "nation" in str(err)


def test_verifier_reports_all_violations_at_once(tiny_runner):
    """The verifier collects findings instead of stopping at the
    first — one run, the whole fix list."""
    plan = P.Output(
        source=P.HashJoin(left=_VALUES2, right=_VALUES2,
                          left_keys=(0, 1), right_keys=(5,)),
        names=("a",),
    )
    err = _verify(tiny_runner.executor, plan)
    assert len(err.violations) >= 3  # arity + range + names


# ------------------------------------------- split-determinism payloads


def _payload(**over):
    base = {
        "taskId": "q.0", "fragment": "{}", "splitTable": "lineitem",
        "splitIndex": 0, "splitCount": 4, "session": {},
    }
    base.update(over)
    for k, v in list(base.items()):
        if v is _MISSING:
            del base[k]
    return base


_MISSING = object()


def test_payload_ok():
    PC.check_task_payload(_payload())
    PC.check_task_payload(_payload(
        splitMode="hash",
        partitionColumns={"tpch.lineitem": "l_orderkey"}))


def test_mutation_payload_missing_split_fields():
    with pytest.raises(PC.PlanCheckError, match="splitIndex"):
        PC.check_task_payload(_payload(splitIndex=_MISSING))
    with pytest.raises(PC.PlanCheckError, match="splitCount"):
        PC.check_task_payload(_payload(splitCount=_MISSING))


def test_mutation_payload_split_out_of_range():
    with pytest.raises(PC.PlanCheckError, match="outside"):
        PC.check_task_payload(_payload(splitIndex=4))


def test_mutation_payload_hash_without_partition_columns():
    with pytest.raises(PC.PlanCheckError, match="partitionColumns"):
        PC.check_task_payload(_payload(splitMode="hash"))


def test_mutation_payload_no_split_table():
    with pytest.raises(PC.PlanCheckError, match="splitTable"):
        PC.check_task_payload(_payload(splitTable=None))


# ----------------------------------------------------- clean-plan sweep


def test_tpch_corpus_verifies_clean(tiny_runner):
    """Every TPC-H plan the engine's own planner emits passes the
    verifier — the zero-false-positive contract that lets plan_check
    run on every pytest execution."""
    from tests.tpch_queries import QUERIES

    for qid in sorted(QUERIES):
        plan = tiny_runner.plan(QUERIES[qid])
        PC.verify(tiny_runner.executor, plan)  # must not raise


def test_distributed_plans_verify_clean(tiny_runner):
    from presto_tpu.dist.fragmenter import add_exchanges
    from tests.tpch_queries import QUERIES

    for qid in (1, 3, 5):
        plan = tiny_runner.plan(QUERIES[qid])
        dplan, _ = add_exchanges(plan, tiny_runner.catalogs)
        PC.verify(tiny_runner.executor, dplan)


def test_hash_partition_count_is_wired(tiny_runner):
    """The plumbing gap the session-props lint surfaced: the
    hash_partition_count property now reaches the dist executor's
    routing (DistExecutor._route_devices)."""
    from presto_tpu.dist.executor import DistExecutor

    tiny_runner.session.set("hash_partition_count", 3)
    try:
        tiny_runner.apply_session()
        assert tiny_runner.executor.hash_partitions == 3
    finally:
        tiny_runner.session.unset("hash_partition_count")
        tiny_runner.apply_session()
    ex = DistExecutor.__new__(DistExecutor)  # routing math only
    ex.D = 8
    for hp, want in ((0, 8), (3, 3), (100, 8)):
        ex.hash_partitions = hp
        assert ex._route_devices() == want, (hp, want)


def test_ladder_is_fixed_point():
    """bucket() output always re-buckets to itself (the property the
    off-ladder check relies on)."""
    for n in (1, 7, 8, 100, 4096, 4097, 1 << 20):
        b = SH.bucket(n)
        assert SH.bucket(b) == b
