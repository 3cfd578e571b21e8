"""Static plan audit: sweep the plans at served scale (SCALE_PLANS)
and the TPC-H/TPC-DS test corpus through the pre-compile plan verifier
(exec/plan_check.py, strict mode) and exit nonzero on any violation.

Reference: presto-verifier's suite replay, applied to PLANS instead of
results — the point is catching invariant drift (schema-inconsistent
edges, off-ladder capacities, non-canonical jit keys, missing split
determinism) across the WHOLE query corpus before a PR lands, not
after a statement hangs on real hardware. Planning is pure host
Python; nothing traces, compiles, or touches a device, so the sweep
is cheap enough for the pre-PR gate (tools/ci_static.sh).

Usage:
    python tools/plan_audit.py                 # scale plans + corpora
    python tools/plan_audit.py --scale-plans   # scale plans only
    python tools/plan_audit.py --corpus tpch   # one corpus only
    python tools/plan_audit.py --sf 0.001      # corpus scale factor
"""

import argparse
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import make_runner, queries  # noqa: E402

# (label, suite, query id, scale factor, session props): plans whose
# capacities only become real at the scale they are served at
# (generator connectors are lazy — row counts, not rows). SF1 and SF10
# Q1/Q3/Q5/Q6 are the benchmark cells' statements (BENCHMARK.json);
# q17 keeps a TPC-DS join build near the 1.32M-slot line, q1_sf100 the
# split-batched scan at 600M rows.
BIG_PAGES = ("page_rows=1048576",)
SCALE_PLANS = [
    ("q1_sf1", "tpch", 1, 1.0, BIG_PAGES),
    ("q6_sf1", "tpch", 6, 1.0, BIG_PAGES),
    ("q3_sf01", "tpch", 3, 0.1, ()),
    ("q1_sf10", "tpch", 1, 10.0, BIG_PAGES),
    ("q6_sf10", "tpch", 6, 10.0, BIG_PAGES),
    ("q3_sf1", "tpch", 3, 1.0, BIG_PAGES),
    ("q5_sf1", "tpch", 5, 1.0, BIG_PAGES),
    ("q17_sf025", "tpcds", 17, 0.25, ()),
    ("q3_sf10", "tpch", 3, 10.0, ()),
    ("q5_sf10", "tpch", 5, 10.0, ()),
    ("q1_sf100", "tpch", 1, 100.0, BIG_PAGES),
]


def _seeded_misestimate_sweep(runner, label: str, dag,
                              failures: list) -> int:
    """ISSUE 15: drive the runtime re-planner over this DAG with
    SYNTHETIC >=10x-off observations (alternating over- and under-
    estimates, plus an 80/20 skewed partition histogram) at every
    stage boundary, and require the LIVE DAG to pass STRICT
    verification after each replan — whether the mutation applied or
    rolled back. This is the adaptive analog of the broken-plan
    mutation suite: the re-planner must never leave the DAG in a
    state the verifier cannot prove. Returns the number of applied
    re-plans (0 = every boundary was a no-op or clean rollback)."""
    from presto_tpu.adaptive import Replanner, StageStats
    from presto_tpu.exec import plan_check as PC

    ex = runner.executor
    rp = Replanner(ex, dag, broadcast_rows=1 << 21,
                   max_replans=16, strict=True)
    dispatched: set = set()
    applied = 0
    for frag in dag.fragments:
        dispatched.add(frag.fid)
        est = max(int(ex.estimate_rows(frag.root)), 2)
        obs = est * 10 if frag.fid % 2 else max(est // 10, 1)
        hot = max(int(obs * 0.8), 1)
        rp.observe(StageStats(
            fid=frag.fid, rows=obs, bytes=obs * 16,
            part_rows=(hot, max(obs - hot, 0)),
            part_bytes=(hot * 16, max(obs - hot, 0) * 16),
            task_rows=(obs // 2, obs - obs // 2),
            # ISSUE 17: measured wire bytes 8x under raw (a typical
            # per-column codec ratio) so the sweep drives the
            # freight-costed broadcast test through replan+verify
            wire_bytes=obs * 2,
        ))
        out = rp.replan(set(dispatched))
        if out is not None and not out.rejected:
            applied += 1
        try:
            PC.verify_dag(ex, dag, strict=True)
        except PC.PlanCheckError as e:
            failures.append((label, [
                f"[adaptive seeded-misestimate, after stage "
                f"{frag.fid}] {v}" for v in e.violations]))
            print(f"# {label}: ADAPTIVE SWEEP FAILED after stage "
                  f"{frag.fid}", file=sys.stderr)
            return applied
    return applied


def _wire_misestimate_case(failures: list) -> None:
    """ISSUE 17: one seeded wire-misestimate pin. A build whose RAW
    spool bytes blow the broadcast byte share but whose MEASURED
    post-codec wire bytes fit (scan-ordered keys delta+deflate to
    almost nothing) must pass the re-planner's
    broadcast test — and the pre-wire-stats behavior (raw-byte
    costing) must be reproduced exactly by wire_bytes=0, so legacy
    producers never get mis-flipped."""
    from presto_tpu.adaptive import Replanner, StageStats

    rp = Replanner(None, None, broadcast_bytes=1 << 20)
    kw = dict(fid=0, rows=1 << 16, part_rows=(1 << 16,),
              part_bytes=(1 << 24,), task_rows=(1 << 16,))
    raw_only = StageStats(bytes=1 << 24, **kw)
    measured = StageStats(bytes=1 << 24, wire_bytes=1 << 18, **kw)
    still_fat = StageStats(bytes=1 << 24, wire_bytes=1 << 22, **kw)
    checks = [
        (not rp._fits_broadcast(raw_only),
         "raw 16MiB build with no wire stats must NOT fit a 1MiB "
         "broadcast share"),
        (rp._fits_broadcast(measured),
         "16MiB build measuring 256KiB on the wire must fit a 1MiB "
         "broadcast share"),
        (not rp._fits_broadcast(still_fat),
         "build measuring 4MiB on the wire must NOT fit a 1MiB "
         "broadcast share"),
    ]
    bad = [msg for ok, msg in checks if not ok]
    if bad:
        failures.append(("wire-misestimate case", bad))
        for msg in bad:
            print(f"# wire-misestimate case: {msg}", file=sys.stderr)
    else:
        print("# wire-misestimate case: ok", file=sys.stderr)


def _ici_flip_case(failures: list) -> None:
    """ISSUE 18: one seeded ICI-vs-spool flip pin. Identical freight,
    two observed planes: a spooled build whose wire bytes fit the
    broadcast byte share flips to broadcast, but the SAME build
    observed on the ICI plane (ici_bytes > 0 — its repartition edge
    already lowered to the in-program all_to_all) must NOT flip:
    broadcast reads are spool reads, so the flip would move freight
    the current plan ships over the interconnect back onto the
    serde+HTTP wire. The re-planner charges that an ICI_WIRE_RATIO
    budget handicap (adaptive/replanner.py)."""
    from presto_tpu.adaptive import Replanner, StageStats

    rp = Replanner(None, None, broadcast_bytes=1 << 20)
    kw = dict(fid=0, rows=1 << 14, part_rows=(1 << 14,),
              part_bytes=(1 << 19,), task_rows=(1 << 14,))
    spooled = StageStats(bytes=1 << 19, wire_bytes=1 << 19, **kw)
    on_ici = StageStats(bytes=1 << 19, ici_bytes=1 << 19, **kw)
    tiny_on_ici = StageStats(bytes=1 << 13, ici_bytes=1 << 13, **kw)
    checks = [
        (rp._fits_broadcast(spooled),
         "512KiB spooled build must fit a 1MiB broadcast share "
         "(the spool-plane flip this case contrasts against)"),
        (not rp._fits_broadcast(on_ici),
         "the SAME 512KiB build observed on the ICI plane must NOT "
         "flip — broadcast would move its freight back onto the "
         "wire"),
        (rp._fits_broadcast(tiny_on_ici),
         "an 8KiB ICI-plane build must still flip (fits even the "
         "ICI_WIRE_RATIO-shrunk share — truly tiny builds beat any "
         "exchange)"),
    ]
    bad = [msg for ok, msg in checks if not ok]
    if bad:
        failures.append(("ici-flip case", bad))
        for msg in bad:
            print(f"# ici-flip case: {msg}", file=sys.stderr)
    else:
        print("# ici-flip case: ok", file=sys.stderr)


def _audit_one(runner, label: str, sql: str, failures: list,
               dag_stats: list, replans: list) -> None:
    from presto_tpu.dist.fragmenter import fragment_dag
    from presto_tpu.exec import plan_check as PC

    try:
        plan = runner.plan(sql)
    except Exception as e:  # noqa: BLE001 - a plan failure is a verdict
        failures.append((label, [f"planning failed: {e!r}"]))
        print(f"# {label}: PLANNING FAILED {e!r}", file=sys.stderr)
        return
    try:
        PC.verify(runner.executor, plan, strict=True)
    except PC.PlanCheckError as e:
        failures.append((label, e.violations))
        print(f"# {label}: {len(e.violations)} violation(s)",
              file=sys.stderr)
        for v in e.violations:
            print(f"#   - {v}", file=sys.stderr)
        return
    # ISSUE 7: fragment the SAME plan through the general stage-DAG
    # cutter and verify the resulting multi-stage DAG (RemoteSource
    # types vs origin-fragment output across every exchange hop,
    # repartition-key sanity, co-partitioned join agreement). Pure
    # host planning — no trace/compile — so the sweep stays cheap.
    try:
        dag = fragment_dag(runner.executor, plan, runner.catalogs)
    except Exception as e:  # noqa: BLE001 - a cut failure is a verdict
        failures.append((label, [f"fragment_dag failed: {e!r}"]))
        print(f"# {label}: FRAGMENTATION FAILED {e!r}",
              file=sys.stderr)
        return
    if dag is not None:
        try:
            PC.verify_dag(runner.executor, dag)
        except PC.PlanCheckError as e:
            failures.append((label, [f"[stage-dag] {v}"
                                     for v in e.violations]))
            print(f"# {label}: {len(e.violations)} DAG violation(s)",
                  file=sys.stderr)
            for v in e.violations:
                print(f"#   - {v}", file=sys.stderr)
            return
        dag_stats.append(len(dag.fragments))
        # ISSUE 15: the seeded-misestimate adaptive sweep runs over
        # the SAME (already statically-verified) DAG — mutating it is
        # fine, nothing re-reads it after this point
        applied = _seeded_misestimate_sweep(runner, label, dag,
                                            failures)
        replans.append(applied)
        print(f"# {label}: ok ({len(dag.fragments)}-stage dag, "
              f"{applied} seeded re-plans)", file=sys.stderr)
    else:
        print(f"# {label}: ok (not dag-distributable)",
              file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale-plans", action="store_true",
                    help="SCALE_PLANS only")
    ap.add_argument("--corpus", choices=("tpch", "tpcds", "all"),
                    default=None, help="corpus only (default both "
                    "plus SCALE_PLANS)")
    ap.add_argument("--sf", type=float, default=0.001,
                    help="corpus scale factor (planning-only)")
    args = ap.parse_args()
    do_scale = args.scale_plans or args.corpus is None
    corpora = ([] if args.scale_plans else
               ["tpch", "tpcds"] if args.corpus in (None, "all")
               else [args.corpus])

    t0 = time.time()
    failures: list = []
    dag_stats: list = []
    replans: list = []
    n = 0
    _wire_misestimate_case(failures)
    _ici_flip_case(failures)
    if do_scale:
        for name, suite, qid, sf, props in SCALE_PLANS:
            runner = make_runner(suite, sf, props)
            _audit_one(runner, f"scale {name}",
                       queries(suite)[qid], failures, dag_stats,
                       replans)
            n += 1
    for suite in corpora:
        runner = make_runner(suite, args.sf)
        for qid, sql in sorted(queries(suite).items()):
            _audit_one(runner, f"{suite} q{qid}", sql, failures,
                       dag_stats, replans)
            n += 1
    wall = time.time() - t0
    multi = sum(1 for s in dag_stats if s >= 2)
    print(f"# plan_audit: {n} plans, {len(failures)} with violations, "
          f"{len(dag_stats)} dag-distributable "
          f"({multi} multi-stage), {sum(replans)} seeded adaptive "
          f"re-plans applied, {wall:.1f}s", file=sys.stderr)
    if failures:
        print("PLAN AUDIT FAILED:")
        for label, violations in failures:
            for v in violations:
                print(f"  {label}: {v}")
        return 1
    print(f"plan audit clean: {n} plans verified "
          f"({len(dag_stats)} stage DAGs, {sum(replans)} seeded "
          f"adaptive re-plans) in {wall:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
