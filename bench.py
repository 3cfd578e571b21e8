#!/usr/bin/env python3
"""Benchmark ladder on the real TPU chip (BASELINE.md configs).

Driver contract: prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
and writes BENCH_DETAILS.json with every rung measured.

Process architecture (not re-verified on the current chip, see ROADMAP
A2): an earlier TPU runtime permanently degraded every kernel launch in
a process after ANY device->host read, and the dominant per-process cost
was loading compiled programs onto the device (~10s of wall per rung
even on a warm persistent compile cache, nearly zero host CPU). So
bench.py is a pure HOST-side orchestrator — it never imports jax — and
runs each phase as a bounded subprocess holding the chip exclusively:

  1. --group-child <rung>: ONE child PER RUNG (round 15 — per-rung
     isolation, so a slow/hanging rung can only lose itself), each
     preceded by a bounded per-rung --prewarm child that pays the
     compile bill into the persistent cache off the timed path (and
     whose strict plan-check/HBM-audit verdict VETOES timing a plan
     the model says faults).
     Timing protocol (round-4 discovery): on an earlier TPU runtime
     block_until_ready returned at DISPATCH, not at completion. Honest
     wall-clock = dispatch + a one-element device->host read that
     drains the FIFO execution queue (see drain() below); cycles of
     dispatch+drain are stable and repeatable. Rounds 2-3 numbers
     measured without the drain were dispatch time only. The last
     timed run's pages double as the validation artifact: bulk decode
     happens after ALL timing, and overflow-free decode at the same
     initial capacities certifies the timed runs (capacity_boost==1).
     A faulting rung loses only its group.
  2. --oracle-child: engine-vs-sqlite correctness at ORACLE_SF.
  3. --sqlite-child: wall-clock sqlite3 baselines on CPU jax (cached in
     bench_baseline.json; the child never touches the TPU).

A global deadline (BENCH_BUDGET_S, default 1200s) bounds the ladder:
each phase gets min(its cap, remaining budget); whatever happens, the
final driver JSON line prints (phases skipped for budget are recorded
in BENCH_DETAILS.json, never silently dropped).

vs_baseline: speedup vs sqlite3 executing the adapted query over the
same generated rows on this host (single-node CPU engine stand-in; the
reference repo publishes no numbers — see BASELINE.md).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# (rung name, suite, query id, scale factor, session props).
# BASELINE.md ramp order.
#
# 1M-row pages quarter the per-query launch count vs the 256k default;
# at ~6ms of overhead per launch that is the difference
# between overhead-bound and bandwidth-bound (round-4 roofline).
#
# The SF10 join rungs ran for three rounds behind a
# BENCH_INCLUDE_SF10_JOINS opt-in because fixed session thresholds
# (spill_threshold_bytes / max_join_build_rows) demonstrably failed to
# keep join-pipeline intermediates under the >=4M-row device
# fault line. The memory governor (exec/membudget.py) now sizes every
# buffer from the footprint model — builds, probe chunks, outputs,
# scan pages all stay under the fault line BY CONSTRUCTION — so the
# rungs run unconditionally with no hand-tuned props.
#
# q1_sf100 is the north-star on-ramp (BASELINE.json): the scan-agg
# pipeline streams 600M lineitem rows through fixed-size
# generation-chunked buffers batched via the split-batch path; the
# governor bounds the resident set, so scale only costs wall clock.
BIG_PAGES = ("page_rows=1048576",)
RUNGS = [
    ("q1_sf1", "tpch", 1, 1.0, BIG_PAGES),
    ("q6_sf1", "tpch", 6, 1.0, BIG_PAGES),
    ("q3_sf01", "tpch", 3, 0.1, ()),
    ("q1_sf10", "tpch", 1, 10.0, BIG_PAGES),
    ("q6_sf10", "tpch", 6, 10.0, BIG_PAGES),
    ("q3_sf1", "tpch", 3, 1.0, BIG_PAGES),
    # BASELINE rung 4 family: Q5 became plannable at scale once the
    # join tree orders FK-safe (unique-key) builds first — the
    # c_nationkey fan-out join is gone (sql/planner.py
    # _build_join_tree)
    ("q5_sf1", "tpch", 5, 1.0, BIG_PAGES),
    # BASELINE rung 5 (TPC-DS). SF0.25 keeps the largest join build
    # (store_returns, next_pow2 of 1.32M slots) under the same line.
    ("q17_sf025", "tpcds", 17, 0.25, ()),
    # BASELINE rungs 3-4 at stated scale (memory-governed; see above)
    ("q3_sf10", "tpch", 3, 10.0, ()),
    ("q5_sf10", "tpch", 5, 10.0, ()),
    # the SF100 on-ramp: scan-agg only, no join risk
    ("q1_sf100", "tpch", 1, 100.0, BIG_PAGES),
]
HEADLINE = "q1_sf1"
ORACLE_SF = 0.01  # small-SF correctness cross-check (fast)
MAX_SQLITE_SF = 1.0  # sqlite cannot hold SF10 in RAM in reasonable time
REPS = 3
DETAILS_PATH = os.path.join(REPO, "BENCH_DETAILS.json")

# columns each query touches (for the fast sqlite loader)
QUERY_COLS = {
    ("tpch", 1): {
        "lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                     "l_extendedprice", "l_discount", "l_tax",
                     "l_shipdate"]},
    ("tpch", 6): {
        "lineitem": ["l_shipdate", "l_discount", "l_quantity",
                     "l_extendedprice"]},
    ("tpch", 3): {
        "customer": ["c_custkey", "c_mktsegment"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                   "o_shippriority"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                     "l_shipdate"]},
    ("tpch", 5): {
        "customer": ["c_custkey", "c_nationkey"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
        "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                     "l_discount"],
        "supplier": ["s_suppkey", "s_nationkey"],
        "nation": ["n_nationkey", "n_name", "n_regionkey"],
        "region": ["r_regionkey", "r_name"]},
    ("tpcds", 17): {
        "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_customer_sk",
                        "ss_store_sk", "ss_ticket_number", "ss_quantity"],
        "store_returns": ["sr_returned_date_sk", "sr_item_sk",
                          "sr_customer_sk", "sr_ticket_number",
                          "sr_return_quantity"],
        "catalog_sales": ["cs_sold_date_sk", "cs_bill_customer_sk",
                          "cs_item_sk", "cs_quantity"],
        "date_dim": ["d_date_sk", "d_quarter_name"],
        "store": ["s_store_sk", "s_state"],
        "item": ["i_item_sk", "i_item_id", "i_item_desc"]},
}


def _read_details():
    if os.path.exists(DETAILS_PATH):
        with open(DETAILS_PATH) as f:
            return json.load(f)
    return {"rungs": {}}


def _write_details(details) -> None:
    with open(DETAILS_PATH, "w") as f:
        json.dump(details, f, indent=1, sort_keys=True)


def _run_child(args, timeout, env=None):
    """Run a child, return (last stdout line parsed as JSON or None,
    stderr tail)."""
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    try:
        proc = subprocess.run(
            args, capture_output=True, text=True, timeout=timeout,
            env=full_env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return None, "timeout"
    for line in reversed(proc.stdout.strip().splitlines() or []):
        if line.startswith("{"):
            try:
                return json.loads(line), proc.stderr[-300:]
            except json.JSONDecodeError:
                break
    return None, (proc.stderr[-300:] or f"rc={proc.returncode}")


# --------------------------------------------------------- orchestrator


def _groups():
    """ONE GROUP PER RUNG (ISSUE 15 satellite, ROADMAP item 2
    remainder): every rung times and validates inside its OWN
    subprocess under its own budget, so a slow or hanging rung can
    only ever lose itself — the BENCH_r03/r04 rc=124 failure mode
    (one shared-group timeout zeroing every rung's certification,
    repeated by r05's headline group) becomes structurally
    impossible. The shared program-load bill the old (suite, sf,
    props) grouping amortized is paid instead by the per-rung
    --prewarm child into the PERSISTENT compile cache, off the timed
    path, so the timing child loads executables from disk."""
    return [[rung] for rung in RUNGS]


def _group_cap(group) -> int:
    """Wall cap for one group child, sized from the MEASURED round-5
    compile bills (BENCH_r05 driver artifact: q1 86s, q6 90s, q3 338s,
    q5 133s of first-run compile on the committed cache, plus ~45s of
    gen-compile and up to ~70s resident-first each — the round-4 model
    under-capped the group and every rung lost its validation to the
    hard kill). The child also receives an internal deadline
    (BENCH_CHILD_DEADLINE_S) so it stops TIMING in time to
    decode+validate what already ran."""
    cap = 240
    for _name, suite, qid, sf, _props in group:
        is_join = (suite, qid) not in (("tpch", 1), ("tpch", 6))
        # scan-agg: 90s compile + 45s gen-compile + 70s resident-first
        # + reps/decode; join: q3 measured 338s compile + gen + reps
        cap += 600 if is_join else 300
        if suite == "tpcds":
            # Q17's 8-table cross-channel join compiles ~600s fresh
            cap += 600
        if sf >= 10:
            cap += 480 if is_join else 120
        if sf >= 100:
            cap += 900
    return cap


def main() -> int:
    import time

    # 1200s default: the driver's own (unknown) outer window killed the
    # r3 AND r4 ladders at a harder 2400s budget before the finally
    # could print — the in-process guarantee cannot survive an outer
    # SIGKILL, so the whole ladder must finish comfortably early.
    budget = float(os.environ.get("BENCH_BUDGET_S", "1200"))
    deadline = time.time() + budget
    # the oracle phase is BASELINE.md's per-rung correctness gate; r4
    # skipped it for budget. Reserve its slice up front so the timing
    # groups cannot starve it.
    oracle_reserve = float(os.environ.get("BENCH_ORACLE_RESERVE_S", "240"))
    timing_deadline = deadline - oracle_reserve
    # Stale results must not survive an early child crash: start clean.
    if os.path.exists(DETAILS_PATH):
        os.remove(DETAILS_PATH)
    details = {"rungs": {}}
    try:
        # ---- phase 1+2: timing + validation, one child per group — a
        # rung that faults the device or hangs loses only its group
        # (observed round 3: a q3_sf10 fault killed queued timings).
        for group in _groups():
            names = [g[0] for g in group]
            remaining = timing_deadline - time.time()
            if remaining < 90:
                details = _read_details()
                for n in names:
                    details["rungs"].setdefault(n, {})[
                        "time_error"] = "skipped: bench budget exhausted"
                _write_details(details)
                print(f"# group {names}: SKIPPED (budget)",
                      file=sys.stderr)
                continue
            # ---- per-rung prewarm child (ISSUE 15 satellite): pay
            # the compile bill into the persistent cache OFF the
            # timed path — bounded on its own, so a hung compile
            # costs the rung its prewarm, never its timing budget.
            # Also runs the strict plan check + static HBM audit, so
            # a rung that would fault surfaces here. Skipped when the
            # remaining budget could not fund prewarm AND timing.
            pre_cap = min(_group_cap(group),
                          remaining - _group_cap(group) * 0.5)
            if pre_cap >= 60 and not os.environ.get(
                    "BENCH_NO_PREWARM"):
                t0 = time.time()
                pinfo, perr = _run_child(
                    [sys.executable, __file__, "--prewarm",
                     ",".join(names)],
                    timeout=pre_cap,
                )
                # the prewarm child prints its JSON even when it
                # exits nonzero — its audit results, not just its
                # parseability, decide whether timing may proceed
                vetoed = set()
                if pinfo is not None:
                    vetoed = (set(pinfo.get("hbm_audit_failed") or ())
                              | set(pinfo.get("plan_check_failed")
                                    or ()))
                details = _read_details()
                for n in names:
                    r = details["rungs"].setdefault(n, {})
                    r["prewarm_s"] = round(time.time() - t0, 1)
                    if pinfo is None:
                        r["prewarm_error"] = perr
                    elif n in vetoed:
                        r["prewarm_error"] = (
                            "static audit failed (see prewarm child "
                            "output): plan-check/HBM verdict vetoes "
                            "timing")
                        r["time_error"] = (
                            "skipped: prewarm audit veto — launching "
                            "a plan the model says faults is the "
                            "hang the audit exists to prevent")
                    else:
                        r.pop("prewarm_error", None)
                _write_details(details)
                print(f"# prewarm {names}: "
                      f"{round(time.time() - t0, 1)}s"
                      + (f" VETOED {sorted(vetoed)}" if vetoed else
                         ("" if pinfo is not None
                          else f" FAILED: {perr[:120]}")),
                      file=sys.stderr)
                if vetoed:
                    # do NOT launch the timing child on a plan the
                    # static audit refused to execute
                    continue
                remaining = timing_deadline - time.time()
                if remaining < 90:
                    details = _read_details()
                    for n in names:
                        details["rungs"].setdefault(n, {})[
                            "time_error"] = ("skipped: bench budget "
                                             "exhausted after prewarm")
                    _write_details(details)
                    continue
            cap = min(_group_cap(group), remaining)
            info, err = _run_child(
                [sys.executable, __file__, "--group-child",
                 ",".join(names)],
                timeout=cap,
                # leave room to decode+validate completed rungs before
                # the hard kill
                env={"BENCH_CHILD_DEADLINE_S": str(max(cap - 90, 60))},
            )
            if info is None and err != "timeout":
                # transient compile-service failures (HTTP 500 /
                # connection resets) deserve ONE retry when budget
                # remains; a timeout does not (it would double-spend)
                remaining = timing_deadline - time.time()
                if remaining > 120:
                    print(f"# group {names}: retrying after: "
                          f"{err[:120]}", file=sys.stderr)
                    cap = min(_group_cap(group), remaining)
                    info, err = _run_child(
                        [sys.executable, __file__, "--group-child",
                         ",".join(names)],
                        timeout=cap,
                        env={"BENCH_CHILD_DEADLINE_S":
                             str(max(cap - 90, 60))},
                    )
            details = _read_details()
            if info is None:
                for n in names:
                    r = details["rungs"].setdefault(n, {})
                    if "steady_s" not in r:
                        r["time_error"] = err
                    elif "result_rows" not in r:
                        r["validate_error"] = err
                _write_details(details)
                print(f"# group {names} failed: {err}", file=sys.stderr)
        for name, *_rest in RUNGS:
            r = details["rungs"].setdefault(name, {})
            # valid = timed at a SETTLED boost whose decode was
            # overflow-free (group_child's boost ladder; absent
            # capacity_boost => the run was never certified). A rung
            # that needed a boosted capacity is still honest — the
            # timed reps ran AT that boost — it is just recorded.
            r["valid"] = bool(
                r.get("result_rows", 0) > 0  # ladder rungs are non-empty
                and r.get("capacity_boost", 0) >= 1
                and not r.get("validate_error")
            )
        _write_details(details)
        if not any(
            "steady_s" in r for r in details.get("rungs", {}).values()
        ):
            print("# all timing children failed", file=sys.stderr)
            return 1

        # ---- phase 3: sqlite baselines on CPU (cached, so usually ~0s;
        # bench_baseline.json is committed pre-populated — an uncached
        # entry is the exception, so the cap stays small and the oracle
        # reserve is honored)
        sq_budget = max(
            60, min(300, deadline - oracle_reserve - time.time())
        )
        info, err = _run_child(
            [sys.executable, __file__, "--sqlite-child"],
            timeout=sq_budget + 30,
            env={"JAX_PLATFORMS": "cpu",
                 "BENCH_SQLITE_BUDGET_S": str(sq_budget)},
        )
        cache = info or {}
        if not cache:
            # child died mid-compute: fall back to the persisted cache
            # so already-measured baselines still publish
            bp = os.path.join(REPO, "bench_baseline.json")
            if os.path.exists(bp):
                with open(bp) as f:
                    cache = json.load(f)
        for name, suite, qid, sf, _props in RUNGS:
            prefix = "" if suite == "tpch" else f"{suite}_"
            key = f"{prefix}q{qid}_sf{sf}"
            r = details["rungs"][name]
            r["sqlite_s"] = cache.get(key)
            if cache.get(key) and r.get("steady_s"):
                r["speedup_vs_sqlite"] = round(
                    cache[key] / r["steady_s"], 1
                )
        _write_details(details)

        # ---- phase 4: oracle child (engine vs sqlite at small SF) —
        # BASELINE.md's per-rung correctness gate, protected by the
        # up-front oracle_reserve so it actually runs (r4 skipped it)
        details["oracle_sf"] = ORACLE_SF
        remaining = deadline - time.time()
        if remaining < 60:
            details["oracle_ok"] = {"skipped": "bench budget exhausted"}
        else:
            info, err = _run_child(
                [sys.executable, __file__, "--oracle-child"],
                timeout=remaining,
            )
            details["oracle_ok"] = (
                info if info is not None else {"error": err}
            )
        _write_details(details)

        # ---- phase 5 (ISSUE 17): the concurrent-serving rung — the
        # loadbench batching A/B child, recorded per round like every
        # other rung. Skip-on-budget, and an SLO failure is reported
        # in the details, never allowed to zero the ladder's exit.
        remaining = deadline - time.time()
        if remaining < 180:
            details["load_skipped"] = "bench budget exhausted"
            _write_details(details)
        else:
            info, err = _run_child(
                [sys.executable, __file__, "--load"],
                timeout=remaining,
            )
            # the child wrote details["load"] itself — re-read before
            # adding the summary so it survives
            details = _read_details()
            details["load_summary"] = (
                info if info is not None else {"error": err}
            )
            _write_details(details)
        return 0
    finally:
        # the driver contract: exactly one JSON line, no matter what
        head = details.get("rungs", {}).get(HEADLINE, {})
        print(json.dumps({
            "metric": f"tpch_{HEADLINE}_wall",
            "value": head.get("steady_s", 0),
            "unit": "s",
            "vs_baseline": head.get("speedup_vs_sqlite") or 0.0,
        }))


# -------------------------------------------------------------- children


# HBM bandwidth of one v5e chip, for the efficiency metric
HBM_GBPS = 819.0
# rungs that get the device-resident (memory-connector analog) timing:
# scan = HBM read, separating data generation from query compute
RESIDENT = {"q1_sf1", "q6_sf1", "q1_sf10", "q6_sf10"}


def _col_byte_width(t) -> int:
    import numpy as np

    from presto_tpu import types as T

    if T.is_string(t):
        return 4  # dictionary codes
    if isinstance(t, T.DecimalType) and not t.is_short:
        return 16
    try:
        return np.dtype(t.numpy_dtype).itemsize
    except (TypeError, AttributeError):  # dict-coded/state types
        return 8


def group_child(only_names) -> int:
    """Time then validate the named rungs (one (suite, sf, props) group)
    in one process. D2H discipline (module docstring): all timing first,
    then validation re-runs with results kept on device, decode last.

    Attribution per rung: gen_s times the on-device
    generation of exactly the columns the query touches (scan==generate
    for the generator connectors, SURVEY §8.2.6), so steady_s can be
    read as generation + query compute. resident_steady_s (RESIDENT
    rungs) times the query over a device-resident page cache — the
    memory-connector analog where a scan is an HBM read — with
    touched_gb / eff_gbps / pct_hbm quantifying how close the query
    kernel runs to the chip's HBM bandwidth."""
    import statistics
    import time

    from tools._common import configure_jax, make_runner, queries

    jax = configure_jax()
    # merge into what earlier group children wrote
    details = _read_details()
    details["backend"] = jax.default_backend()
    details["device"] = str(jax.devices()[0])
    runners = {}

    def runner_for(suite, sf, props):
        key = (suite, sf, props)
        if key not in runners:
            runners[key] = make_runner(suite, sf, props)
        return runners[key]

    profile_dir = (
        os.path.join(REPO, "bench_profile")
        if os.environ.get("BENCH_PROFILE") else None
    )

    import zlib

    from presto_tpu import compilecache as cc
    from presto_tpu.devsync import drain

    # in-child deadline (set by the orchestrator): when timing a rung
    # would run past it, skip the REMAINING rungs and decode what
    # already timed — a hard kill would lose every rung's validation
    child_deadline = None
    if os.environ.get("BENCH_CHILD_DEADLINE_S"):
        child_deadline = (
            time.time() + float(os.environ["BENCH_CHILD_DEADLINE_S"])
        )

    selected = [r for r in RUNGS if only_names is None
                or r[0] in only_names]
    for name, suite, qid, sf, props in selected:
        if (child_deadline is not None
                and time.time() > child_deadline):
            details["rungs"].setdefault(name, {})["time_error"] = (
                "skipped: group deadline reached"
            )
            _write_details(details)
            print(f"# {name}: SKIPPED (group deadline)",
                  file=sys.stderr)
            continue
        runner = runner_for(suite, sf, props)
        ex = runner.executor
        plan = runner.plan(queries(suite)[qid])

        def run_device(ex=ex, plan=plan):
            ex._pending_overflow = []
            # transfer ledger (ISSUE 12): per-run crossing tallies so
            # BENCH_DETAILS records each rung's copy tax
            ex._reset_transfer_gauges()
            # per-run path attribution (rung discrepancies
            # were unexplainable without it): which
            # execution paths actually engaged, and how many fused-scan
            # launches the split batching left
            ex.pallas_joins_used = 0
            ex.pallas_kernels_used = 0
            ex.generated_joins_used = 0
            ex.fused_partial_aggs = 0
            ex.program_launches = 0
            ex.splits_scanned = 0
            ex.memory_chunked_pipelines = 0
            ex.peak_memory_bytes = 0
            # device-resident data plane (ISSUE 13): these never pass
            # through _begin_attempt on the raw pages() drive, so the
            # per-run reset lives here — recorded values are THIS
            # run's, not a settle+timed cumulative
            ex.buffers_donated = 0
            ex.mesh_local_exchanges = 0
            # ICI exchange plane (ISSUE 18): per-run, same reasoning
            ex.ici_exchanges = 0
            ex.ici_bytes = 0
            ex.mesh_exchange_fallbacks = 0
            ex.adaptive_replans = 0
            ex.adaptive_dist_flips = 0
            ex.adaptive_capacity_seeds = 0
            ex.adaptive_replan_rejected = 0
            ex.skew_preempted = 0
            ex.exchange_wire_bytes = 0
            ex.exchange_raw_bytes = 0
            ex.exchange_fetch_reused_conns = 0
            pages = list(ex.pages(plan))
            drain(pages)
            flags = list(ex._pending_overflow)
            # free materialized intermediates AND close their
            # PageStores: the governed tier selection can route
            # intermediates to host/disk stores with no spill props
            # set, and a bare dict reset would leak spill dirs across
            # the settle/timed/profile runs of a whole group child
            ex._release_stream_cache()
            return pages, flags

        def path_counters(ex=ex):
            return {
                "pallas_joins_used": ex.pallas_joins_used,
                # every Pallas engagement of ANY kind (joins, the
                # segmented-reduction agg, the exchange partition-id
                # pass) — ISSUE 18's kernel-coverage counter
                "pallas_kernels_used": ex.pallas_kernels_used,
                "generated_joins_used": ex.generated_joins_used,
                "fused_partial_aggs": ex.fused_partial_aggs,
                "program_launches": ex.program_launches,
                "splits_per_launch": (
                    round(ex.splits_scanned / ex.program_launches, 1)
                    if ex.program_launches else 0.0
                ),
                # memory governor (exec/membudget.py): largest single
                # device buffer this run + governed chunked rewrites
                "peak_device_bytes": ex.peak_memory_bytes,
                "memory_chunked_pipelines": ex.memory_chunked_pipelines,
                # fault tolerance: >0 means this rung survived a real
                # (or injected) device fault via the OOM-degradation
                # ladder — a slow correct rung, not a crashed one
                "device_oom_retries": ex.device_oom_retries,
                # transfer ledger (ISSUE 12, exec/xfer.py): the rung's
                # host<->device copy tax — ROADMAP item 6's
                # device-resident work is graded against these
                "h2d_bytes": ex.h2d_bytes,
                "d2h_bytes": ex.d2h_bytes,
                "h2d_transfers": ex.h2d_transfers,
                "d2h_transfers": ex.d2h_transfers,
                "transfer_wall_s": round(ex.transfer_wall_s, 6),
                # device-resident data plane (ISSUE 13): serde-free
                # same-process exchange edges + donated-program
                # invocations on the successful attempt
                "mesh_local_exchanges": ex.mesh_local_exchanges,
                "buffers_donated": ex.buffers_donated,
                # ICI exchange plane (ISSUE 18): repartition edges
                # lowered to in-program all_to_all + the bytes they
                # routed over the interconnect instead of the spool
                # serde/HTTP plane (0 on the local pages() drive —
                # nonzero only under the DCN stage scheduler, same
                # contract as adaptive_replans)
                "ici_exchanges": ex.ici_exchanges,
                "ici_bytes": ex.ici_bytes,
                "mesh_exchange_fallbacks": ex.mesh_exchange_fallbacks,
                # adaptive execution (ISSUE 15): re-plans applied at
                # stage boundaries (0 on the local pages() drive —
                # nonzero only when a rung runs the DCN stage
                # scheduler; recorded so BENCH_DETAILS carries the
                # full counter surface either way)
                "adaptive_replans": ex.adaptive_replans,
                "adaptive_dist_flips": ex.adaptive_dist_flips,
                "adaptive_capacity_seeds": ex.adaptive_capacity_seeds,
                "adaptive_replan_rejected":
                    ex.adaptive_replan_rejected,
                "skew_preempted": ex.skew_preempted,
                # wire-efficient exchange plane (ISSUE 16, dist/serde
                # + dist/connpool): post-codec vs pre-codec exchange
                # bytes and keep-alive reuse (0 on the local pages()
                # drive — the DCN boundary is where pages serialize)
                "exchange_wire_bytes": ex.exchange_wire_bytes,
                "exchange_raw_bytes": ex.exchange_raw_bytes,
                "exchange_fetch_reused_conns":
                    ex.exchange_fetch_reused_conns,
            }

        # ---- first (warm-up) run doubles as the BOOST-SETTLE loop:
        # a rung whose initial capacities overflow re-runs on the
        # shared boost ladder until its flags are clean, and the timed
        # reps then run AT the settled boost — so the recorded steady_s
        # times the configuration that actually produces correct
        # results, and validation can certify it honestly (r05's
        # q17_sf025 was timed at capacities whose output was truncated
        # and could never validate). Compile wall and steady wall stay
        # REPORTED SEPARATELY (compilecache.py counters), and the
        # first-run record persists BEFORE the timed reps — a
        # compile-bound rung that later hits the group deadline keeps
        # an honest first_run_s/compile_wall_s instead of vanishing
        # into a group timeout (BENCH_r05's q1/q6/q3/q5 group)
        from presto_tpu.exec import shapes as SH

        cc_base = cc.snapshot()
        t0 = time.time()
        ex._capacity_boost = 1
        for _attempt in range(6):
            pages, flags = run_device()
            if not any(bool(f) for f in flags):
                break
            ex._capacity_boost = SH.next_boost(ex._capacity_boost)
            print(f"# {name}: capacity overflow, retrying at boost "
                  f"{ex._capacity_boost}", file=sys.stderr)
        first_run = time.time() - t0
        ccd = cc.delta(cc_base)
        table = "lineitem" if suite == "tpch" else "store_sales"
        slots_in = runner.catalogs[suite].row_count(table)
        r = details["rungs"].setdefault(name, {})
        r.update({
            "suite": suite,
            "query": qid,
            "sf": sf,
            "props": list(props),
            "first_run_s": round(first_run, 3),
            "compile_s": round(first_run, 3),  # legacy alias
            "compile_wall_s": ccd["compile_wall_s"],
            "programs_compiled": ccd["programs_compiled"],
            "program_cache_hits": ccd["program_cache_hits"],
            "fact_slots": slots_in,
        })
        _write_details(details)
        print(f"# {name}: first run {first_run:.1f}s "
              f"(compile wall {ccd['compile_wall_s']}s over "
              f"{ccd['programs_compiled']} programs, "
              f"{ccd['program_cache_hits']} cache hits)",
              file=sys.stderr)
        if (child_deadline is not None
                and time.time() > child_deadline):
            r["time_error"] = (
                "timed reps skipped: group deadline (first run + "
                "compile wall recorded above)"
            )
            _write_details(details)
            continue
        times = []
        # adaptive reps: a rung whose first timed run is already slow
        # gets one rep — median-of-3 precision is not worth 2 extra
        # minutes of budget on a 60s+ rung
        reps = REPS
        for i in range(reps):
            t0 = time.time()
            pages, flags = run_device()
            dt = time.time() - t0
            times.append(dt)
            if i == 0 and dt > 60:
                break
        steady = statistics.median(times)
        if profile_dir and name == HEADLINE:
            # device-level (XLA/TPU) trace for the headline rung —
            # the jax.profiler hook complementing the engine-level
            # Chrome trace below (BENCH_PROFILE=1 enables)
            with jax.profiler.trace(profile_dir):
                run_device()
            r["device_profile_dir"] = profile_dir
        r.update({
            "steady_s": round(steady, 5),
            "times_s": [round(t, 5) for t in times],
            "slots_per_s": round(slots_in / steady),
            # rep-latency spread (ISSUE 9): with <=3 reps p99 is the
            # max — honest for the artifact, and the field names match
            # what the concurrent-load benchmark (ROADMAP item 1) will
            # report at real sample counts
            "p50_s": round(statistics.median(times), 5),
            "p99_s": round(max(times), 5),
        })
        r.pop("time_error", None)  # a retried group child succeeded
        print(f"# {name}: steady {steady*1e3:.1f} ms "
              f"({slots_in/steady/1e6:.0f}M slots/s), "
              f"first run {first_run:.0f}s", file=sys.stderr)
        _write_details(details)

        # ---- decode+validate IMMEDIATELY (batching
        # validation at group end meant one slow rung could void every
        # rung's certification when the group hit its deadline). The
        # last timed run's pages ARE the validation artifact — same
        # plan, same settled boost; an overflow-free decode certifies
        # the timed reps. The D2H decode cost is paid per rung now, but
        # the timing loop for THIS rung has already finished and later
        # rungs' launches were already post-first-drain.
        t0 = time.time()
        overflow = any(bool(f) for f in flags)
        rows = []
        for page in pages:
            rows.extend(page.to_pylist())
        csum = 0
        for row in rows:
            csum = (csum + zlib.crc32(repr(row).encode())) & 0xFFFFFFFF
        decode_s = time.time() - t0
        r["result_rows"] = len(rows)
        r["checksum_crc32"] = csum
        r["decode_s"] = round(decode_s, 3)
        r["wall_with_decode_s"] = round(steady + decode_s, 2)
        # path attribution for the timed run
        # + the memory governor's peak_device_bytes /
        # memory_chunked_pipelines
        r.update(path_counters())
        if overflow:
            r["validate_error"] = (
                "capacity overflow persisted through the boost ladder"
            )
        else:
            # the boost the timed reps actually ran at; 1 = initial
            # capacities, >1 = honest but boosted (recorded, valid)
            r["capacity_boost"] = ex._capacity_boost
            r.pop("validate_error", None)
        _write_details(details)
        with open(os.path.join(REPO, f"val_{name}.json"), "w") as f:
            json.dump({
                "rows": len(rows),
                "wall_with_decode_s": r["wall_with_decode_s"],
                "checksum_crc32": csum,
                "capacity_boost": r.get("capacity_boost", 0),
                "head": [str(v)[:24]
                         for v in (rows[0] if rows else [])],
            }, f)
        print(f"# validate {name}: rows={len(rows)} "
              f"decode {decode_s:.2f}s overflow={overflow} "
              f"boost={ex._capacity_boost}", file=sys.stderr)
        del pages, rows

        # ---- lifecycle trace export (ISSUE 9): one extra traced run
        # per rung when BENCH_TRACE_DIR is set — off the timed path
        # and after path_counters() snapshotted the timed run, so the
        # trace run's counter resets cannot contaminate the artifact.
        # The Chrome JSON loads in Perfetto; BENCH_DETAILS records the
        # path so the driver's artifact links timing to its timeline.
        trace_dir = os.environ.get("BENCH_TRACE_DIR")
        if trace_dir:
            from presto_tpu import obs as OBS

            tr = OBS.QueryTrace(name)
            OBS.attach(ex, tr)
            try:
                ex.execute(plan)
            finally:
                OBS.finalize(ex, tr, trace_dir)
            r["trace_path"] = os.path.join(
                trace_dir, f"{name}.trace.json")
            _write_details(details)

        # ---- generation-only attribution
        cols = QUERY_COLS.get((suite, qid))
        if cols:
            conn = runner.catalogs[suite]
            page_rows = int(runner.session.get("page_rows"))
            touched = 0
            for t, cs in cols.items():
                schema = conn.table_schema(t)
                touched += conn.row_count(t) * sum(
                    _col_byte_width(schema.column_type(c)) for c in cs
                )

            def run_gen(conn=conn, cols=cols, page_rows=page_rows):
                out = None
                for t, cs in cols.items():
                    out = list(
                        conn.pages(t, cs, target_rows=page_rows)
                    )
                drain(out)

            t0 = time.time()
            run_gen()
            gen_compile = time.time() - t0
            gtimes = []
            for _ in range(3):
                t0 = time.time()
                run_gen()
                gtimes.append(time.time() - t0)
            gen_s = statistics.median(gtimes)
            r["gen_s"] = round(gen_s, 5)
            r["gen_compile_s"] = round(gen_compile, 3)
            r["touched_gb"] = round(touched / 1e9, 3)
            r["gen_gbps"] = round(touched / gen_s / 1e9, 2)
            r["eff_gbps"] = round(touched / steady / 1e9, 2)
            r["pct_hbm"] = round(
                100.0 * touched / steady / 1e9 / HBM_GBPS, 2
            )
            print(f"# {name}: gen {gen_s*1e3:.1f} ms "
                  f"({r['gen_gbps']} GB/s), query+gen eff "
                  f"{r['eff_gbps']} GB/s = {r['pct_hbm']}% HBM",
                  file=sys.stderr)
            _write_details(details)

        # ---- device-resident (memory-connector analog) timing
        if name in RESIDENT:
            rr = make_runner(suite, sf, props, cached=True)
            rex = rr.executor
            rplan = rr.plan(queries(suite)[qid])

            def run_res(rex=rex, rplan=rplan):
                rex._pending_overflow = []
                pages = list(rex.pages(rplan))
                drain(pages)
                rex._release_stream_cache()

            t0 = time.time()
            run_res()  # fills the page cache + compiles
            res_first = time.time() - t0
            rtimes = []
            for _ in range(REPS):
                t0 = time.time()
                run_res()
                rtimes.append(time.time() - t0)
            res_steady = statistics.median(rtimes)
            r["resident_first_s"] = round(res_first, 3)
            r["resident_steady_s"] = round(res_steady, 5)
            r["resident_slots_per_s"] = round(slots_in / res_steady)
            if cols:
                r["resident_eff_gbps"] = round(
                    touched / res_steady / 1e9, 2
                )
                r["resident_pct_hbm"] = round(
                    100.0 * touched / res_steady / 1e9 / HBM_GBPS, 2
                )
            print(f"# {name}: resident steady "
                  f"{res_steady*1e3:.1f} ms "
                  f"({slots_in/res_steady/1e6:.0f}M slots/s"
                  + (f", {r['resident_pct_hbm']}% HBM" if cols else "")
                  + ")", file=sys.stderr)
            del rr, rex, rplan  # free the cached pages
            _write_details(details)

    print(json.dumps({"ok": True}))
    return 0


def prewarm_child(only_names) -> int:
    """Compile the named rungs' program sets into the persistent cache
    WITHOUT timing them (run once, results discarded): later group
    children — and later processes on this machine — load executables
    from disk instead of re-invoking the compiler. This is the SF100
    on-ramp: pay the 40+ minute partitioned-join compile once, off the
    timed path. Prints one JSON line of per-rung compile stats."""
    import time

    from tools._common import configure_jax, make_runner, queries

    configure_jax()
    from presto_tpu import compilecache as cc
    from presto_tpu.devsync import drain

    out = {"cache_dir": None, "rungs": {}}
    audit_failed = []
    plan_check_failed = []  # separate list: a schema/jit-key
    # violation is not an HBM failure and must not be reported as one
    selected = [r for r in RUNGS
                if only_names is None or r[0] in only_names]
    for name, suite, qid, sf, props in selected:
        runner = make_runner(suite, sf, props)
        ex = runner.executor
        plan = runner.plan(queries(suite)[qid])
        # pre-compile plan verification (exec/plan_check.py, strict):
        # schema edges, ladder capacities, canonical jit keys — the
        # same gate tools/plan_audit.py sweeps; a violating rung
        # surfaces here instead of minting a wrong program set
        from presto_tpu.exec import plan_check as PC

        try:
            PC.verify(ex, plan, strict=True)
        except PC.PlanCheckError as e:
            plan_check_failed.append(name)
            print(f"# prewarm {name}: PLAN CHECK FAILED\n{e}",
                  file=sys.stderr)
            out["rungs"][name] = {"plan_check_ok": False}
            continue
        # static HBM audit BEFORE anything launches (tools/hbm_audit.py
        # shares the same model): a rung whose plan would exceed the
        # budget or cross the device fault line surfaces HERE, off the
        # timed path, instead of hanging a group child
        from presto_tpu.exec import membudget as MB

        report = MB.audit(ex, plan)
        bad = report.over_fault_line() + report.over_budget()
        if bad:
            audit_failed.append(name)
            print(f"# prewarm {name}: HBM AUDIT FAILED\n"
                  + MB.render(report), file=sys.stderr)
            out["rungs"][name] = {
                "hbm_audit_ok": False,
                "planned_peak_bytes": report.peak_bytes,
            }
            # do NOT execute a plan the model says crosses the fault
            # line — launching it is exactly the hang this audit exists
            # to keep off the prewarm path
            continue
        base = cc.snapshot()
        t0 = time.time()
        ex._pending_overflow = []
        pages = list(ex.pages(plan))
        drain(pages)
        ex._release_stream_cache()  # closes disk-tier spill dirs too
        d = cc.delta(base)
        d["wall_s"] = round(time.time() - t0, 3)
        d["hbm_audit_ok"] = True  # failed-audit rungs continue'd above
        d["planned_peak_bytes"] = report.peak_bytes
        out["rungs"][name] = d
        print(f"# prewarm {name}: {d['programs_compiled']} programs, "
              f"compile wall {d['compile_wall_s']}s, "
              f"{d['program_cache_hits']} cache hits", file=sys.stderr)
    out["cache_dir"] = cc.cache_dir()
    out["hbm_audit_failed"] = audit_failed
    out["plan_check_failed"] = plan_check_failed
    print(json.dumps(out))
    return 1 if audit_failed or plan_check_failed else 0


def replay_child(only_names) -> int:
    """Result-cache replay attribution (ISSUE 10): run each selected
    rung's statement TWICE through a runner with the result cache
    enabled and record cold vs cached wall in BENCH_DETAILS —
    `replay_cold_s` is ordinary execution (plus the one publication
    D2H), `replay_cached_s` is a pure page replay that skips
    compile+launch (`replay_cache_hits` >= 1 certifies the second run
    actually served from the cache; a rung whose plan is uncacheable
    records `replay_uncacheable` instead of fake numbers). Runs as its
    own child for the same chip-isolation reasons as every other
    phase. Invoke: `python bench.py --replay [r1,r2,...]`."""
    import time

    from tools._common import configure_jax, make_runner, queries

    configure_jax()
    from presto_tpu.cache import ResultCache, uncacheable_reason
    from presto_tpu.devsync import drain

    details = _read_details()
    selected = [r for r in RUNGS
                if only_names is None or r[0] in only_names]
    out = {"rungs": {}}
    for name, suite, qid, sf, props in selected:
        runner = make_runner(suite, sf, props)
        ex = runner.executor
        plan = runner.plan(queries(suite)[qid])
        r = details["rungs"].setdefault(name, {})
        reason = uncacheable_reason(plan, runner.catalogs)
        if reason is not None:
            r["replay_uncacheable"] = reason
            out["rungs"][name] = {"uncacheable": reason}
            _write_details(details)
            continue
        # a fresh per-rung store: replay attribution, not cross-rung
        # sharing (budget sized to the rung — the point is the wall
        # delta, not eviction behavior)
        ex.result_cache = ResultCache(budget_bytes=1 << 31)
        base_hits = ex.result_cache_hits
        # un-timed warm-up: compile wall must not contaminate the
        # cold-vs-cached delta (this direct pages() stream sets no
        # cache points, so it cannot pre-populate the store either)
        ex._pending_overflow = []
        pages = list(ex.pages(plan))
        drain(pages)
        flags = list(ex._pending_overflow)
        ex._release_stream_cache()
        t0 = time.time()
        ex.execute(plan)
        cold = time.time() - t0
        t0 = time.time()
        ex.execute(plan)
        cached = time.time() - t0
        hits = ex.result_cache_hits - base_hits
        if hits == 0:
            # both passes executed for real (cacheable plan but no
            # worth-caching point selected, or the entry exceeded the
            # budget): recording a "speedup" would be run-to-run
            # variance dressed up as cache effect
            r["replay_uncacheable"] = (
                "no cache hit on the second run (no cache point "
                "selected or entry not admitted)"
            )
            out["rungs"][name] = {"uncacheable": r["replay_uncacheable"]}
            _write_details(details)
            ex.result_cache = None
            continue
        r.pop("replay_uncacheable", None)
        r.update({
            "replay_cold_s": round(cold, 5),
            "replay_cached_s": round(cached, 5),
            "replay_cache_hits": hits,
            "replay_speedup": (round(cold / cached, 1)
                               if cached > 0 else None),
        })
        out["rungs"][name] = {
            "cold_s": r["replay_cold_s"],
            "cached_s": r["replay_cached_s"],
            "hits": hits,
            "overflow_seen": any(bool(f) for f in flags),
        }
        _write_details(details)
        print(f"# replay {name}: cold {cold:.3f}s -> cached "
              f"{cached:.4f}s ({hits} cache hits)", file=sys.stderr)
        ex.result_cache = None
    print(json.dumps(out))
    return 0


def oracle_child() -> int:
    """Engine-vs-sqlite correctness at ORACLE_SF using the test suites'
    adapted oracle queries."""
    out = {}
    try:
        from tests.oracle import load_sqlite
        from tests.test_sql_tpch import ENGINE_SQL, ORACLE, compare
        from tools._common import configure_jax, make_runner

        configure_jax()
        suite_qids = sorted({(s, q) for _, s, q, _, _ in RUNGS})
        runner = make_runner("tpch", ORACLE_SF)
        db = load_sqlite(runner.catalogs["tpch"],
                         runner.catalogs["tpch"].tables())
        for suite, qid in suite_qids:
            if suite != "tpch":
                continue
            try:
                got = runner.execute(ENGINE_SQL[qid]).rows
                want = db.execute(ORACLE[qid][0]).fetchall()
                compare(qid, got, want, ORACLE[qid][1])
                out[str(qid)] = True
            except AssertionError as e:
                out[str(qid)] = f"MISMATCH: {str(e)[:200]}"
        if any(s == "tpcds" for s, _ in suite_qids):
            from tests.test_sql_tpcds import (
                _compare,
                _StddevSamp,
                ds_oracle,
            )

            dsrunner = make_runner("tpcds", ORACLE_SF)
            dsdb = load_sqlite(dsrunner.catalogs["tpcds"],
                               dsrunner.catalogs["tpcds"].tables())
            dsdb.create_aggregate("stddev_samp", 1, _StddevSamp)
            from tests.tpcds_queries import QUERIES as DS_QUERIES

            for suite, qid in suite_qids:
                if suite != "tpcds":
                    continue
                try:
                    oracle_sql, float_cols = ds_oracle(qid)
                    got = dsrunner.execute(DS_QUERIES[qid]).rows
                    want = dsdb.execute(oracle_sql).fetchall()
                    _compare(got, want, float_cols, f"Q{qid}")
                    out[f"tpcds_{qid}"] = True
                except AssertionError as e:
                    out[f"tpcds_{qid}"] = f"MISMATCH: {str(e)[:200]}"
    # noqa: BLE001 - the oracle child must ALWAYS print its JSON
    # verdict; any engine/sqlite error becomes the recorded outcome
    except Exception as e:  # noqa: BLE001 - verdict must print
        out["error"] = repr(e)[:300]
    print(json.dumps(out))
    return 0


def sqlite_child() -> int:
    """sqlite3 wall-clock baselines over the same generated rows
    (single-node CPU SQL engine stand-in); cached because they are slow
    and stable. Runs with JAX_PLATFORMS=cpu — never touches the TPU."""
    import time

    import numpy as np

    from presto_tpu import types as T
    from tools._common import make_runner

    cache_path = os.path.join(REPO, "bench_baseline.json")
    cache = {}
    if os.path.exists(cache_path):
        with open(cache_path) as f:
            cache = json.load(f)
    # computing a MISSING baseline loads whole tables into sqlite
    # (minutes at SF1); respect the orchestrator's budget and always
    # print whatever the cache holds rather than dying mid-compute
    deadline = time.time() + float(
        os.environ.get("BENCH_SQLITE_BUDGET_S", "1800")
    )

    def fast_load(connector, needed):
        import sqlite3

        db = sqlite3.connect(":memory:")
        for table, cols in needed.items():
            schema = connector.table_schema(table)

            def styp(t):
                if T.is_string(t):
                    return "TEXT"
                if T.is_floating(t):
                    return "REAL"
                return "INTEGER"

            decl = ", ".join(
                f"{c} {styp(schema.column_type(c))}" for c in cols
            )
            db.execute(f"CREATE TABLE {table} ({decl})")
            # join-key indexes: without them sqlite nested-loops the
            # multi-way joins (observed: Q5 SF1 > 35 min un-indexed);
            # indexing is standard practice for a comparison engine
            # and makes the baseline FAIRER to sqlite, not worse
            for c in cols:
                if c.endswith("key") or c.endswith("_sk"):
                    db.execute(
                        f"CREATE INDEX idx_{table}_{c} ON {table}({c})"
                    )
            ins = (f"INSERT INTO {table} VALUES "
                   f"({', '.join('?' for _ in cols)})")
            for page in connector.pages(table, cols):
                idx = np.nonzero(np.asarray(page.valid))[0]
                arrays = []
                for blk in page.blocks:
                    if isinstance(blk.data, tuple):
                        hi = np.asarray(blk.data[0])[idx].astype(object)
                        lo = np.asarray(blk.data[1])[idx].astype(object)
                        col = (hi * (1 << 64)) + (lo & ((1 << 64) - 1))
                    elif blk.dictionary is not None:
                        col = blk.dictionary.decode(
                            np.asarray(blk.data)[idx])
                    else:
                        col = np.asarray(blk.data)[idx].tolist()
                    arrays.append(col)
                db.executemany(ins, zip(*arrays))
        db.commit()
        return db

    def oracle_sql(suite, qid):
        if suite == "tpch":
            from tests.test_sql_tpch import ORACLE

            return ORACLE[qid][0]
        from tests.test_sql_tpcds import _StddevSamp, ds_oracle

        return ds_oracle(qid)[0]

    for name, suite, qid, sf, _props in RUNGS:
        prefix = "" if suite == "tpch" else f"{suite}_"
        key = f"{prefix}q{qid}_sf{sf}"
        if cache.get(key) is not None or sf > MAX_SQLITE_SF:
            continue
        if time.time() > deadline - 600:
            # one uncached rung costs MINUTES (table load + query);
            # a 60s margin would start a rung it cannot finish and the
            # orchestrator would lose the whole child to the hard kill
            print(f"# sqlite {key}: skipped (budget)", file=sys.stderr)
            continue
        try:
            runner = make_runner(suite, sf)
            t0 = time.time()
            db = fast_load(runner.catalogs[suite],
                           QUERY_COLS[(suite, qid)])
            if suite == "tpcds":
                from tests.test_sql_tpcds import _StddevSamp

                db.create_aggregate("stddev_samp", 1, _StddevSamp)
            print(f"# sqlite load {key}: {time.time()-t0:.0f}s",
                  file=sys.stderr)
            sql = oracle_sql(suite, qid)
            t0 = time.time()
            db.execute(sql).fetchall()
            first = time.time() - t0
            t0 = time.time()
            db.execute(sql).fetchall()
            cache[key] = min(first, time.time() - t0)
            # persist per entry: a later rung's timeout must not lose
            # this one's minutes of work
            with open(cache_path, "w") as f:
                json.dump(
                    {k: v for k, v in cache.items() if v is not None},
                    f, indent=1, sort_keys=True)
        except Exception:  # noqa: BLE001 - never poison the cache file
            cache[key] = None
    with open(cache_path, "w") as f:
        json.dump({k: v for k, v in cache.items() if v is not None},
                  f, indent=1, sort_keys=True)
    print(json.dumps({k: v for k, v in cache.items()
                      if v is not None}))
    return 0


def load_child() -> int:
    """ISSUE 17: the concurrent-serving rung. Runs tools/loadbench.py
    twice over the SAME fixed mixed deck (8 clients, 80% repeated
    statements, fixed seed) — cross-query batching pinned OFF, then
    ON — and records QPS / p50 / p99 / cache hit rate /
    queries_per_launch / launches_per_query for both passes into
    BENCH_DETAILS.json under "load". Passes run --no-cache so every
    statement actually executes: the A/B grades the DISPATCH plane,
    and replays launch nothing.

    SLO gate (the exit code): the batched pass must not regress p99
    past BENCH_LOAD_P99_SLO_MS (default 60000 — a hang-catcher, not a
    latency promise: BENCH_LOAD_WARMUP_S of unmeasured deck keeps
    MOST compile bills out of the window, but a fresh server can
    still mint late-width batch programs inside it; deployments
    tighten the bound via the env) and must not lose QPS to the solo
    pass beyond 20%. Like every child, the last stdout line is one
    JSON object for the driver."""
    duration = float(os.environ.get("BENCH_LOAD_DURATION_S", "10"))
    warmup = float(os.environ.get("BENCH_LOAD_WARMUP_S", "6"))
    slo_ms = float(os.environ.get("BENCH_LOAD_P99_SLO_MS", "60000"))
    out = {}
    for label, knob in (("solo", "false"), ("batched", "true")):
        info, err = _run_child(
            [sys.executable, "-m", "tools.loadbench",
             "--clients", "8", "--duration", str(duration),
             "--warmup", str(warmup),
             "--repeat-frac", "0.8", "--seed", "42", "--no-cache",
             "--batching", knob],
            timeout=(duration + warmup) * 10 + 300,
        )
        out[label] = info if info is not None else {"error": err}
        print(f"# load ({label}): "
              + (json.dumps(info, sort_keys=True) if info else err),
              file=sys.stderr)
    details = _read_details()
    details["load"] = out
    _write_details(details)
    b, s = out["batched"], out["solo"]
    failures = []
    if "error" in b or "error" in s:
        failures.append("load pass failed: "
                        + str(b.get("error") or s.get("error")))
    else:
        if b["p99_ms"] > slo_ms:
            failures.append(
                f"p99 SLO: batched {b['p99_ms']}ms > {slo_ms}ms")
        if s["qps"] > 0 and b["qps"] < 0.8 * s["qps"]:
            failures.append(
                f"QPS regression: batched {b['qps']} < 80% of "
                f"solo {s['qps']}")
    summary = {
        "metric": "loadbench_batched_p99",
        "value": b.get("p99_ms", 0),
        "unit": "ms",
        "qps_batched": b.get("qps", 0),
        "qps_solo": s.get("qps", 0),
        "queries_per_launch": b.get("queries_per_launch", 0),
        "launches_per_query_batched": b.get("launches_per_query", 0),
        "launches_per_query_solo": s.get("launches_per_query", 0),
        "slo_failures": failures,
    }
    print(json.dumps(summary))
    if failures:
        for f in failures:
            print(f"# load SLO FAILED: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if "--group-child" in sys.argv:
        i = sys.argv.index("--group-child")
        only = (
            sys.argv[i + 1].split(",")
            if len(sys.argv) > i + 1
            and not sys.argv[i + 1].startswith("-") else None
        )
        sys.exit(group_child(only))
    if "--prewarm" in sys.argv:
        i = sys.argv.index("--prewarm")
        only = (
            sys.argv[i + 1].split(",")
            if len(sys.argv) > i + 1
            and not sys.argv[i + 1].startswith("-") else None
        )
        sys.exit(prewarm_child(only))
    if "--replay" in sys.argv:
        i = sys.argv.index("--replay")
        only = (
            sys.argv[i + 1].split(",")
            if len(sys.argv) > i + 1
            and not sys.argv[i + 1].startswith("-") else None
        )
        sys.exit(replay_child(only))
    if "--oracle-child" in sys.argv:
        sys.exit(oracle_child())
    if "--sqlite-child" in sys.argv:
        sys.exit(sqlite_child())
    if "--load" in sys.argv:
        sys.exit(load_child())
    sys.exit(main())
