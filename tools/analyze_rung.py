"""Honest per-node breakdown of one bench rung on the real chip:
EXPLAIN ANALYZE with the executor's stats_drain mode, which drains the
device execution queue after every page so per-node wall times are device
time, not dispatch time (see bench.py docstring for the timing model).

Usage: analyze_rung.py {tpch|tpcds} QID SF [k=v session props...]
"""

import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import configure_jax, make_runner, queries  # noqa: E402


def main() -> int:
    suite, qid, sf = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    configure_jax()
    runner = make_runner(suite, sf, props=sys.argv[4:])
    sql = queries(suite)[qid]
    plan = runner.plan(sql)
    ex = runner.executor
    # warm compile + first-flush out of the way (un-timed)
    t0 = time.time()
    ex.execute(plan)
    print(f"# warm run (compile + flush): {time.time() - t0:.1f}s",
          file=sys.stderr)
    ex.stats_drain = True
    # lifecycle trace for the analyzed run (obs/trace.py): the
    # critical-path and phase-split summaries below read it, and the
    # same spans back the /v1/query tree on a server
    from presto_tpu import obs as OBS

    tr = OBS.QueryTrace(f"rung-{suite}-q{qid}-sf{sf}")
    OBS.attach(ex, tr)
    t0 = time.time()
    _names, _rows, stats = ex.execute_with_stats(plan)
    total = time.time() - t0
    OBS.finalize(ex, tr, os.environ.get("PRESTO_TPU_TRACE_DIR"))
    from presto_tpu.runner import explain_text

    print(explain_text(plan, stats=stats))
    # critical path: the slowest span chain root -> leaf, plus the
    # per-kind wall split (queue vs run vs fetch on distributed
    # traces; attempt/operator locally)
    cp = OBS.critical_path(tr)
    print("# critical path: " + " -> ".join(
        f"{s['kind']}:{s['name']}={s['ms']}ms" for s in cp["chain"]
    ) if cp["chain"] else "# critical path: (no spans)",
        file=sys.stderr)
    print("# phase split (ms): " + ", ".join(
        f"{k}={v}" for k, v in cp["by_kind_ms"].items()
    ), file=sys.stderr)
    # gather accounting + fusion engagement for the analyzed run (the
    # late-materialization / fused-partial-agg observability contract)
    ctr = stats.get("counters", {})
    if ctr:
        print("# counters: " + ", ".join(
            f"{k}={ctr[k]}" for k in sorted(ctr)
        ), file=sys.stderr)
    if ctr.get("program_launches"):
        # launch amortization: at ~6ms of overhead per
        # launch, the fused scan phase's dispatch floor is launches*6ms
        print(f"# launch amortization: {ctr['program_launches']} "
              f"fused-scan launches x ~6ms per-launch overhead, "
              f"{ctr['splits_per_launch']} splits/launch "
              f"(split_batch_size folds the per-split driver loop "
              f"into XLA)", file=sys.stderr)
    # memory governor: measured largest buffer vs the
    # static model's prediction for the same plan
    from presto_tpu.exec import membudget as MB

    report = MB.audit(ex, plan)
    print(f"# hbm governor: peak_device_bytes="
          f"{ctr.get('peak_device_bytes', 0)} "
          f"(model max {report.max_buffer_bytes}, "
          f"pipeline peak {report.peak_bytes}), "
          f"memory_chunked_pipelines="
          f"{ctr.get('memory_chunked_pipelines', 0)} "
          f"(model planned {report.chunked_count})", file=sys.stderr)
    # fault tolerance (ISSUE 5): a rung that needed device-OOM
    # degradation (or, behind a DCN coordinator, task re-dispatch) is
    # reporting a real HBM-model miss — BENCH_DETAILS carries the same
    # counters so the driver's artifact shows it too
    print(f"# fault tolerance: device_oom_retries="
          f"{ctr.get('device_oom_retries', 0)} "
          f"task_retries={ctr.get('task_retries', 0)} "
          f"workers_excluded={ctr.get('workers_excluded', 0)} "
          f"deadline_ms_remaining="
          f"{ctr.get('deadline_ms_remaining', -1)}", file=sys.stderr)
    # result cache (ISSUE 10, presto_tpu/cache/): hit/miss for the
    # analyzed run plus the store's hit rate so far in this process —
    # a repeated rung with hits=0 means its plan is uncacheable or the
    # session left result_cache_enabled off
    hits = ctr.get("result_cache_hits", 0)
    misses = ctr.get("result_cache_misses", 0)
    looked = hits + misses
    print(f"# result cache: hits={hits} misses={misses} "
          f"hit_rate={hits / looked if looked else 0.0:.2f} "
          f"evictions={ctr.get('result_cache_evictions', 0)} "
          f"invalidations={ctr.get('result_cache_invalidations', 0)}",
          file=sys.stderr)
    # transfer ledger (ISSUE 12/13, exec/xfer.py): the rung's measured
    # host<->device copy tax, plus the device-resident data plane's
    # two deltas — mesh-local exchange edges (serde skipped, zero
    # crossings when device-resident) and donated-program invocations
    print(f"# transfer ledger: h2d_bytes={ctr.get('h2d_bytes', 0)} "
          f"d2h_bytes={ctr.get('d2h_bytes', 0)} "
          f"h2d_transfers={ctr.get('h2d_transfers', 0)} "
          f"d2h_transfers={ctr.get('d2h_transfers', 0)} "
          f"transfer_wall_s={ctr.get('transfer_wall_s', 0.0)} "
          f"mesh_local_exchanges={ctr.get('mesh_local_exchanges', 0)} "
          f"buffers_donated={ctr.get('buffers_donated', 0)} "
          f"ici_exchanges={ctr.get('ici_exchanges', 0)} "
          f"ici_bytes={ctr.get('ici_bytes', 0)} "
          f"pallas_kernels_used={ctr.get('pallas_kernels_used', 0)}",
          file=sys.stderr)
    print(f"# analyzed wall (incl. per-page drain overhead): {total:.2f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
