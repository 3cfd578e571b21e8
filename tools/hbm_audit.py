"""Static per-rung HBM-footprint audit: predict every device buffer a
rung's plan will allocate (exec/membudget.py — the SAME sizing
functions the executor calls, so prediction and execution cannot
drift), check the prediction against the device-memory budget and the
>=4M-row fault line, and optionally execute the rung to compare
the model against the measured peak.

Exit status (wired into bench.py --prewarm so regressions surface
before timing):
  0  every planned buffer fits its budget and the fault line, and —
     with --execute — the model's largest buffer is within 2x of the
     measured peak_device_bytes
  1  a pipeline plans over budget / over the fault line, or the model
     missed the measured peak by more than 2x

Usage: hbm_audit.py {tpch|tpcds} QID SF [k=v session props...]
                    [--execute] [--budget BYTES] [--fault-rows N]

--budget / --fault-rows force the governor's inputs (e.g. audit an
SF10 plan under TPU assumptions from a CPU box: --fault-rows 2097152).
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import configure_jax, make_runner, queries  # noqa: E402


def main() -> int:
    argv = list(sys.argv[1:])
    budget = fault = None
    execute = "--execute" in argv
    if execute:
        argv.remove("--execute")
    if "--budget" in argv:
        i = argv.index("--budget")
        budget = int(argv[i + 1])
        del argv[i:i + 2]
    if "--fault-rows" in argv:
        i = argv.index("--fault-rows")
        fault = int(argv[i + 1])
        del argv[i:i + 2]
    suite, qid, sf = argv[0], int(argv[1]), float(argv[2])
    props = argv[3:]
    configure_jax()
    from presto_tpu.exec import membudget as MB

    runner = make_runner(suite, sf, props=props)
    ex = runner.executor
    if budget is not None:
        ex.device_memory_budget = budget
    if fault is not None:
        ex.fault_rows = fault
    plan = runner.plan(queries(suite)[qid])
    report = MB.audit(ex, plan)
    print(MB.render(report))
    rc = 0
    for b in report.over_fault_line():
        print(f"OVER FAULT LINE: {b.label} plans {b.rows} rows "
              f">= {report.fault_rows}")
        rc = 1
    for b in report.over_budget():
        print(f"OVER BUDGET: {b.label} plans {b.bytes} bytes "
              f"> {report.budget}")
        rc = 1
    if execute:
        from presto_tpu.devsync import drain

        ex._pending_overflow = []
        ex.peak_memory_bytes = 0
        ex.memory_chunked_pipelines = 0
        pages = list(ex.pages(plan))
        drain(pages)
        ex._release_stream_cache()
        measured = ex.peak_memory_bytes
        model = report.max_buffer_bytes
        print(f"measured peak_device_bytes={measured} "
              f"model max buffer={model} "
              f"memory_chunked_pipelines={ex.memory_chunked_pipelines}")
        # the model sizes ALLOCATIONS; the measured peak is the largest
        # page the accounting saw. >2x apart in either direction means
        # the model no longer describes the executor — fail loudly.
        if measured and model and (
            model > 2 * measured or measured > 2 * model
        ):
            print(f"MODEL MISS: model {model} vs measured {measured} "
                  f"(>2x apart)")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
