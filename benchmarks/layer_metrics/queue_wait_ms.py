"""admission: what a statement waits before it runs (resource group,
footprint arbiter or, on the serial path, the execution lock). The
query's trace begins when it is admitted, so the wait is the server's
``elapsedTimeMillis`` minus the end of the last stage of that trace.
Median over the window's statements. The harness prints
``batch_gather_wait_ms`` per statement from /metrics beside it."""

import statistics


def read(ctx):
    xs = [s.query_info["elapsedTimeMillis"]
          - max(st["endMs"] for st in s.query_info["stages"])
          for s in ctx["samples"]
          if s.query_info and s.query_info.get("stages")]
    return statistics.median(xs) if xs else None
