"""client and protocol: what the client waits beyond the server's own
elapsed time (HTTP round trips, nextUri polling, row encode and decode).
Median over the window's statements of client wall minus the
``elapsedTimeMillis`` that /v1/query/{id} reports (whole milliseconds)."""

import statistics


def read(ctx):
    xs = [s.latency_s * 1e3 - s.query_info["elapsedTimeMillis"]
          for s in ctx["samples"] if s.query_info]
    return statistics.median(xs) if xs else None
