"""Runs the per-layer metrics' readers: one file each under
``layer_metrics/``, found by the metric's name in BENCHMARK.json.

A reader is ``read(ctx) -> number or None``; None (nothing to read in
this cell) leaves the metric out of the result line. ``ctx`` holds

``samples``: the window's correct statements (traffic.Sample, each with
  ``query_info`` from /v1/query/{id} and, in a traced run,
  ``metrics_after`` from /metrics);
``traced_statements``: (statement, share) for every statement that ran
  partly or wholly inside the recorded stretch, ``share`` being the part
  of its client-side latency that fell inside it;
``metrics_start``, ``metrics_end``: /metrics at both ends of the window;
``trace``: trace.reduce()'s numbers of the sub-window, or None;
``concurrent``: whether the configuration selects the concurrent server;
``peaks``: the device's row of peaks.json;
``scan_bytes(statement)``: stored bytes a scan of the statement's
  touched columns reads (scanbytes.py).
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from . import manifest


def per_statement(ctx: Dict, name: str) -> Optional[float]:
    """An execution counter per statement. The serial path's /metrics
    shows the last statement's value (a gauge), read after every
    statement; the concurrent path's shows a process total, read at
    both ends of the window."""
    if ctx["concurrent"]:
        n = len(ctx["samples"])
        if not n or name not in ctx["metrics_end"]:
            return None
        return (ctx["metrics_end"][name] - ctx["metrics_start"][name]) / n
    xs = [s.metrics_after[name] for s in ctx["samples"]
          if s.metrics_after and name in s.metrics_after]
    return statistics.fmean(xs) if xs else None


def read_all(cell, ctx: Dict, log) -> Dict[str, Dict]:
    out = {}
    for metric in cell.per_layer:
        reader = manifest.load_module("layer_metrics", metric["name"])
        value = reader.read(ctx)
        if value is None:
            log(phase="per_layer", metric=metric["name"],
                note="nothing to read in this run")
            continue
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out
