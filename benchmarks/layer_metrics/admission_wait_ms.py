"""admission: what a statement waits between submission and admission
(resource group, footprint arbiter or, on the serial path, the
execution lock): the ``queue`` phase of the statement's own trace, as
/v1/query/{id} reports it under ``phases`` (microseconds from
submission). Median over the window's statements. The harness prints
``batch_gather_wait_ms`` per statement from /metrics beside it. A
program without the ``queue`` span gives nothing to read."""

import statistics

from benchmarks.harness.manifest import load_module

phase_us = load_module("layer_metrics", "frontend_ms").phase_us


def read(ctx):
    xs = [us / 1e3 for us in (phase_us(s.query_info, ("queue",))
                              for s in ctx["samples"]) if us is not None]
    return statistics.median(xs) if xs else None
