"""kernels: the joins' share of their memory roofline: the time the
chip needs to stream every touched column of every table of the
statements once (harness/scanbytes.py over the reference's tables, the
floor whatever implements the join) at its peak HBM bandwidth, over the
device time of the join family's build and probe programs
(``join_build_device_ms_per_query`` + ``join_probe_device_ms_per_query``
before their division). Bound: memory. A statement counts by the share
of it that ran inside the recorded stretch."""

from benchmarks.harness.manifest import load_module

join_seconds = load_module(
    "layer_metrics", "join_build_device_ms_per_query").join_seconds


def read(ctx):
    parts = [join_seconds(ctx, build) for build in (True, False)]
    spent = sum(p[0] for p in parts if p is not None)
    total = sum(ctx["scan_bytes"](st) * share
                for st, share in ctx["traced_statements"])
    if not spent or not total:
        return None
    return 100.0 * total / ctx["peaks"]["hbm_bytes_per_s"] / spent
