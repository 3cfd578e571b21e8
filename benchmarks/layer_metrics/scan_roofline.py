"""kernels: the scan's share of its memory roofline: the time the chip
needs to stream the stored bytes of the touched columns of the rows
scanned (harness/scanbytes.py) at its peak HBM bandwidth, over the
device time spent. Bound: memory. With a generated scan nothing is read
from HBM, so this is a floor for a stored-table implementation. A
statement counts by the share of it that ran inside the recorded
stretch."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    total = sum(ctx["scan_bytes"](st) * share
                for st, share in ctx["traced_statements"])
    if not total:
        return None
    least_s = total / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
