"""executor: grace-join partitions per statement
(``spill_partitions_used``), the passes the buffer ceiling forces on a
build that does not fit. Serial path only: the concurrent server does
not add this counter up across its per-query executors."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    if ctx["concurrent"]:
        return None
    return per_statement(ctx, "spill_partitions_used")
