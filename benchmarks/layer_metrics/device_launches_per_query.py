"""executor: calls of a device program per statement: ``device_launches``,
counted at the executor's one launch point for every program it makes
(``launches_per_query`` beside it counts the fused-scan programs only).
Eager ``jnp`` calls in driver code are not counted. Read like
``program_launches``: a gauge of the last statement on the serial path,
a process total on the concurrent path. A program without the counter
gives nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    return per_statement(ctx, "device_launches")
