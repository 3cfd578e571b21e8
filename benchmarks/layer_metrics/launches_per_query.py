"""executor: device program launches per statement. On the serial path
/metrics shows the last statement's ``program_launches`` (a gauge), read
after every statement; on the concurrent path it is a process total,
read at both ends of the window. A count: it repeats exactly in a
one-client cell."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    return per_statement(ctx, "program_launches")
