"""executor: host time inside the calls of device programs per
statement (``dispatch_wall_us``: trace-cache lookup, argument handling,
enqueue; where the runtime makes a call wait for room in its queue,
that wait too), in milliseconds. A program without the counter gives
nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    us = per_statement(ctx, "dispatch_wall_us")
    return None if us is None else us / 1e3
