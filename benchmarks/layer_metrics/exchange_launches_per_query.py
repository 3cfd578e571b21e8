"""exchange: calls per statement of a program that moves rows between
chips: ``exchange_launches``, counted at the executor's one launch
point where the program's family is ``exchange`` (a part of
``device_launches``). Read like ``device_launches``: a gauge of the last
statement on the serial path, a process total on the concurrent path. A
program without the counter gives nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    return per_statement(ctx, "exchange_launches")
