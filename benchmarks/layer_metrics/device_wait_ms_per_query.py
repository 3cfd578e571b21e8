"""transfer: host time blocked on the device per statement
(``device_wait_us``: every device-to-host pull, the forced drain and
the overflow-flag read), in milliseconds. With the chip busy all the
time this is most of a statement's latency. A program without the
counter gives nothing to read."""

from benchmarks.harness.layers import per_statement


def read(ctx):
    us = per_statement(ctx, "device_wait_us")
    return None if us is None else us / 1e3
