"""kernels: device time per statement in the mixed cell, where the chip
is saturated and this number is what throughput turns on: the same
reading as device_busy_ms_per_query."""

from benchmarks.harness.manifest import load_module

read = load_module("layer_metrics", "device_busy_ms_per_query").read
