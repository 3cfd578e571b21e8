"""device: the idle share of the chip in the mixed cell, where it moves
throughput: the same reading as device_idle_share."""

from benchmarks.harness.manifest import load_module

read = load_module("layer_metrics", "device_idle_share").read
