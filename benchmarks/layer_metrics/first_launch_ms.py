"""executor: what a statement pays inside ``execute`` before the device
is given anything: from the start of the ``execute`` phase to the start
of its first ``launch`` span (``spans`` of /v1/query/{id}'s ``phases``,
microseconds from submission): plan verification, cache points, the
walk down the plan to the first program's call. Median over the
window's statements, in milliseconds. A statement that launched
nothing, or a program whose phases carry no ``spans``, gives nothing to
read."""

from benchmarks.harness.manifest import load_module

median_ms = load_module("layer_metrics",
                        "execute_self_ms_per_query").median_ms


def first_launch_us(phase):
    starts = [s["startUs"] for s in phase["spans"]
              if s["kind"] == "launch"]
    return min(starts) - phase["startUs"] if starts else None


def read(ctx):
    return median_ms(ctx, first_launch_us)
