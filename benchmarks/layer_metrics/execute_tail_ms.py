"""transfer: what a statement pays inside ``execute`` after its device
work is over. The first read that blocks on the device once the last
program has been launched (a ``wait`` span, or an ``xfer`` span of a
pull, ``d2h:<label>``, that begins after the last ``launch`` span
ended: the overflow flags' pull) returns when the device is done; from
its end to the end of the ``execute`` phase are the result's pulls, the
deferred row counts and the decode. Read from ``spans`` of
/v1/query/{id}'s ``phases`` (microseconds from submission); median over
the window's statements, in milliseconds. A statement without a launch
or without such a read, or a program whose phases carry no ``spans``,
gives nothing to read."""

from benchmarks.harness.manifest import load_module

median_ms = load_module("layer_metrics",
                        "execute_self_ms_per_query").median_ms


def tail_us(phase):
    launched = [s["endUs"] for s in phase["spans"]
                if s["kind"] == "launch"]
    if not launched:
        return None
    last = max(launched)
    reads = [s for s in phase["spans"] if s["startUs"] >= last and (
        s["kind"] == "wait" or
        (s["kind"] == "xfer" and s["name"].startswith("d2h:")))]
    if not reads:
        return None
    first = min(reads, key=lambda s: s["startUs"])
    return max(phase["endUs"] - first["endUs"], 0)


def read(ctx):
    return median_ms(ctx, tail_us)
