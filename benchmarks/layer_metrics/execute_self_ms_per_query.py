"""executor: host time inside ``execute`` that no span names. The
``execute`` phase of the statement's own trace carries, under ``spans``
of /v1/query/{id}'s ``phases``, every program launch (``launch``), every
metered pull and staging (``xfer``), every other wait on the device
(``wait``) and every eager dispatch outside the program registry
(``eager``) of its attempts, in microseconds from submission. This is
the phase's self time: its length minus the union of those intervals,
each clipped to the phase (they may nest or overlap, and a span timed
on another thread counts once where it overlaps the driver's). Median
over the window's statements, in milliseconds. A program whose phases
carry no ``spans`` gives nothing to read."""

import statistics

from benchmarks.harness.trace import union_seconds  # of any unit


def execute_phase(query_info):
    """The statement's ``execute`` phase where it lists its spans,
    else None (no trace, or a program from before the spans)."""
    for p in (query_info or {}).get("phases", ()):
        if p["kind"] == "execute" and "spans" in p:
            return p
    return None


def covered_us(phase):
    """Length of the union of the phase's spans, clipped to it."""
    lo, hi = phase["startUs"], phase["endUs"]
    clipped = ((max(s["startUs"], lo), min(s["endUs"], hi))
               for s in phase["spans"])
    return union_seconds([(a, b) for a, b in clipped if b > a])


def self_us(phase):
    return phase["endUs"] - phase["startUs"] - covered_us(phase)


def median_ms(ctx, of_phase):
    """Median over the window's statements of ``of_phase(execute
    phase)`` (microseconds, or None), in milliseconds."""
    xs = []
    for s in ctx["samples"]:
        phase = execute_phase(s.query_info)
        us = None if phase is None else of_phase(phase)
        if us is not None:
            xs.append(us / 1e3)
    return statistics.median(xs) if xs else None


def read(ctx):
    return median_ms(ctx, self_us)
