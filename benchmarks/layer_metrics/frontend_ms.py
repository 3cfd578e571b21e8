"""front end: parse (with session and access check) and plan (analyze,
plan, optimize, fragment, up to the instant the executor takes the
plan): the ``parse`` and ``plan`` phases of the statement's own trace,
as /v1/query/{id} reports them under ``phases`` (microseconds from
submission). Median over the window's statements. A program without
these spans gives nothing to read."""

import statistics


def phase_us(query_info, kinds):
    """Summed length in microseconds of the phases of these kinds, or
    None where the statement's info has no such phase."""
    spans = [p for p in (query_info or {}).get("phases", ())
             if p["kind"] in kinds]
    if not spans:
        return None
    return sum(p["endUs"] - p["startUs"] for p in spans)


def read(ctx):
    xs = [us / 1e3 for us in (phase_us(s.query_info, ("parse", "plan"))
                              for s in ctx["samples"]) if us is not None]
    return statistics.median(xs) if xs else None
