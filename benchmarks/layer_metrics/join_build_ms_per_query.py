"""executor: host time a statement spends building its stored joins'
lookup structures: the sum of the ``join_build`` spans under the
``execute`` phase of /v1/query/{id}'s ``phases`` (one a join whose
build side is a stored table, once a statement: source lookup to the
build program's enqueue; the device's part is
``join_build_device_ms_per_query``), mean over the window's statements,
in milliseconds. A program without the span, a statement without a
stored join or a run without ``spans`` gives nothing to read."""

import statistics


def build_us(query_info):
    """The statement's ``join_build`` spans' summed length, or None
    where its ``execute`` phase lists none."""
    for p in (query_info or {}).get("phases", ()):
        if p["kind"] == "execute" and "spans" in p:
            us = [s["endUs"] - s["startUs"] for s in p["spans"]
                  if s["kind"] == "join_build"]
            return sum(us) if us else None
    return None


def read(ctx):
    xs = [us / 1e3 for us in (build_us(s.query_info)
                              for s in ctx["samples"]) if us is not None]
    return statistics.fmean(xs) if xs else None
