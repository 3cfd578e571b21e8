"""front end: parse, plan and fragment. Median over the window's
statements of the time from the start of the query's trace (taken as
runner.execute begins) to the start of its first ``execute`` span, as
/v1/query/{id} reports them (whole milliseconds: the program has no
parse or plan span yet)."""

import statistics


def read(ctx):
    xs = [s.query_info["stages"][0]["startMs"]
          for s in ctx["samples"]
          if s.query_info and s.query_info.get("stages")]
    return statistics.median(xs) if xs else None
