"""kernels: device time of the join family's programs per statement.
The trace's ``programs`` are the ``XLA Modules`` events summed by name,
``jit_<label>(<fingerprint>)``; the program's registry
(presto_tpu/exec/programs.py) gives each label its operator family.
Summed over the programs of family ``join`` and divided like
``device_busy_ms_per_query``, by the shares of the statements that ran
inside the recorded stretch. **The reduction keeps the ten programs
with most device time only**: a join program below them is left out, so
this is a floor. A program without the registry names its join programs
``jit__unknown`` and gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    n = sum(share for _st, share in ctx["traced_statements"])
    if trace is None or not n:
        return None
    try:
        from presto_tpu.exec.programs import family_of
    except ImportError:
        return None
    join_s = sum(seconds for name, seconds in trace["programs"]
                 if family_of(name) == "join")
    return join_s * 1e3 / n
