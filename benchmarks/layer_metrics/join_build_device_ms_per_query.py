"""kernels: device time per statement of the programs that BUILD a
join's lookup structure: the trace's ``programs`` (``XLA Modules``
events summed by name, ``jit_<label>(<fingerprint>)``, every program of
the recorded stretch) whose label the program's registry
(presto_tpu/exec/programs.py) files under family ``join`` and which
ends in ``build`` (``stored_build``: one program over a whole stored
table; ``join_build``, ``radix_build``, ``pallas_ubuild``: the
materialized joins' indexes), divided like ``device_busy_ms_per_query``
by the shares of the statements that ran inside the stretch. A stretch
in which no such program ran gives nothing to read."""


def join_seconds(ctx, build: bool):
    """(seconds of the join family's build programs, or of its other
    programs, in the recorded stretch; statements' shares), or None."""
    trace = ctx["trace"]
    n = sum(share for _st, share in ctx["traced_statements"])
    if trace is None or not n:
        return None
    try:
        from presto_tpu.exec.programs import family_of
    except ImportError:
        return None
    secs = [seconds for name, seconds in trace["programs"]
            if family_of(name) == "join"
            and name.split("(", 1)[0].endswith("build") == build]
    return (sum(secs), n) if secs else None


def read(ctx):
    got = join_seconds(ctx, build=True)
    return None if got is None else got[0] * 1e3 / got[1]
