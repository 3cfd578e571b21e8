"""kernels: device time of the aggregation family's programs per
statement. The trace's ``programs`` are the ``XLA Modules`` events
summed by name, ``jit_<label>(<fingerprint>)``, every program of the
recorded stretch; the program's registry (presto_tpu/exec/programs.py)
gives each label its operator family. Summed over the programs of
family ``agg`` (``agg_partial``, ``agg_merge``, ``agg_final``, the
global aggregation's pair) and divided like ``device_busy_ms_per_query``,
by the shares of the statements that ran inside the recorded stretch.
The partial aggregation fused into a scan step runs inside
``jit_fused_batch`` (family ``scan``) and is not in this number. A
stretch in which no program of the family ran gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    n = sum(share for _st, share in ctx["traced_statements"])
    if trace is None or not n:
        return None
    try:
        from presto_tpu.exec.programs import family_of
    except ImportError:
        return None
    agg_s = [seconds for name, seconds in trace["programs"]
             if family_of(name) == "agg"]
    return sum(agg_s) * 1e3 / n if agg_s else None
