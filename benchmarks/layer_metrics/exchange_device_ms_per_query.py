"""exchange: device time per statement, on one chip, of the programs
that move rows between the chips of a mesh: family ``exchange`` in the
program's registry (presto_tpu/exec/programs.py): ``d_repartition``
(hash routing + ``lax.all_to_all`` + compaction of the landing zone),
``d_gather`` (``all_gather``), ``d_residue`` and the ICI exchange
program. The trace's ``programs`` are the ``XLA Modules`` events summed
by name over every chip of the trace, so the sum is divided by the
number of chips (``busy_s_by_device`` has one entry a chip), and then,
like ``agg_device_ms_per_query``, by the shares of the statements that
ran inside the recorded stretch. The time is the whole program's, its
sort and scatter as well as its collective: the collective alone is not
reduced from the trace yet. A stretch in which no program of the family
ran, or a program that does not name its mesh programs, gives nothing
to read."""


def read(ctx):
    trace = ctx["trace"]
    n = sum(share for _st, share in ctx["traced_statements"])
    if trace is None or not n:
        return None
    try:
        from presto_tpu.exec.programs import family_of
    except ImportError:
        return None
    chips = len(trace.get("busy_s_by_device") or ())
    exchange_s = [seconds for name, seconds in trace["programs"]
                  if family_of(name) == "exchange"]
    if not exchange_s or not chips:
        return None
    return sum(exchange_s) / chips * 1e3 / n
