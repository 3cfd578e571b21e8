"""kernels: device time per statement of the programs that PROBE a
join's build: family ``join`` of the program's registry less the build
programs ``join_build_device_ms_per_query`` reads. Over stored tables
that is the fused scan step whose step list holds the probes
(``stored_probe`` / ``stored_probe_batch``: the read of the probe
table's split, its filter, every stored join's lookup and gathers and
a fused partial aggregation are all inside it); the materialized joins'
``join_probe*`` / ``radix_probe`` / ``pallas_probe`` count too. Divided
like ``device_busy_ms_per_query``. Nothing where no such program ran."""

from benchmarks.harness.manifest import load_module

join_seconds = load_module(
    "layer_metrics", "join_build_device_ms_per_query").join_seconds


def read(ctx):
    got = join_seconds(ctx, build=False)
    return None if got is None else got[0] * 1e3 / got[1]
