"""THE device-program registry and launch point.

Every XLA program the executor makes, on one device or over a mesh
(``d_*``: the ``shard_map`` programs of dist/executor.py), is jitted
under the LABEL its canonical key begins with (``("filter", ...)``,
``("agg_partial", ...)``), so a device trace's ``XLA Modules`` line shows
``jit_<label>(<fingerprint>)`` — the only handle on device time by
engine operator: ``XLA Ops`` events carry no framework op name, so a
``jax.named_scope`` inside the program never reaches the trace.

PROGRAM_LABELS declares every label once with its operator family (the
exec/counters.QUERY_COUNTERS discipline applied to program names):
tests/test_program_launch.py fails when a ``_jit`` key in the engine
begins with an undeclared label.

``launch`` is the one place a program made here is called: it counts
the call (``device_launches``), the host time inside it
(``dispatch_wall_us``: trace-cache lookup, argument handling, enqueue —
and, on a program's first call, tracing, lowering and the compile or
cache load) and annotates it on the profiler's host plane
(``launch:<label>``), all on the CALLING executor: the concurrent server
shares one jit cache between per-query executors, so a program must
never count on the executor that happened to build it.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from presto_tpu.obs.trace import annotation

# operator families a label belongs to (benchmarks/layer_metrics reads
# device time by family off the trace's program names)
FAMILIES = ("scan", "filter_project", "join", "agg", "sort_topn",
            "window", "exchange", "other")

# label (first element of the program's canonical key) -> family
PROGRAM_LABELS: Dict[str, str] = {
    # generate(+filter+project+join probe+partial agg) steps over splits
    "fused": "scan",
    "fused_batch": "scan",
    "xq_batch": "scan",
    "scan_gen": "scan",
    # the same steps over a table stored on the device
    # (connectors/cached.py): the split's columns are slices of the
    # program's arguments, not generated, so a trace tells a stored
    # scan from a generated one
    "stored": "scan",
    "stored_batch": "scan",
    # the store's own: a loaded page written into the resident buffers
    # in place, and the pages() path's read of one split
    "resident_store": "scan",
    "resident_read": "scan",
    "filter": "filter_project",
    "filter_lazy": "filter_project",
    "project": "filter_project",
    "limit": "filter_project",
    "unnest": "filter_project",
    "groupid": "filter_project",
    "latemat_lift": "filter_project",
    "latemat_fin": "filter_project",
    "stream_compact1": "filter_project",
    "stream_compact2": "filter_project",
    "join_build": "join",
    "join_probe": "join",
    "join_probe_unique": "join",
    "radix_build": "join",
    "radix_probe": "join",
    "pallas_ubuild": "join",
    "pallas_probe": "join",
    # a join whose build side is a stored table (connectors/cached.py):
    # the build, one program over the whole stored table that makes
    # its lookup structure once a statement, and the fused scan step
    # (one split, a batch of splits) whose step list probes such
    # structures, handed to it as arguments
    "stored_build": "join",
    "stored_probe": "join",
    "stored_probe_batch": "join",
    "semi": "join",
    "cross": "join",
    "genjoin": "join",
    "genjoin_win": "join",
    "partfilter": "join",
    "agg_partial": "agg",
    "agg_merge": "agg",
    "agg_final": "agg",
    "gagg_partial": "agg",
    "gagg_final": "agg",
    "markdistinct": "agg",
    # not "sort": an eager jnp.sort's program is jit_sort
    "sort_page": "sort_topn",
    "topn_local": "sort_topn",
    "topn_merge": "sort_topn",
    "window": "window",
    "dev_repart": "other",
    # over a mesh (dist/executor.py): shard-local operators fall into
    # the families they have on one device; the programs that move
    # rows between chips (all_to_all, all_gather, the residue split of
    # a replicated page) are the family "exchange"
    "d_scan": "scan",
    # a scan round's whole chain (generator, generated joins, filter,
    # project) in one shard_map program, as "fused" is on one device
    "d_fused": "scan",
    # a batch of scan rounds in one program: d_fused's body once a
    # split in a sequential loop, as "fused_batch" is on one device
    "d_fused_batch": "scan",
    "d_filter": "filter_project",
    "d_project": "filter_project",
    "d_unnest": "filter_project",
    "d_groupid": "filter_project",
    "d_uid": "filter_project",
    "d_stream_compact1": "filter_project",
    "d_stream_compact2": "filter_project",
    "d_genjoin": "join",
    "d_genjoin_win": "join",
    "d_semi": "join",
    "d_probe": "join",
    "d_outer": "join",
    "d_cross": "join",
    "d_agg_partial": "agg",
    "d_agg_final": "agg",
    "d_gagg_partial": "agg",
    "d_topn_local": "sort_topn",
    "d_topn_merge": "sort_topn",
    "d_repartition": "exchange",
    "d_residue": "exchange",
    "d_gather": "exchange",
    "d_ici_exchange": "exchange",
}


# the launches program_launches counts: a fused scan step on one
# device, a scan round (D splits, one a chip) or a batch of rounds
# over a mesh
FUSED_SCAN_LABELS = frozenset(("fused", "fused_batch", "xq_batch",
                               "stored", "stored_batch",
                               "stored_probe", "stored_probe_batch",
                               "d_scan", "d_fused", "d_fused_batch"))


def label_of(key) -> str:
    """The label a canonical program key begins with."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return key if isinstance(key, str) else "program"


def family_of(program_name: str) -> Optional[str]:
    """The family of a program as a device trace names it,
    ``jit_<label>(<fingerprint>)`` or ``jit_<label>``; None for a
    program this registry did not name (an eager ``jnp`` call's
    ``jit_gather``)."""
    name = program_name.split("(", 1)[0]
    if not name.startswith("jit_"):
        return None
    return PROGRAM_LABELS.get(name[len("jit_"):])


class Program:
    """One jitted program under its label. Holds no executor: the jit
    cache that keeps it may be shared between executors."""

    __slots__ = ("label", "note", "jitted", "donates", "fused_scan",
                 "exchange")

    def __init__(self, label: str, fn, donates: bool = False,
                 **jit_kwargs):
        import jax

        def program(*args, **kwargs):
            return fn(*args, **kwargs)

        # what XLA calls the module: jit_<label>
        program.__name__ = program.__qualname__ = label
        self.label = label
        self.note = f"launch:{label}"  # built once, not per call
        self.jitted = jax.jit(program, **jit_kwargs)
        self.donates = donates
        # the launches program_launches counts (split-batched scans,
        # a mesh's scan rounds)
        self.fused_scan = label in FUSED_SCAN_LABELS
        # the launches exchange_launches counts (rows change chips)
        self.exchange = PROGRAM_LABELS.get(label) == "exchange"


def launch(sink, program: Program, *args, **kwargs):
    """Call ``program`` and count the call on ``sink`` (an Executor, or
    None where no query is running on this thread)."""
    t0 = time.perf_counter_ns()
    with annotation(program.note):
        out = program.jitted(*args, **kwargs)
    if sink is not None:
        sink.count_launch(program, time.perf_counter_ns() - t0)
    return out
