"""THE host<->device transfer plane (ISSUE 12): every data-plane
crossing routes through the choke points below, and every crossing
site anywhere in the engine is declared in TRANSFER_REGISTRY.

Reference: the Java engine keeps its data plane inside the operator
tier by construction — Pages move between operators in process memory
and cross a boundary only at the serialized exchange. The TPU build
has a second, sneakier boundary: host RAM <-> HBM, crossed by
`jax.device_put` / `jax.device_get` / numpy coercions on device
values — and before this registry those crossings were scattered and
unmetered.

Two sides, one discipline (the QUERY_COUNTERS / LOCK_REGISTRY model):

  static   tools/xfercheck.py sweeps presto_tpu/ for transfer
           primitives and fails the build on any site missing from
           TRANSFER_REGISTRY, any stale registry row, any `data`-plane
           declaration outside DATA_PLANE_MODULES, and any RAW
           primitive inside a data-plane module that does not route
           through the choke points (escape:
           `# xfercheck: raw-ok - <why>` on the call line).
  dynamic  the choke points (`to_host` / `to_device` / `np_host`)
           meter every crossing — bytes, count, wall — onto the
           process totals here AND onto the thread-bound executor's
           registry counters (h2d_bytes / d2h_bytes / h2d_transfers /
           d2h_transfers + the computed transfer_wall_s), and emit an
           `xfer` span (obs.SPAN_KINDS) when that executor is traced,
           so Chrome traces show copy time as its own phase. A d2h
           pull is also host time BLOCKED ON THE
           DEVICE (the value is ready when the programs that make it
           have run): it counts on `device_wait_us` with the wait
           that crosses no page, `devsync.drain` (`device_wait`), and
           every such wait is a `wait:<site>` annotation on the
           profiler's host plane. The executor's two reads at an
           attempt's end, the overflow flags and the deferred row
           counts, are one pull each (`wait:overflow-flag`,
           `wait:row-counts`).

Sink binding is per-thread (execute()/stream_fragment() install the
running executor via swap_sink), so concurrent per-query executors on
the server never cross-count. The process totals are plain attribute
adds guarded only by the GIL — a lost increment under contention is
an acceptable metric error, never a correctness one (same stance as
the compile-cache counters).

Plane vocabulary for registry rows:
  data     the per-page query path — scan/exchange/spill/replay/
           decode pages of live queries. Only modules listed in
           DATA_PLANE_MODULES may host `data` sites.
  control  setup, admin, diagnostics, plan-time constant folding —
           crossings that never scale with query data volume.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from presto_tpu.obs.trace import annotation

# ---------------------------------------------------------------------
# site -> (direction, plane, justification)
#   direction: "h2d" | "d2h" | "h2d+d2h" (the site crosses both ways)
#   plane:     "data" | "control"  (see module docstring)
# Site names are canonical `module[.Class].function` paths under
# presto_tpu/ (tools/xfercheck.py derives them; nested defs/closures
# attribute to their enclosing top-level function, the concheck
# convention). Every row is cross-checked against a real primitive
# call site — stale rows fail the build exactly like stale
# QUERY_COUNTERS entries.
# ---------------------------------------------------------------------
TRANSFER_REGISTRY: Dict[str, Tuple[str, str, str]] = {
    # ---- the choke points themselves (the only raw-primitive sites
    # allowed in data-plane modules without an escape)
    "exec.xfer.to_host": (
        "d2h", "data",
        "THE d2h choke point: pulls a page/pytree to host numpy, "
        "metered (bytes, count, wall, span)"),
    "exec.xfer.to_device": (
        "h2d", "data",
        "THE h2d choke point: stages a host page/pytree (optionally "
        "sharded) onto the device, metered"),
    "exec.xfer.np_host": (
        "d2h", "data",
        "array-granularity d2h view: numpy coercion of one (possibly "
        "device) array, metered only when bytes actually cross"),
    # ---- page construction (the host-values -> device ingest edge)
    "page.Page.from_arrays": (
        "h2d", "data",
        "page construction stages the validity mask onto the device "
        "(column blocks stage via _encode_column) — the ingest "
        "boundary of Values/memory/test pages"),
    "page._encode_column": (
        "h2d", "data",
        "encoded column data/null arrays stage host values onto the "
        "device at page construction"),
    # ---- executor data plane
    "exec.executor.Executor._fused_stream": (
        "h2d", "data",
        "split-batched fused scans stage 2xB int64 split descriptors "
        "per batched launch (start/count vectors, not page data)"),
    "exec.executor._canonical_join_cols": (
        "h2d", "control",
        "dictionary-universe remap LUT embedded at trace time "
        "(escaped raw-ok: constant folding, sized by dictionary "
        "cardinality)"),
    "exec.executor._state_reduce": (
        "h2d", "control",
        "dictionary sort-rank LUTs embedded at trace time for min/max "
        "over dictionary columns (escaped raw-ok)"),
    "exec.executor._unnest_page": (
        "h2d", "control",
        "array-element flattening LUTs embedded at trace time "
        "(escaped raw-ok)"),
    "exec.executor.Executor._deferred_rows": (
        "d2h", "data",
        "EXPLAIN ANALYZE / trace row accounting of HOST-served pages "
        "(cache replay at the host sink, RemoteSource) reads the "
        "numpy valid mask in place: a free view, never a copy. Device "
        "pages keep a deferred count (Page.rows from the launch, else "
        "num_rows()) that _resolve_row_counts pulls after the run"),
    "exec.executor.Executor._pages_impl": (
        "h2d", "data",
        "RemoteSource ingest: deserialized exchange pages stage onto "
        "the device before entering the consumer fragment"),
    "exec.executor.Executor._join_partition_rebalanced": (
        "d2h", "data",
        "grace-join skew rebalance reads per-piece row counts (host "
        "decision point, admissible on the boosted retry path)"),
    "exec.executor.Executor._resolve_row_counts": (
        "d2h", "data",
        "the attempt's deferred (plan node, page) row counts, every "
        "node's in ONE pull after the run: 4 bytes a chip a page "
        "where the count rode in the launch, 8 a page where "
        "num_rows() made it (tracing / EXPLAIN ANALYZE only)"),
    "exec.executor.Executor._overflow_flagged": (
        "d2h", "data",
        "the attempt's deferred overflow flags, read together in ONE "
        "pull after the last launch (a byte a flag) and OR-ed on the "
        "host: the one host sync of the deferred-sync discipline"),
    "exec.executor.Executor._stage_replay": (
        "h2d", "data",
        "result-cache replay re-stage: stored host pages stage onto "
        "the device for consumers above a non-sink cache point"),
    "dist.executor.DistExecutor._stage_replay": (
        "h2d", "data",
        "mesh-path cache replay re-stage: replayed host pages commit "
        "as mesh-REPLICATED arrays (shard_map consumers with "
        "replicated in_specs need a consistent placement across "
        "every device)"),
    "exec.executor.Executor.ivm_delta_states": (
        "d2h", "data",
        "IVM refresh delta fold: partial-state pages of the delta "
        "window pull to host for persistence as view state "
        "(streaming/ivm.py; O(new rows) per refresh)"),
    "exec.executor.Executor.ivm_fold_finalize": (
        "h2d+d2h", "data",
        "IVM state merge/finalize: persisted host state pages "
        "re-stage for the agg_merge/agg_final kernels (h2d), the "
        "settled state and finalized result pull back for "
        "persistence and row decode (d2h)"),
    "exec.pagestore.PageStore.put": (
        "d2h", "data",
        "host/disk spill tiers pull materialized pages off the device "
        "(SURVEY §6.4 HBM->RAM spill)"),
    "exec.pagestore.PageStore.stream": (
        "h2d", "data",
        "spilled intermediates re-stage onto the device per restream "
        "pass"),
    # ---- result decode (the /v1/statement serialization boundary)
    "page.Page.to_pylist": (
        "d2h", "data",
        "row materialization at the client/test boundary reads the "
        "validity mask (block columns follow via _decode_block)"),
    "page._decode_block": (
        "d2h", "data",
        "column decode at the client/test boundary pulls block "
        "data/null arrays to host"),
    # ---- DCN exchange serialization plane
    "dist.serde._arrays_of": (
        "d2h", "data",
        "page wire format reads block arrays host-side; pages arrive "
        "already host at the process boundary, so bytes cross only "
        "when a caller serializes a device-resident page"),
    "dist.serde.serialize_page": (
        "d2h", "data",
        "null/validity masks of the serialized page, same boundary as "
        "_arrays_of"),
    "dist.spool._block_value_u64": (
        "d2h", "data",
        "spooled-exchange hash partitioning reads key columns of "
        "already-host pages (the one accounted pull is "
        "server.worker._execute_task's to_host)"),
    "dist.spool.row_hash_u64": (
        "d2h", "data",
        "partition-hash driver reads the validity/null masks of "
        "already-host pages"),
    "dist.spool.take_rows_host": (
        "d2h", "data",
        "per-partition compaction gathers rows of already-host pages"),
    "dist.spool.partition_host_page": (
        "d2h", "data",
        "partition split reads the validity mask of already-host "
        "pages"),
    "dist.spool.device_partition_pages": (
        "h2d+d2h", "data",
        "device-tier exchange partitioning: a host-resident input "
        "(cache replay) stages through the choke point, dictionary "
        "value-hash LUTs stage per distinct dictionary — device "
        "pages pass through free (ISSUE 13); the spool-stats plane "
        "(ISSUE 15) pulls the nparts-long per-partition row-count "
        "vector back per page"),
    "dist.spool.spool_blob": (
        "d2h", "data",
        "LAZY spool materialization: device-resident exchange pages "
        "serialize to wire bytes only when an HTTP fetch (DCN-remote "
        "consumer or replay) or budget demotion needs host bytes"),
    # ---- worker task runtime (the one real d2h of the exchange)
    "server.worker.TaskRuntime._run_task": (
        "d2h", "data",
        "fragment output leaves the device exactly once, at the "
        "serialization boundary (spooled and legacy emit paths)"),
    "server.worker.TaskRuntime.serve_cached_fragment": (
        "d2h", "data",
        "fleet cache serve (ISSUE 19): row-count readback of the "
        "replayed pages' validity masks while parking them as a "
        "pre-finished task spool — cached pages are host-resident, "
        "so np_host meters ZERO bytes unless a demoted entry "
        "rehydrated device-side"),
    # ---- distributed executor (mesh staging)
    "dist.executor.DistExecutor._round_generator": (
        "h2d", "data",
        "per-round split-start indices stage onto the mesh (D int64s "
        "per round, not page data), for the bare scan and the fused "
        "scan round alike"),
    "dist.executor.DistExecutor._fenced": (
        "d2h", "data",
        "CPU-only collective fence: blocks on program outputs to "
        "serialize rendezvous order — a sync, not a copy"),
    "dist.executor.ici_exchange_pages": (
        "h2d", "data",
        "ICI exchange staging: spooled producer pages commit onto "
        "the exchange mesh sharded over axis d (device-resident "
        "pages cross ZERO bytes — the zero-crossing half of the "
        "ledger pin; a host-resident input pays its honest h2d "
        "once) plus replicated dictionary value-hash LUTs"),
    "dist.executor._stack_to_mesh": (
        "h2d+d2h", "data",
        "local pages gather to host (d2h when device-resident) and "
        "re-stage as one mesh-sharded global array (h2d)"),
    "dist.executor.make_mesh": (
        "d2h", "control",
        "numpy object array of device HANDLES for Mesh construction — "
        "no array bytes cross"),
    # ---- diagnostics / timing
    "devsync.drain": (
        "d2h", "control",
        "forced-completion fence for honest timing "
        "(stats_drain): reads ONE element of the last leaf"),
    # ---- trace-time LUT embedding (jnp coercions of host arrays in
    # kernel builders: constant folding sized by dictionary/identity
    # cardinality, never by query data volume)
    "ops.agg._minmax_identity": (
        "h2d", "control",
        "min/max identity scalar embedded at trace time"),
    "ops.compact.concat_all": (
        "h2d", "control",
        "dictionary-code remap LUTs staged when concatenated pages "
        "carry differing dictionaries — sized by dictionary "
        "cardinality, not row count"),
    "ops.keys.equality_encoding": (
        "h2d", "control",
        "dictionary value-identity LUT embedded at trace time"),
    "ops.keys.order_encoding_parts": (
        "h2d", "control",
        "dictionary sort-rank LUT embedded at trace time"),
    "ops.window._one_function": (
        "h2d", "control",
        "dictionary sort-rank LUTs + window identity scalars embedded "
        "at trace time"),
    "connectors.tpch.TpchConnector._gen_nation_at": (
        "h2d", "control",
        "nation->region map (25 entries) embedded into the generator "
        "at trace time"),
    # ---- expression evaluation
    "expr.eval._const_val": (
        "d2h", "control",
        "plan literal -> typed numpy scalar before device staging; "
        "input is a Python constant, never a device array"),
    "expr.functions_ext._string_cast_val": (
        "d2h", "control",
        "CAST-from-string constant folding coerces a host Python "
        "value to numpy"),
    "expr.functions_ext._val_to_pylist": (
        "d2h", "data",
        "host-side lambda evaluation (array higher-order functions) "
        "pulls the element column once per distinct-argument page"),
}

# modules (canonical dotted paths under presto_tpu/) whose crossings
# are per-page query work: `data`-plane registry rows must live here,
# and raw primitives here must route through the choke points above.
DATA_PLANE_MODULES = frozenset({
    "page",
    "exec.executor",
    "exec.pagestore",
    "exec.xfer",
    "dist.executor",
    "dist.serde",
    "dist.spool",
    "cache.store",
    "server.worker",
    "expr.functions_ext",
})


# ------------------------------------------------------ process totals
class _Totals:
    """Process-lifetime transfer tallies (the /metrics and
    system.metrics overlay — per-query executors come and go on the
    concurrent server path, the process truth lives here)."""

    __slots__ = ("h2d_bytes", "d2h_bytes", "h2d_transfers",
                 "d2h_transfers", "transfer_wall_s")

    def __init__(self) -> None:
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_transfers = 0
        self.d2h_transfers = 0
        self.transfer_wall_s = 0.0


_totals = _Totals()
_tls = threading.local()


def process_totals() -> Dict[str, float]:
    """Snapshot of the process-lifetime transfer counters under the
    registry counter names (+ transfer_wall_s)."""
    return {
        "h2d_bytes": _totals.h2d_bytes,
        "d2h_bytes": _totals.d2h_bytes,
        "h2d_transfers": _totals.h2d_transfers,
        "d2h_transfers": _totals.d2h_transfers,
        "transfer_wall_s": round(_totals.transfer_wall_s, 6),
    }


def swap_sink(sink) -> Optional[object]:
    """Install ``sink`` (an Executor, or None) as THIS thread's
    metering target and return the previous one — execute()/
    stream_fragment() bracket their run with a swap/restore pair so
    nested executors and concurrent query threads never cross-count."""
    prev = getattr(_tls, "sink", None)
    _tls.sink = sink
    return prev


def current_sink() -> Optional[object]:
    """THIS thread's metering sink (an Executor, or None). The wire
    plane (dist/serde.py, dist/connpool.py) meters exchange bytes and
    connection reuse onto the same thread-bound sink the transfer
    choke points use, so the registry counters land on whichever
    executor owns the running fragment/query."""
    return getattr(_tls, "sink", None)


def _device_nbytes(tree) -> int:
    """Bytes that would cross d2h: the summed size of device-backed
    (jax.Array) leaves. numpy leaves are already host — zero."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            n += leaf.size * leaf.dtype.itemsize
    return n


def _host_nbytes(tree) -> int:
    """Bytes that would cross h2d: the summed size of host (numpy)
    leaves. jax.Array leaves are already device-resident — zero."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, np.ndarray):
            n += leaf.size * leaf.dtype.itemsize
    return n


def _wait_note(site: str):
    """The ``wait:<site>`` annotation, begun now."""
    return annotation("wait:" + site)


def _count_wait(wall: float) -> None:
    sink = getattr(_tls, "sink", None)
    if sink is not None:
        sink.count_device_wait(wall)


class _timed_site:
    """``with <kind>(site):`` around a stretch of the driver thread:
    a ``<kind>:<site>`` annotation on the profiler's host plane and,
    where the thread-bound executor is traced, a span of that kind
    (obs.SPAN_KINDS) of its open attempt, cut from one clock reading
    at each end."""

    __slots__ = ("site", "note", "t0")
    kind = ""

    def __init__(self, site: str):
        self.site = site
        self.note = annotation(f"{self.kind}:{site}")
        self.t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        self.note.__exit__(None, None, None)
        sink = getattr(_tls, "sink", None)
        if sink is not None:
            self._record(sink, wall)


class device_wait(_timed_site):
    """``with device_wait(site):`` around a host read that blocks on the
    device and crosses no page (devsync.drain): counted on
    ``device_wait_us``, annotated like the pulls below, and a ``wait``
    span."""

    __slots__ = ()
    kind = "wait"

    def _record(self, sink, wall: float) -> None:
        sink.count_device_wait(wall)
        if sink.trace is not None:
            sink.span_ending_now("wait", self.site, wall)


class eager(_timed_site):
    """``with eager(site):`` around ``jnp`` calls dispatched from the
    driver thread outside ``_jit`` (no program of the registry, no
    count on ``device_launches``): host time in which the runtime may
    hold the call while the device is behind. An ``eager`` span and
    an ``eager:<site>`` annotation; no counter."""

    __slots__ = ()
    kind = "eager"

    def _record(self, sink, wall: float) -> None:
        if sink.trace is not None:
            sink.span_ending_now("eager", self.site, wall)


def _meter(direction: str, nbytes: int, wall: float, label: str) -> None:
    if direction == "h2d":
        _totals.h2d_transfers += 1
        _totals.h2d_bytes += nbytes
    else:
        _totals.d2h_transfers += 1
        _totals.d2h_bytes += nbytes
        _count_wait(wall)
    _totals.transfer_wall_s += wall
    sink = getattr(_tls, "sink", None)
    if sink is None:
        return
    sink.count_transfer(direction, nbytes, wall)
    if sink.trace is not None:
        sink.span_ending_now("xfer", f"{direction}:{label}", wall,
                             bytes=nbytes)


def to_host(tree, label: str = "page"):
    """Pull a page/pytree to host numpy — THE metered d2h crossing.
    Already-host input passes through with nothing metered (no bytes
    cross), which is what makes host-served cache replays genuinely
    free on the counters."""
    nbytes = _device_nbytes(tree)
    if nbytes == 0:
        return tree
    t0 = time.perf_counter()
    with _wait_note(label):
        host = jax.device_get(tree)
    _meter("d2h", nbytes, time.perf_counter() - t0, label)
    return host


def to_device(tree, spec=None, label: str = "page"):
    """Stage a host page/pytree onto the device (optionally under a
    Sharding spec) — THE metered h2d crossing. Device-resident leaves
    contribute no bytes (device_put leaves them in place)."""
    nbytes = _host_nbytes(tree)
    t0 = time.perf_counter()
    with annotation("xfer:h2d:" + label):
        out = (jax.device_put(tree, spec) if spec is not None
               else jax.device_put(tree))
    if nbytes:
        _meter("h2d", nbytes, time.perf_counter() - t0, label)
    return out


def np_host(arr, label: str = "array"):
    """numpy view of ONE array, metered as d2h only when ``arr`` is
    device-backed — the accounted replacement for the scattered
    `np.asarray(block.data)` host-pull idioms (page decode, wire
    serde, spool partitioning). On an already-host array this is a
    plain np.asarray view: zero copies, zero meters."""
    if isinstance(arr, jax.Array):
        t0 = time.perf_counter()
        with _wait_note(label):
            out = np.asarray(arr)
        _meter("d2h", out.nbytes, time.perf_counter() - t0, label)
        return out
    return np.asarray(arr)
