"""Plan interpreter: chains statically-shaped jitted kernels per page, with
host-side control (capacity retries, build sizing, limit accounting) between
kernel launches.

Reference: presto-main operator/Driver.java's processFor loop moving Pages
through operator chains, SqlTaskExecution mapping splits to drivers. The TPU
translation collapses each operator's inner loop into an XLA program; the
Python host plays the Driver role only at blocking boundaries (aggregation
flush, join build, sort) and for the dynamic-cardinality escape hatch
(overflow-retry with doubled capacity, SURVEY §8.2.1).

Jit discipline: every per-page kernel is compiled once per (plan node,
page schema, capacity) and cached — expression trees and plan nodes are
hashable and ride in the jit cache key, which is the reference's
compiled-expression LRU (sql/gen/ExpressionCompiler cache) reborn.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
from typing import (
    Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.obs.trace import annotation

from presto_tpu import compilecache as CC
from presto_tpu import types as T
from presto_tpu.exec import counters as CTRS
from presto_tpu.connectors.base import Connector
from presto_tpu.exec import agg_states as S
from presto_tpu.exec import faults as FAULTS
from presto_tpu.exec import latemat as LM
from presto_tpu.exec import membudget as MB
from presto_tpu.exec import plan as P
from presto_tpu.exec import programs as PG
from presto_tpu.exec import prune as PR
from presto_tpu.exec import shapes as SH
from presto_tpu.exec import xfer as XF
from presto_tpu.expr.eval import evaluate, evaluate_filter
from presto_tpu.ops import agg as A
from presto_tpu.ops import hashing as H
from presto_tpu.ops import hll as HLL
from presto_tpu.ops import join as J
from presto_tpu.ops import keys as K
from presto_tpu.ops.compact import (
    compact_page,
    concat_all,
    gather_rows,
    slice_page,
)
from presto_tpu.ops.sort import sort_page
from presto_tpu.page import Block, Dictionary, Page


# every program-shape size quantizes through the SHARED bucket ladder
# (exec/shapes.py) — the name survives for the dist executor and tests
_next_pow2 = SH.bucket


def _row_bytes(types) -> int:
    """Static per-row footprint of a channel list (spill estimates)."""
    total = 2  # valid bit + null mask, bytewise
    for t in types:
        if isinstance(t, T.DecimalType) and not t.is_short:
            total += 16
        elif isinstance(t, T.HllStateType):
            total += 8 * HLL.WORDS  # packed register words
        elif T.is_string(t):
            total += 4  # dictionary codes
        else:
            try:
                total += np.dtype(t.numpy_dtype).itemsize
            except (TypeError, AttributeError):  # dict-coded/state
                total += 8
    return total


class _GeneratedSource:
    """What a split's columns come from when the connector generates
    them (the fused scan driver's source, Executor._fused_stream): the
    connector's traceable gen_body / gen_batch (``reads``: itself),
    and no device buffer to hand a launch. A stored table's is
    connectors/cached.StoredSource, whose ``args`` are the buffers."""

    args = ()

    def __init__(self, conn, table: str, names: tuple):
        self._conn, self._table, self._names = conn, table, names

    @property
    def reads(self):
        return self

    def body(self, n_pad: int):
        return self._conn.gen_body(self._table, n_pad, self._names)

    def batch(self, n_pad: int):
        return self._conn.gen_batch(self._table, n_pad, self._names)


# slots a stored join's direct-address table keeps a stored build slot:
# dbgen's order keys use 8 of every 32 values, so a table of keys
# 1..4N holds N orders; dense keys (custkey, suppkey) leave it 3/4
# empty, which costs bytes and no time (a gather's time does not
# depend on its operand's size above a few thousand entries, PERF.md)
STORED_JOIN_KEY_SPREAD = 4


class StoredJoin(NamedTuple):
    """What Executor._stored_join_info says of a join whose build side
    is a Filter / Project chain over a scan of a STORED table
    (connectors/cached.py): the probe key that is looked up, the
    build's scan and chain, and the size of its lookup structure."""

    pivot_ch: int          # probe channel looked up
    build_key_ch: int      # its partner, a channel of the build's root
    extra_pairs: tuple     # (probe ch, build ch) pairs checked after
    scan: object           # the build side's P.TableScan
    chain_fns: tuple       # its Filter / Project chain, bottom-up
    rows: int              # the stored table's slots
    cap: int               # entries of the direct-address table
    nbytes: int            # device bytes of the whole structure
    # what Executor._ride_stored_joins adds, from the chain alone:
    rides: bool = False    # probed inside an earlier link's build
    riders: tuple = ()     # the StoredRiders probed inside this build


class StoredRider(NamedTuple):
    """A stored join probed inside the build of the earlier join that
    carries its key (Executor._ride_stored_joins): once a BUILD row of
    that join, not once a probe slot."""

    node: object           # the rider's P.HashJoin
    info: StoredJoin       # as _stored_join_info gave it
    pivot_ch: int          # its probe key, a channel of the build page
    pairs: tuple           # its key pairs checked in the build


def _stored_link(link) -> bool:
    """Whether a _scan_chain link is a (HashJoin, StoredJoin) pair."""
    return isinstance(link, tuple) and isinstance(link[1], StoredJoin)


def _stored_join_bytes(cap: int, rows: int, types) -> int:
    """Device bytes of a stored join's lookup structure: the table's
    int32 entries, its key floor, and the build page as the chain (and
    the joins that ride on it) leave it, one row a stored slot; a
    column narrower than 8 bytes counted at 8 (_carried_wide)."""
    return 4 * cap + 8 + rows * max(_row_bytes(types), 2 + 8 * len(types))


def _plain_int(t) -> bool:
    """A key type whose values are one integer word (what a generated
    or a stored join compares as int64)."""
    return not (
        T.is_string(t) or t.is_dictionary_encoded
        or T.is_floating(t)
        or (isinstance(t, T.DecimalType) and not t.is_short)
    )


def _canonical_join_cols(
    left_blocks: List[Block], right_blocks: List[Block]
):
    """Equality-encoded uint64 key columns for a join, canonicalizing
    dictionary-coded pairs through a merged host universe so equal strings
    compare equal across differing dictionaries."""
    lcols: List[jnp.ndarray] = []
    rcols: List[jnp.ndarray] = []
    lnulls, rnulls = [], []
    for lb, rb in zip(left_blocks, right_blocks):
        if lb.dictionary is not None or rb.dictionary is not None:
            ld, rd = lb.dictionary, rb.dictionary
            # raw codes are equality-faithful only for a shared dictionary
            # WITHOUT duplicate values; transform-produced dictionaries
            # (substr/lower via _dict_map) map many codes to one value and
            # must go through the merged-universe canonicalization too
            if ld == rd and not (ld is not None and
                                 ld.has_duplicate_values()):
                lcols.append(lb.data.astype(jnp.int64).astype(jnp.uint64))
                rcols.append(rb.data.astype(jnp.int64).astype(jnp.uint64))
            else:
                universe = {}
                for d in (ld, rd):
                    for v in (d.values if d is not None else []):
                        universe.setdefault(v, len(universe))

                def canon(b, d):
                    if d is None or len(d) == 0:
                        return jnp.zeros(b.data.shape, dtype=jnp.uint64)
                    lut = np.array(
                        [universe[v] for v in d.values], np.uint64
                    )
                    codes = jnp.clip(b.data, 0, len(d) - 1)
                    # xfercheck: raw-ok - trace-time LUT embedding
                    return jnp.asarray(lut)[codes]

                lcols.append(canon(lb, ld))
                rcols.append(canon(rb, rd))
            lnulls.append(lb.nulls)
            rnulls.append(rb.nulls)
        else:
            lc = K.equality_encoding(lb)
            rc = K.equality_encoding(rb)
            lcols.extend(lc)
            rcols.extend(rc)
            lnulls.extend([lb.nulls] * len(lc))
            rnulls.extend([rb.nulls] * len(rc))
    return lcols, lnulls, rcols, rnulls


class _FoldBuffer:
    """Bounded incremental merge of partial-state pages: buffered pages
    flush into a single pcap-sized accumulator through a merge-only
    group-by whenever flush_slots accumulate. One implementation shared
    by the single-pass aggregation, the multi-pass partitioned
    aggregation, and the per-partition fold accumulators (reference:
    InMemoryHashAggregationBuilder flushing under memory pressure)."""

    def __init__(self, ex, merge_fn, pcap, max_iters, flush_slots):
        self.ex = ex
        self.merge_fn = merge_fn
        self.pcap = pcap
        self.max_iters = max_iters
        self.flush_slots = flush_slots
        self.acc = None
        self.buf: list = []
        self.slots = 0
        self.saw_input = False

    def add(self, page) -> None:
        self.saw_input = True
        if self.buf and self.slots + page.capacity > self.flush_slots:
            # pre-flush: the merge concat stays bounded by
            # acc + flush_slots + one page, never creeping past it by
            # a whole buffered batch (the governor's fold bound —
            # membudget.py — relies on this)
            self.flush()
        self.buf.append(page)
        self.slots += page.capacity
        if self.slots >= self.flush_slots:
            self.flush()

    def _merged(self):
        pages = ([self.acc] if self.acc is not None else []) + self.buf
        if not pages:
            return None
        merged = _concat_states(pages)
        self.ex._account_page(merged)
        return merged

    def flush(self) -> None:
        merged = self._merged()
        if merged is None:
            return
        out, overflow = self.merge_fn(merged, self.pcap, self.max_iters)
        self.ex._pending_overflow.append(overflow)
        self.acc, self.buf, self.slots = out, [], 0

    def final_merged(self):
        """All remaining state as one page (None if nothing was added)."""
        return self._merged()


def _concat_states(pages):
    """Partial-state pages as one page for the merge or final step: a
    concatenate a column, dispatched eagerly from the driver thread
    (no program of the registry), so an ``eager`` span: the first of
    them is where the host meets a device that is behind."""
    if len(pages) == 1:
        return pages[0]
    with XF.eager("concat-states"):
        return concat_all(pages)


class AggSizing(NamedTuple):
    """First-attempt sizes of one blocking grouped aggregation, decided
    once by Executor._agg_sizing for the partition decision, the
    compaction buffer and the single path (and for membudget.audit)."""

    cap: int            # group capacity of the single path
    compact_rows: int   # compaction accumulator slots; 0 = no compaction
    parts: int          # hash-partition passes; 1 = the single path
    # which bound decided: estimate | optimistic | boost for the
    # capacity, spill_bytes | rows_cap | bytes_cap once parts > 1
    sized_by: str

    @property
    def governed(self) -> bool:
        """The budget model, not the spill threshold, set the passes."""
        return self.sized_by in ("rows_cap", "bytes_cap")


class MemoryBudgetExceeded(RuntimeError):
    """Reference: ExceededMemoryLimitException — the query fails rather
    than thrash (SURVEY §6.4: kill-don't-spill is the v1 policy; spill to
    host RAM is the documented follow-up)."""


class QueryDeadlineExceeded(RuntimeError):
    """query_max_run_time expired (reference: QueryTracker's
    enforceTimeLimits failing queries past query.max-run-time). Raised
    at page boundaries in the execute()/stream_fragment() driver loops
    — a compiled program in flight cannot be interrupted, but the query
    can never outlive its deadline by more than one launch."""


# The device-fault classifier lives in exec/faults.py (shared with the
# DCN coordinator so the marker list cannot drift between the local
# OOM-degradation ladder and worker-error recognition); these aliases
# keep the executor's historical private names importable.
_DEVICE_FAULT_MARKERS = FAULTS.DEVICE_FAULT_MARKERS
_is_device_fault = FAULTS.is_device_fault


_donation_warning_filtered = False


def _filter_donation_warning() -> None:
    """One-time (per process) suppression of jax's 'Some donated
    buffers were not usable' UserWarning: a donated input whose
    (shape, dtype) matches no output cannot be reused and jax says so
    per program (e.g. the validity mask of a differently-sized merge
    output) — expected here, not actionable: donation is best-effort
    per buffer by design. Guarded so repeated donated-program cache
    misses never stack duplicate entries onto warnings.filters."""
    global _donation_warning_filtered
    if _donation_warning_filtered:
        return
    import warnings

    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")
    _donation_warning_filtered = True


def page_bytes(page: Page) -> int:
    """Static page footprint from shapes/dtypes (no device reads)."""
    total = page.valid.shape[0]  # bool valid
    for blk in page.blocks:
        datas = blk.data if isinstance(blk.data, tuple) else (blk.data,)
        for d in datas:
            total += d.size * d.dtype.itemsize
        if blk.nulls is not None:
            total += blk.nulls.shape[0]
    return total


@dataclasses.dataclass
class NodeStats:
    """Per-plan-node execution stats (reference: OperatorStats)."""

    label: str
    wall_s: float = 0.0
    pages: int = 0
    # one entry a page: a host int, or a device value left unread
    # until the run is over (Executor._resolve_row_counts reads them
    # all at once): the scalar of page.num_rows(), or the array a
    # launch returned with the page (Page.rows), whose sum it is
    row_counts: list = dataclasses.field(default_factory=list)

    @property
    def rows(self) -> int:
        return sum(int(np.sum(c)) for c in self.row_counts)


def _page_of(out) -> Optional[Page]:
    """The page a program's output holds: the output itself, or the
    first element of a tuple (page, flags...); None for anything
    else."""
    if isinstance(out, Page):
        return out
    if isinstance(out, tuple) and out and isinstance(out[0], Page):
        return out[0]
    return None


def _rows_key(key):
    """The jit-cache key of the program that also returns its page's
    row count; it still begins with the program's label."""
    return (*key, "rows") if isinstance(key, tuple) else (key, "rows")


def _returning_rows(fn):
    """``fn`` as a program that also returns the row count of the page
    it makes, computed in the same launch: ``(out, rows)`` with
    ``rows`` an int32[1] (under shard_map a chip's own count, so one
    entry a chip and no collective), or ``()`` where the output holds
    no page."""
    def counted(*args, **kwargs):
        out = fn(*args, **kwargs)
        page = _page_of(out)
        if page is None:
            return out, ()
        return out, jnp.sum(page.valid, dtype=jnp.int32).reshape(1)

    return counted


def _attaching_rows(run):
    """The caller's side of _returning_rows: the count travels on the
    page (Page.rows) to the pages() boundary that wants it, and the
    caller gets the output it always got."""
    def launched(*args, **kwargs):
        out, rows = run(*args, **kwargs)
        page = _page_of(out)
        if page is not None:
            page.rows = rows
        return out

    return launched


class Executor:
    """Reference: LocalQueryRunner's local execution half — interpret a
    physical plan against in-process connectors, no scheduler, no HTTP."""

    def __init__(
        self,
        catalogs: Dict[str, Connector],
        *,
        page_rows: int = 1 << 18,
        use_jit: bool = True,
    ):
        self.catalogs = catalogs
        self.page_rows = page_rows
        self.use_jit = use_jit
        self._jit_cache: Dict = {}
        # Deferred-sync discipline: an earlier TPU runtime permanently
        # degraded every subsequent kernel launch (~50ms floor) after ANY
        # device->host read (not re-verified on the current chip, see
        # ROADMAP A2), so the hot path never calls bool()/int()/
        # np.asarray on device values. Capacity-overflow flags accumulate
        # here as device scalars and are checked ONCE per execute(); on
        # overflow the whole query re-runs with boosted capacities
        # (SURVEY §8.2.1's compiled-branch escape, moved to query scope).
        self._pending_overflow: List[jnp.ndarray] = []
        self._capacity_boost = 1
        # per-group slot bound for collect-state aggregates (array_agg/
        # map_agg/approx_percentile); session array_agg_max_elements
        self.collect_k = 1024
        self._collect_stats = None  # id(node) -> NodeStats when ANALYZE
        # EXPLAIN ANALYZE wall honesty: drain the device queue
        # after every page so per-node wall_s is real device time (costs
        # ~6ms/page of sync overhead; off by default)
        self.stats_drain = False
        # memory accounting (reference: OperatorContext->QueryContext
        # hierarchy + query.max-memory enforcement): page footprints are
        # computed from STATIC shapes (host arithmetic, never a device
        # read), tracked as a high-water mark per query, and enforced
        # against max_memory_bytes by failing the query rather than
        # thrashing — the reference's kill-don't-spill default.
        self.max_memory_bytes: Optional[int] = None
        self.peak_memory_bytes = 0
        self._live_bytes = 0
        # Partitioned (grace-style) execution — the spill analog (SURVEY
        # §6.4, reference: spiller/* + revocable memory): when a join
        # build or aggregation state estimate exceeds this many bytes, the
        # operator runs in hash-partition passes over its inputs instead
        # of one materialization. Re-scanning per pass is cheap because
        # generator connectors compute pages from row indices ("scan" =
        # "generate", SURVEY §8.2.6); host-page connectors restage from
        # host RAM — which IS the HBM->host-RAM spill.  None = disabled.
        self.spill_bytes: Optional[int] = None
        self.spill_partitions_used = 0  # observability / tests
        # Restreamable intermediates (reference: PagesIndex +
        # FileSingleStreamSpiller): multi-pass operators consume their
        # sources through _source_stream, which materializes EXPENSIVE
        # subtrees (joins/aggs/sorts below) once into a PageStore and
        # restreams, instead of re-executing the subplan per pass.
        # Intermediates estimated above host_spill_bytes stage to host
        # RAM (the HBM->host spill); below it they stay device-resident
        # as a page list. None = host tier disabled.
        self.host_spill_bytes: Optional[int] = None
        # Third tier: intermediates estimated above disk_spill_bytes
        # write to .npz spill files (FileSingleStreamSpiller proper);
        # None = disk tier disabled. spill_path = target directory.
        self.disk_spill_bytes: Optional[int] = None
        self.spill_path: Optional[str] = None
        self._stream_cache: Dict = {}
        self.host_spill_pages = 0  # observability / tests
        self.host_spill_bytes_used = 0
        self.disk_spill_pages = 0
        # Per-partition skew rebalancing (SURVEY §6.7): on boosted
        # retries, inner grace-join partitions chunk their build rows
        # by position instead of growing buffers (join_skew_rebalance
        # session property); skew_chunks_used is observability.
        self.join_skew_rebalance = True
        self.skew_chunks_used = 0
        # Adaptive execution (ISSUE 15, presto_tpu/adaptive/): the
        # stage-boundary re-planner's counters live on the COORDINATOR
        # executor (the scheduler increments them); skew_preengaged is
        # the worker-side hint — observed per-partition skew in an
        # upstream spool pre-engages the position-chunked rebalance at
        # boost 1 instead of discovering the hot key via an overflow
        # retry (skew_preempted counts those engagements).
        self.adaptive_replans = 0
        self.adaptive_dist_flips = 0
        self.adaptive_capacity_seeds = 0
        self.adaptive_replan_rejected = 0
        self.skew_preengaged = False
        self.skew_preempted = 0
        # Hard per-pass row cap for join builds (session property
        # max_join_build_rows): partitions a join whenever the build-side
        # row estimate exceeds it, independent of the byte threshold.
        # Exists because an earlier XLA:TPU runtime faulted kernels touching
        # >=~4M-row buffers — the byte threshold tunes memory, this tunes
        # the kernel-size ceiling. None = disabled.
        self.max_build_rows: Optional[int] = None
        # Pallas unique-key join fast path (pallas_join_enabled session
        # property); pallas_joins_used is observability for tests
        self.pallas_join = False
        self.pallas_joins_used = 0
        # mesh all_to_all exchange plane (dist/scheduler.py; mirrored
        # onto the coordinator): exchanges lowered onto the ICI mesh,
        # their send-buffer bytes, and loud fallbacks to the spool plane
        self.ici_exchanges = 0
        self.ici_bytes = 0
        self.mesh_exchange_fallbacks = 0
        # build-free generated joins (generated_join_enabled session
        # property); generated_joins_used is observability for tests
        self.generated_join = True
        self.generated_joins_used = 0
        # Late materialization for join chains (session property
        # late_materialization_enabled; exec/latemat.py): joins emit a
        # row-id indirection per build side instead of gathering every
        # carried column; values gather ONCE at the first consumer that
        # needs them. "auto" engages only on TPU — the win is HBM
        # gather bandwidth, while the extra per-join
        # programs cost real CPU compile time (same policy as
        # pallas_join_enabled). Direct Executor construction defaults
        # to ON (library users, unit tests); the session layer maps
        # auto per backend. Counters: gathers_deferred = per-page
        # column gathers skipped at join-output time;
        # gathers_materialized = per-page column value gathers actually
        # performed (lift + chain-boundary finish). On a lazy chain,
        # materialized per carried build column per page is exactly 1.
        self.late_mat = True
        self.gathers_deferred = 0
        self.gathers_materialized = 0
        # Whole-pipeline fusion THROUGH partial aggregation (session
        # property fused_partial_agg_enabled): a scan→filter→project→
        # partial-agg chain compiles to ONE XLA program per split
        # (the computed bound for Q1/Q6). "auto" fuses only
        # on TPU — the win is per-launch overhead, which CPU
        # doesn't pay, while the bigger fused programs cost real CPU
        # compile time (same policy as pallas_join_enabled).
        # fused_partial_aggs counts fused streams built (mirrors
        # generated_joins_used).
        self.agg_fusion = "auto"
        self.fused_partial_aggs = 0
        # Split-batched execution (session property split_batch_size):
        # fold the per-SPLIT driver loop of a fused pipeline into XLA.
        # Fused scan→filter→project→partial-agg chains run a whole
        # batch of splits as ONE program — a lax.scan over split
        # indices with the partial-aggregation state as carry — and
        # page-emitting chains (probe-side join pipelines) vmap the
        # fused body over a [B, n_pad] stacked batch, emitting the
        # batch as one page. Batch sizes quantize onto the shapes.py
        # ladder (one canonical program per bucket); tail batches pad
        # with zero traced row counts (every generated row masks out);
        # overflow flags OR-reduce across the batch into the deferred
        # ladder. "auto" engages on TPU only — the win is the ~6ms
        # per-launch overhead, which CPU doesn't pay, while the
        # scanned/vmapped programs cost real CPU compile time (the
        # pallas_join_enabled policy); an int forces that max batch.
        # Counters: program_launches = fused-scan program launches
        # this attempt, splits_scanned = real (unpadded) splits they
        # covered — splits_per_launch in EXPLAIN ANALYZE is their
        # ratio. split_batch_fallbacks counts streams that fell back
        # to the per-split loop because the chain did not trace under
        # vmap/scan (diagnostic; never reset).
        self.split_batch = "auto"
        self.program_launches = 0
        self.splits_scanned = 0
        self.split_batch_fallbacks = 0
        # every program made by _jit is called through exec/programs.
        # launch, which counts on the CALLING executor, this attempt:
        # device_launches = calls, dispatch_wall_us = host time inside
        # them, device_wait_us = host time blocked on the device
        # (exec/xfer.py pulls, the overflow flags' among them, and
        # devsync.drain);
        # _launches_by_label feeds the attempt span while tracing;
        # exchange_launches = the calls among them whose program moves
        # rows between chips (family "exchange", over a mesh);
        # mesh_fused_rounds = the scan rounds a mesh ran as one
        # program, mesh_batched_rounds = the ones among them that
        # shared a launch with other rounds
        self.device_launches = 0
        self.exchange_launches = 0
        self.mesh_fused_rounds = 0
        self.mesh_batched_rounds = 0
        self.dispatch_wall_us = 0
        self.device_wait_us = 0
        self._launches_by_label: Dict[str, int] = {}
        # the (plan node, page) row counts pages() kept this attempt
        # under tracing or EXPLAIN ANALYZE: row_counts_launched = the
        # page brought its count from the launch that made it
        # (Page.rows), row_counts_eager = pages() dispatched
        # page.num_rows(), two eager programs on the driver thread
        self.row_counts_launched = 0
        self.row_counts_eager = 0
        # scans of a stored table (connectors/cached.py), this attempt:
        # resident_splits_scanned = real splits whose columns the fused
        # scan step read from the store, resident_bytes_scanned = the
        # bytes of stored columns and validity those splits' slices
        # hold (counted where they are launched, from the buffers'
        # dtypes and the split's padded rows: no device read). What the
        # store holds and what loading it cost are the catalogs' own
        # (resident_table_bytes, resident_loads, resident_load_wall_us
        # below)
        self.resident_splits_scanned = 0
        self.resident_bytes_scanned = 0
        # joins whose build side is a stored table (_stored_build),
        # this attempt: join_builds = lookup structures built (one a
        # join, once a statement), join_build_rows = stored slots those
        # builds read, join_build_bytes = device bytes the structures
        # hold (both from shapes: no device read), join_probes_at_build
        # = the joins among them probed inside another's build program,
        # once a build row (_ride_stored_joins), join_build_wall_us
        # = host microseconds from a build's start to its program's
        # enqueue (the join_build spans' sum; the device's part is the
        # trace's jit_stored_build)
        self.join_builds = 0
        self.join_probes_at_build = 0
        self.join_build_rows = 0
        self.join_build_bytes = 0
        self.join_build_wall_us = 0
        # the structures themselves, by build: made once an attempt,
        # freed with the statement (_release_stream_cache)
        self._stored_builds: Dict = {}
        # Call nodes the planner's constant fold replaced (expr/fold.py)
        # in this executor's runner's planning passes
        # (count_constants_folded below); no attempt resets it
        self.plan_constants_folded = 0
        self._attempt_span = None   # the open attempt, while tracing
        # _agg_sizing's decisions this attempt (the attempt span
        # reports the costliest: most passes, then largest capacity)
        self._agg_sizings: List[AggSizing] = []
        # blocking-aggregation sizing heuristics (session properties
        # agg_optimistic_rows / agg_compact_enabled): start group
        # capacities tight and densify join-sparse inputs, both guarded
        # by the overflow-retry ladder
        self.agg_optimistic_rows = 1 << 18
        self.agg_compact = True
        # DCN ingest registry: RemoteSource.key -> callable yielding
        # host pages (reference: ExchangeClient wiring per task)
        self.remote_sources: Dict[str, object] = {}
        # Compile-cost observability (compilecache.py): per-query deltas
        # of the process-wide counters, set by execute() /
        # stream_fragment() and reported through EXPLAIN ANALYZE.
        # programs_compiled counts real XLA backend compiles (a
        # persistent-cache hit is a program_cache_hits instead);
        # compile_wall_s is their summed wall.
        self.programs_compiled = 0
        self.program_cache_hits = 0
        self.compile_wall_s = 0.0
        # Device-memory governor (session property device_memory_budget;
        # exec/membudget.py): every buffer capacity already quantizes
        # onto the shapes.py ladder, so a pipeline's peak live device
        # bytes is computable BEFORE compile — and pipelines that would
        # exceed the budget rewrite into chunked/streaming forms
        # (grace-partition join passes, probe-side position chunking,
        # generation-chunked scans, partitioned aggregation, PageStore
        # host/disk overflow) instead of faulting the device. 0 = auto:
        # real HBM minus headroom on TPU, a generous cap on CPU (tier-1
        # behavior unchanged unless a test forces a tiny budget).
        self.device_memory_budget = 0
        self._budget_resolved: Optional[Tuple] = None
        # fault_rows: per-buffer row-capacity ceiling. None = auto
        # (SAFE_BUFFER_ROWS on TPU — the >=4M-row kernel fault,
        # with construction headroom — unlimited elsewhere); 0 = off;
        # an int forces the ceiling (tests, the static audit).
        self.fault_rows: Optional[int] = None
        # memory_chunked_pipelines: governed rewrites this attempt
        # (reset in _begin_attempt, reported in EXPLAIN ANALYZE
        # alongside peak_device_bytes)
        self.memory_chunked_pipelines = 0
        # ---- fault tolerance (ISSUE 5: task retry + deadlines + OOM
        # degradation). query_deadline: absolute time.monotonic()
        # deadline set per query by runner.apply_session from the
        # query_max_run_time session property; checked at page
        # boundaries in the execute()/stream_fragment() driver loops.
        self.query_deadline: Optional[float] = None
        # device-OOM degradation: a caught XLA RESOURCE_EXHAUSTED /
        # allocation fault re-enters execution with the resolved
        # device-memory budget halved (the membudget governor then
        # rewrites over-share pipelines into their chunked forms), up
        # to device_oom_attempts times — an HBM-model miss becomes a
        # slow correct query, not a crash. Wired from the
        # task_retry_attempts session property (0 restores raise-
        # through). device_oom_retries is per-query observability.
        self.device_oom_attempts = 2
        self.device_oom_retries = 0
        self._oom_divisor = 1
        # test/chaos hook: raise a synthetic RESOURCE_EXHAUSTED on the
        # next N attempts (FAULT_DEVICE_OOM env seeds subprocess
        # workers; tests set the attribute directly)
        self.inject_device_oom = int(
            os.environ.get("FAULT_DEVICE_OOM", "0")
        )
        # DCN coordinator task recovery, maintained by DcnRunner on ITS
        # executor (lifetime-cumulative, like the join counters):
        # task_retries = fragments re-dispatched to a surviving worker,
        # workers_excluded = nodes dropped from the query's pool.
        self.task_retries = 0
        self.workers_excluded = 0
        # release_skips = dead-worker page-buffer DELETE releases
        # skipped (DcnRunner mirrors its own count here so every
        # counter surface — EXPLAIN ANALYZE, /metrics, system.metrics
        # — reads one registry off one object; exec/counters.py)
        self.release_skips = 0
        # Coordinator HA (ISSUE 20, dist/checkpoint.py), lifetime-
        # cumulative on the coordinator's executor: journal records
        # published, queries recovered across a restart, dead
        # placements re-dispatched during re-attach, and checkpoint
        # records dropped loudly.
        self.checkpoints_written = 0
        self.coordinator_reattaches = 0
        self.reattach_redispatches = 0
        self.checkpoint_drops = 0
        # Stage-DAG scheduling (ISSUE 7, dist/scheduler.py): the
        # general fragment-DAG coordinator maintains these on ITS
        # executor, lifetime-cumulative like the task-retry counters.
        # stages_scheduled = DAG stages dispatched;
        # spooled_exchange_pages = pages published into worker-side
        # spooled-exchange partitions (summed from task status);
        # nonleaf_replays = lost NON-LEAF tasks re-dispatched to
        # replay from upstream spools (the Tardigrade recovery the
        # PR-5 model could not express); speculative_tasks_won/lost =
        # straggler races where the speculated copy beat / lost to
        # the original placement.
        self.stages_scheduled = 0
        self.spooled_exchange_pages = 0
        self.nonleaf_replays = 0
        self.speculative_tasks_won = 0
        self.speculative_tasks_lost = 0
        # plan_check (exec/plan_check.py): pre-compile verification of
        # the physical plan — schema-consistent edges, ladder/fault-line
        # capacities, canonical jit-key material, split determinism.
        # "auto" = on under pytest or PRESTO_TPU_PLAN_CHECK=1 (the
        # build/test surface), off on the hot serving path; True/False
        # force.
        self.plan_check = "auto"
        # ---- query-lifecycle tracing (ISSUE 9, presto_tpu/obs/).
        # trace: the active obs.QueryTrace, attached per query by the
        # driver (LocalRunner / DcnRunner / worker task runtime) via
        # obs.attach; None = tracing off, and every recording site
        # below guards on that one check — spans record at page/
        # attempt boundaries ONLY, never inside traced code, so jit
        # keys and compiled programs carry no trace state.
        self.trace = None
        # trace_parent: the span an execute() nests under (the runner
        # sets its open ``plan`` span while plan-time scalar subqueries
        # run); None = the run is the statement's own ``execute`` phase
        self.trace_parent = None
        # trace_spans: spans this executor recorded into the active
        # trace (per query; the tracing-off test pins it at 0, and
        # obs.finalize settles it to the trace's full span count)
        self.trace_spans = 0
        # listener_errors: EventListener exceptions swallowed by
        # events.dispatch — counted through count_listener_error so a
        # misbehaving listener is visible on every counter surface
        # instead of vanishing (executor lifetime)
        self.listener_errors = 0
        # ---- observed-stats profiles (obs/profile.py): when a
        # ProfileStore is wired (stats_profile_dir session property),
        # execute()/stream_fragment() seed their starting
        # _capacity_boost from the persisted settled bucket of the
        # same (plan fingerprint, connector snapshot) and record the
        # settled bucket + observed cardinalities on success.
        # capacity_boost_retries counts boosted re-entries this query
        # (the number ROADMAP item 4 drives to zero on repeats);
        # profile_store_hits counts seeded starts.
        self.profile_store = None
        self.capacity_boost_retries = 0
        self.profile_store_hits = 0
        # ---- result cache (ISSUE 10, presto_tpu/cache/): when a
        # ResultCache is wired (result_cache_enabled session property
        # -> runner.apply_session, or set directly by library users),
        # execute()/stream_fragment() select the plan's maximal
        # cacheable subtrees as CACHE POINTS (cache/rules.py) and
        # pages() serves those subtrees from the cache — a hit replays
        # stored host pages and skips compile+launch entirely
        # (program_launches stays 0); a miss streams normally while
        # collecting, and publishes ONLY after the attempt completes
        # overflow-free (a truncated page set can never be cached).
        # Counters are lifetime-cumulative like the join counters;
        # /metrics + system.metrics overlay the process-shared store's
        # totals so concurrent per-query executors aggregate.
        self.result_cache = None
        self.result_cache_hits = 0
        self.result_cache_misses = 0
        self.result_cache_evictions = 0
        self.result_cache_invalidations = 0
        # fleet-cache tallies (ISSUE 19), lifetime-cumulative like the
        # four above: warm-start manifest loads/drops (runner boot
        # pass), coordinator-probed remote hits (dist/scheduler.py),
        # and containment-rewrite hits (cache/rules.py subsumption)
        self.cache_warm_loads = 0
        self.cache_manifest_drops = 0
        self.cache_remote_hits = 0
        self.cache_subsumed_hits = 0
        # serve contained filters from wider cached siblings (session
        # result_cache_subsumption; runner.apply_session resolves)
        self.cache_subsumption = False
        # per-query cache-point state: id(subtree) -> (key, node,
        # tables, watermark, snap, family) — node refs held so ids
        # stay stable; inflight guards the miss path's re-entrant
        # pages() call; pending holds completed-but-unpublished
        # streams until the attempt succeeds
        self._cache_points: Dict[int, tuple] = {}
        self._cache_inflight: set = set()
        self._cache_pending: List = []
        # ---- transfer accounting (ISSUE 12, exec/xfer.py): the choke
        # points meter every host<->device crossing onto THIS query's
        # gauges while the executor is the thread-bound sink
        # (execute()/stream_fragment() install it via XF.swap_sink).
        # Per-query, reset at query start like the spill gauges;
        # transfer_wall_s is the float wall surfaced as a computed
        # EXPLAIN ANALYZE entry (the compile_wall_s pattern).
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_transfers = 0
        self.d2h_transfers = 0
        self.transfer_wall_s = 0.0
        # host-serve sink: ids of the plan nodes whose pages feed
        # ONLY result serialization/decode (the root and its Output
        # pass-through chain) — a cache replay or RemoteSource ingest
        # there serves host pages directly (zero h2d, zero d2h)
        # instead of round-tripping device_put -> decode pull
        # (exec/xfer.py)
        self._host_sink_ids: frozenset = frozenset()
        # ---- device-resident data plane (ISSUE 13). buffer_donation:
        # thread donate_argnums through _jit for the fold-merge /
        # topn-merge accumulator programs so a chained merge reuses
        # its input's HBM in place instead of allocating a fresh
        # accumulator per step (and a boosted retry's re-run reuses
        # rungs, not residue — _begin_attempt drops every donated
        # chain's references). "auto" engages on TPU only (the win is
        # HBM; donation is free but pointless on CPU) — the
        # pallas_join_enabled policy; session prop
        # buffer_donation_enabled forces. buffers_donated counts
        # donated-program invocations this attempt.
        self.buffer_donation = "auto"
        self.buffers_donated = 0
        # device_exchange: spooled-exchange pages partition on DEVICE
        # (dist/spool.device_partition_pages) and spool as device
        # Pages that materialize to host bytes lazily — the
        # d2h/h2d exchange pair deletes for mesh-local exchanges.
        # "auto" = TPU only (the jitted partition programs cost real
        # CPU compile time for copies CPU barely pays); session prop
        # device_exchange_enabled forces. mesh_local_exchanges counts
        # exchange edges served device/host-direct between same-
        # process placements, skipping serde entirely (executor
        # lifetime, like the spooled-exchange counters).
        self.device_exchange = "auto"
        self.mesh_local_exchanges = 0
        # ---- exchange wire plane (ISSUE 16, dist/serde.py +
        # dist/connpool.py): lifetime counters metered through the
        # thread-bound transfer sink, like the crossings above.
        # exchange_wire_bytes = post-codec blob bytes serialize_page
        # shipped; exchange_raw_bytes = the pre-codec array bytes
        # behind them (ratio = wire compression);
        # exchange_fetch_reused_conns = shuffle-plane requests served
        # on a reused keep-alive connection instead of a fresh TCP
        # connect.
        self.exchange_wire_bytes = 0
        self.exchange_raw_bytes = 0
        self.exchange_fetch_reused_conns = 0
        # ---- streaming subsystem (ISSUE 14, presto_tpu/streaming/ +
        # connectors/stream.py): lifetime counters mirrored onto the
        # executor so every surface (EXPLAIN ANALYZE, /metrics,
        # system.metrics) renders refresh activity.
        # delta_pages_folded = delta partial-state pages an
        # IVM refresh folded into persisted view state (O(new rows)
        # work); ivm_refreshes = incremental refreshes completed;
        # ivm_full_recomputes = refreshes that fell back to a full
        # recompute (non-IVM-safe plan or ivm_enabled=false — loud,
        # never silent); cursor_polls = tailing /v1/statement cursor
        # polls served; stream_appends_seen = append batches the
        # engine observed on append-only stream connectors (write
        # path + tail polls that saw the offset advance).
        self.delta_pages_folded = 0
        self.ivm_refreshes = 0
        self.ivm_full_recomputes = 0
        self.cursor_polls = 0
        self.stream_appends_seen = 0
        # ---- cross-query launch batching (ISSUE 17,
        # server/launch_batcher.py): the concurrent server path
        # attaches ONE process-shared LaunchBatcher to every per-query
        # executor; compatible fused-pipeline launches (same jit-key
        # family + shapes.py bucket) gang into one vmapped device step
        # with in-program per-query demux. cross_query_batching is the
        # tri-state session knob ("auto" = on whenever a batcher is
        # attached — attachment itself is the concurrent-server
        # condition; raw Executors never batch); wait_ms bounds the
        # gather window so a lone query never stalls past it.
        # Counters: cross_query_batches = shared steps this executor
        # dispatched as leader; cross_query_batched_queries = launches
        # it served from a shared batch (leader or follower);
        # batch_gather_wait_ms = summed window wait;
        # queries_per_launch = widest batch ridden (per-query gauge).
        self.launch_batcher = None
        self.cross_query_batching = "auto"
        self.cross_query_batch_wait_ms = 25
        self.cross_query_batches = 0
        self.cross_query_batched_queries = 0
        self.batch_gather_wait_ms = 0
        self.queries_per_launch = 0

    # ------------------------------------------------------------ plumbing
    def count_listener_error(self) -> None:
        """THE sink events.dispatch reports swallowed listener
        exceptions to — a registry counter (exec/counters.py), so a
        misbehaving EventListener shows on /metrics, system.metrics,
        and EXPLAIN ANALYZE instead of disappearing."""
        self.listener_errors += 1

    def count_transfer(self, direction: str, nbytes: int,
                       wall_s: float) -> None:
        """THE sink exec/xfer.py meters crossings to while this
        executor is the thread-bound transfer sink — registry counters
        (exec/counters.py), so every crossing shows on EXPLAIN
        ANALYZE, /metrics, and system.metrics."""
        if direction == "h2d":
            self.h2d_transfers += 1
            self.h2d_bytes += nbytes
        else:
            self.d2h_transfers += 1
            self.d2h_bytes += nbytes
        self.transfer_wall_s += wall_s

    def count_wire(self, wire: int, raw: int) -> None:
        """Registry-counter sink dist/serde.serialize_page meters
        exchange wire bytes to while this executor is the
        thread-bound sink (exec/xfer.py current_sink) — the
        compression-ratio pair every surface renders."""
        self.exchange_wire_bytes += wire
        self.exchange_raw_bytes += raw

    def count_reused_conn(self) -> None:
        """Registry-counter sink for dist/connpool.py: one
        shuffle-plane HTTP request served on a reused keep-alive
        connection."""
        self.exchange_fetch_reused_conns += 1

    def _reset_transfer_gauges(self) -> None:
        """Per-query transfer-gauge reset (execute(),
        stream_fragment(), and the runner's statement-cache hit path
        — a replayed statement reports ZERO crossings)."""
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_transfers = 0
        self.d2h_transfers = 0
        self.transfer_wall_s = 0.0

    @staticmethod
    def _sink_chain_ids(node) -> frozenset:
        """ids of the nodes whose page streams reach result decode /
        emit untouched: the root plus its Output pass-through chain
        (Output yields its source's pages verbatim) — the places a
        host page can be served without any device consumer ever
        seeing it."""
        ids = {id(node)}
        while isinstance(node, P.Output):
            node = node.source
            ids.add(id(node))
        return frozenset(ids)

    def count_mesh_local(self) -> None:
        """Registry-counter sink for the mesh-local exchange fast path
        (dist/spool.iter_source_pages, the stage scheduler's root
        drain): one same-process exchange edge served Pages directly —
        no HTTP, no serde, and zero metered crossings when the spool
        is device-resident (ISSUE 13)."""
        self.mesh_local_exchanges += 1

    def count_reattach(self) -> None:
        """Registry-counter sink for one query carried across a
        coordinator restart (dist/checkpoint.reattach_query) — either
        the spooled fast path or the re-run-from-SQL rung."""
        self.coordinator_reattaches += 1

    def count_reattach_redispatch(self) -> None:
        """Registry-counter sink for one dead-spool re-dispatch during
        crash re-attach (dist/checkpoint._redispatch_dead): a persisted
        placement stopped answering and its persisted payload was
        re-POSTed onto the live pool."""
        self.reattach_redispatches += 1

    def count_cache_invalidations(self, n: int) -> None:
        """Registry-counter sink for the runner's write-path result-
        cache invalidation (runner._invalidate_caches) — same pattern
        as count_listener_error: the increment lives on the executor
        so every counter surface renders it."""
        self.result_cache_invalidations += n

    # The four streaming sinks below may be hit from CONCURRENT
    # threads (tail-cursor polls on protocol handler threads, writer
    # threads) sharing one bootstrap executor: the
    # increments are plain GIL-guarded adds, so a lost increment
    # under contention is an acceptable METRIC error, never a
    # correctness one — the exec/xfer.py process-totals stance.
    def count_ivm_refresh(self, full: bool = False) -> None:
        """Registry-counter sink for streaming/ivm.refresh: one
        incremental refresh completed, or — ``full`` — one loud
        full-recompute fallback (non-IVM-safe plan, or ivm_enabled
        off)."""
        if full:
            self.ivm_full_recomputes += 1
        else:
            self.ivm_refreshes += 1

    def count_delta_pages(self, n: int) -> None:
        """Registry-counter sink for the IVM delta fold: ``n`` delta
        partial-state pages folded into persisted view state this
        refresh (streaming/ivm.refresh)."""
        self.delta_pages_folded += n

    def count_cursor_poll(self) -> None:
        """Registry-counter sink for the tailing /v1/statement cursor
        plane (server/http_server.TailCursor.poll)."""
        self.cursor_polls += 1

    def count_stream_append(self) -> None:
        """Registry-counter sink for append batches observed on
        append-only stream connectors: the runner's INSERT advance
        path and tail polls that saw the offset move."""
        self.stream_appends_seen += 1

    def _trace_operators(self, tr, att_span) -> None:
        """Emit per-plan-node operator spans from the successful
        attempt's EXPLAIN ANALYZE accounting (pages() wall/rows/pages),
        anchored at the attempt start — operator walls are per-node
        totals, so the spans overlap rather than partition the attempt.
        Called once per successful attempt, AFTER the run (the row-
        count sync here is the same one-sync-at-the-end discipline
        execute_with_stats uses)."""
        stats = self._collect_stats
        if not stats:
            return
        self._resolve_row_counts()
        for st in stats.values():
            if not isinstance(st, NodeStats):
                continue
            tr.complete("operator", st.label, att_span.t0,
                        att_span.t0 + st.wall_s, parent=att_span,
                        rows=st.rows, pages=st.pages)
            self.trace_spans += 1

    def _resolve_row_counts(self) -> None:
        """Read the attempt's deferred row counts, all plan nodes' at
        once: one metered pull through exec/xfer.py after the run
        (the deferred-sync rule), then host ints."""
        stats = [st for st in (self._collect_stats or {}).values()
                 if isinstance(st, NodeStats)]
        where = [(st, i) for st in stats
                 for i, c in enumerate(st.row_counts)
                 if not isinstance(c, int)]
        if not where:
            return
        host = XF.to_host([st.row_counts[i] for st, i in where],
                          label="row-counts")
        for (st, i), c in zip(where, host):
            st.row_counts[i] = int(np.sum(c))

    def _seed_profile(self, node) -> Optional[str]:
        """Observed-stats profile seeding (obs/profile.py): start the
        overflow ladder at the SETTLED capacity bucket a previous run
        of this (plan fingerprint, connector snapshot) recorded — the
        repeated query skips the boost climb (`capacity_boost_retries`
        stays 0). Returns the profile key for recording, or None when
        no store is wired."""
        if self.profile_store is None:
            return None
        key = self.profile_store.key(node, self.catalogs)
        prof = self.profile_store.lookup(key)
        if prof and int(prof.get("capacity_boost", 1)) > 1:
            self._capacity_boost = int(prof["capacity_boost"])
            self.profile_store_hits += 1
        return key

    def _record_profile(self, key: str, rows_out: Optional[int],
                        pages_out: Optional[int] = None) -> None:
        """Persist this run's observed stats: the settled capacity
        bucket plus per-operator output cardinalities when the stats
        accounting ran (tracing or EXPLAIN ANALYZE) — ROADMAP item 4's
        replanning input."""
        prof: Dict = {"capacity_boost": self._capacity_boost}
        if rows_out is not None:
            prof["rows_out"] = int(rows_out)
        if pages_out is not None:
            prof["pages_out"] = int(pages_out)
        stats = self._collect_stats
        if stats:
            self._resolve_row_counts()
            ops: Dict[str, int] = {}
            for st in stats.values():
                if isinstance(st, NodeStats):
                    ops[st.label] = ops.get(st.label, 0) + st.rows
            prof["operator_rows"] = ops
        self.profile_store.record(key, prof)

    def _plan_check_on(self) -> bool:
        pc = self.plan_check
        if pc in (True, "true", "on"):
            return True
        if pc in (False, "false", "off", 0):
            return False
        env = os.environ.get("PRESTO_TPU_PLAN_CHECK", "").lower()
        if env in ("0", "false", "off"):
            return False  # explicit operator opt-out wins over auto
        # only an explicit opt-IN enables outside pytest — a typo'd
        # env value must not force the verifier onto the serving path
        return bool(os.environ.get("PYTEST_CURRENT_TEST")
                    or env in ("1", "true", "on"))

    def _verify_plan(self, node: P.PhysicalNode) -> None:
        """Run the pre-compile plan verifier when enabled (auto = the
        test surface only — the serving path pays nothing).
        A clean verdict is memoized per (plan object, sizing knobs) —
        retry ladders and repeated executions of one plan re-verify
        nothing; the held references keep id() stable."""
        if not self._plan_check_on():
            return
        key = (id(node), self.device_memory_budget, self.fault_rows,
               self.page_rows)
        cache = getattr(self, "_plan_check_memo", None)
        if cache is None:
            cache = self._plan_check_memo = {}
        if key in cache:
            return
        from presto_tpu.exec import plan_check as PC

        PC.verify(self, node)
        if len(cache) >= 16:
            cache.clear()
        cache[key] = node  # keep the ref so id() cannot be reused

    @staticmethod
    def _tristate_on(mode) -> bool:
        """THE tri-state knob resolution (pallas_join policy): "off"
        never, "force"/"true" always, "auto" on TPU only. One
        resolver so the accepted alias sets cannot drift per knob."""
        if mode in (True, "true", "force"):
            return True
        if mode in (False, None, "false", "off", 0):
            return False
        return jax.default_backend() == "tpu"

    def _donate_on(self) -> bool:
        """buffer_donation_enabled: forcing it on CPU is the test
        path — jax deletes donated inputs on every backend, so
        use-after-donate bugs fail loudly under tier-1 too; auto is
        TPU-only (the win is HBM reuse in place)."""
        return self._tristate_on(self.buffer_donation)

    def _device_exchange_on(self) -> bool:
        """device_exchange_enabled: spooled-exchange pages partition
        on device and spool as device Pages (dist/spool.
        device_partition_pages); auto = TPU only — the partition
        programs cost real CPU compile time for copies the CPU
        backend barely pays."""
        return self._tristate_on(self.device_exchange)

    # whether the programs this executor makes also return the row
    # count of the page they make (_returning_rows), so a pages()
    # boundary under tracing or EXPLAIN ANALYZE dispatches no eager
    # program for it. Over a mesh (dist/executor.py) they do: there an
    # eager program between launches is the pace of a statement. On
    # one device the host runs far ahead of the device, the eager count
    # costs nothing end to end, and the programs stay as they are.
    launch_counts_rows = False

    def _jit(self, key, fn=None, static_argnums=(), donate_argnums=(),
             make=None, returns_rows=False):
        """One program per CANONICAL key, jitted under the label the
        key begins with (exec/programs.py) and called through THE
        launch point, which counts and annotates the call on this
        executor. Keys name exactly the inputs that shape the traced
        program (the kernel's bound args, static sizes, dictionary
        signatures) and deliberately exclude plan-node
        identity/estimates — two plans that differ only in a capacity
        estimate share one program, and the bucketed static sizes
        (exec/shapes.py) make their programs identical. ``make``
        builds the function on a cache miss where building it is work
        (the fused-scan sites).

        ``donate_argnums`` marks args whose buffer the CALLER provably
        never touches again (fold/topn merge accumulators); when
        donation resolves on (_donate_on) the program reuses that HBM
        in place and the invocation counts on buffers_donated. The
        donated program caches under a salted key so flipping the
        session property mid-executor can never hand a donating
        program to a non-donating call site.

        Where ``launch_counts_rows`` holds, or the function handed in
        already ``returns_rows`` (a shard_map body counts a chip's own
        rows: DistExecutor._mesh_jit), the program returns the page's
        row count beside its output and the call hands it on as
        ``Page.rows``; salted likewise, because executors that share
        a jit cache need not agree."""
        if not self.use_jit:
            run = fn if fn is not None else make()
            return _attaching_rows(run) if returns_rows else run
        label = PG.label_of(key)
        donate = bool(donate_argnums) and self._donate_on()
        if donate:
            key = (key, "donate")
        rows = returns_rows or self.launch_counts_rows
        if rows:
            key = _rows_key(key)
        prog = self._jit_cache.get(key)
        if prog is None:
            kw = {"static_argnums": static_argnums}
            if donate:
                _filter_donation_warning()
                kw["donate_argnums"] = donate_argnums
            body = fn if fn is not None else make()
            if rows and not returns_rows:
                body = _returning_rows(body)
            prog = self._jit_cache[key] = PG.Program(
                label, body, donates=donate, **kw)
        run = functools.partial(PG.launch, self, prog)
        return _attaching_rows(run) if rows else run

    def _jit_drop(self, key) -> None:
        """Forget the program _jit made for ``key`` (a chain that did
        not trace), under whichever salt it was cached."""
        for k in (key, _rows_key(key)):
            self._jit_cache.pop(k, None)

    def count_launch(self, prog, wall_ns: int) -> None:
        """THE sink exec/programs.launch counts a program call on."""
        self.device_launches += 1
        self.dispatch_wall_us += (wall_ns + 500) // 1000
        if prog.fused_scan:
            self.program_launches += 1
        if prog.exchange:
            self.exchange_launches += 1
        if prog.donates:
            self.buffers_donated += 1
        if self.trace is not None:
            by = self._launches_by_label
            by[prog.label] = by.get(prog.label, 0) + 1
            self.span_ending_now("launch", prog.label, wall_ns / 1e9)

    def span_ending_now(self, kind: str, name: str, wall_s: float,
                        **attrs) -> None:
        """A span of the open attempt that ends at this instant and
        lasted ``wall_s``: how the launch point, exec/xfer.py's choke
        points and the resident store put an interval they timed
        themselves (the reading their counter sums) on the query
        trace. Callers guard on ``self.trace is not None``."""
        tr = self.trace
        t1 = tr.now()
        tr.complete(kind, name, t1 - wall_s, t1,
                    parent=self._attempt_span, **attrs)
        self.trace_spans += 1

    def count_constants_folded(self, n: int) -> None:
        """THE sink the runner records a planning pass's constant
        folds on (tools/lint's counters rule: a registry counter is
        written where it is declared, on the executor)."""
        self.plan_constants_folded += n

    def count_resident_load(self, table: str, wall_s: float,
                            **attrs) -> None:
        """THE sink connectors/cached.py records a table's load on: a
        span of the attempt whose scan touched the table first (the
        tallies are the connector's own)."""
        if self.trace is not None:
            self.span_ending_now("resident_load", table, wall_s, **attrs)

    def count_device_wait(self, wall_s: float) -> None:
        """THE sink exec/xfer.py counts host time blocked on the
        device on (its pulls, the overflow flags' among them, and
        devsync.drain)."""
        self.device_wait_us += int(round(wall_s * 1e6))

    # ------------------------------------------- device-memory governor
    # floor for OOM-tightened budgets: the governor's sizing math stays
    # sane however many times the ladder halves. Capped at the resolved
    # budget itself so an EXPLICIT tiny test budget is never silently
    # raised back above what the test forced.
    _OOM_BUDGET_FLOOR = 1 << 20

    def _budget(self) -> int:
        """Resolved device-memory budget in bytes (membudget.py): an
        explicit device_memory_budget wins; auto = HBM minus headroom
        on TPU, a generous cap on CPU; what the catalogs hold resident
        on the device (connectors/cached.py) comes off it: the
        governor plans with the memory that is there; a device-OOM
        retry halves it (_tighten_budget) so the governor re-plans
        chunked. Cached per (setting, tightening, resident bytes) —
        resolution may query device memory stats once."""
        resident = self.resident_table_bytes
        key = (self.device_memory_budget, self._oom_divisor, resident)
        if self._budget_resolved is None or self._budget_resolved[0] != key:
            resolved = MB.resolve_budget(self.device_memory_budget,
                                         resident=resident)
            floor = min(resolved, self._OOM_BUDGET_FLOOR)
            self._budget_resolved = (
                key,
                max(resolved // self._oom_divisor, floor),
            )
        return self._budget_resolved[1]

    def _catalog_sum(self, name: str) -> int:
        return sum(int(getattr(conn, name, 0) or 0)
                   for conn in self.catalogs.values())

    # what this executor's catalogs hold resident on the device and
    # what loading it cost (connectors/cached.py keeps the tallies; the
    # registry, /metrics and EXPLAIN ANALYZE read them under the
    # connector's names): process truths, not per-attempt counts
    @property
    def resident_table_bytes(self) -> int:
        return self._catalog_sum("resident_table_bytes")

    @property
    def resident_loads(self) -> int:
        return self._catalog_sum("resident_loads")

    @property
    def resident_load_wall_us(self) -> int:
        return self._catalog_sum("resident_load_wall_us")

    def _tighten_budget(self) -> None:
        """Halve the resolved budget for the next attempt (the device
        itself just proved the HBM model optimistic)."""
        self._oom_divisor = min(self._oom_divisor * 2, 1 << 10)

    def _check_deadline(self) -> None:
        dl = self.query_deadline
        if dl is not None and time.monotonic() > dl:
            raise QueryDeadlineExceeded(
                "query exceeded query_max_run_time (deadline passed "
                f"{time.monotonic() - dl:.2f}s ago)"
            )

    def _absorb_device_fault(self, e: BaseException,
                             oom_left: int) -> int:
        """Shared OOM-degradation gate for the execute()/
        stream_fragment() driver loops: absorb a device fault by
        tightening the budget (the membudget governor re-plans the
        next attempt chunked) and return the decremented retry budget;
        re-raise anything else, or anything once the budget is
        exhausted."""
        if oom_left <= 0 or not _is_device_fault(e):
            raise e
        self.device_oom_retries += 1
        self._tighten_budget()
        return oom_left - 1

    def _maybe_inject_oom(self) -> None:
        """Fault-injection hook for tests/chaos (SURVEY §6.3 extended
        inward): synthesize the device allocator's failure mode so the
        OOM-degradation ladder is exercisable on CPU."""
        if self.inject_device_oom > 0:
            self.inject_device_oom -= 1
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: injected device OOM (fault hook)"
            )

    def _fault_rows(self) -> Optional[int]:
        """Per-buffer row-capacity ceiling for governed sizing: on TPU
        the >=4M-row kernel fault line with construction headroom
        (shapes.SAFE_BUFFER_ROWS); None elsewhere. Tests and the static
        audit force it via self.fault_rows."""
        if self.fault_rows is not None:
            return self.fault_rows or None
        return (
            SH.SAFE_BUFFER_ROWS
            if jax.default_backend() == "tpu" else None
        )

    def _governed_target_rows(self, types, count: bool = True,
                              row_bytes: Optional[int] = None) -> int:
        """Generation chunk (page) size for a scan of `types`-typed
        columns: the configured page_rows, shrunk so ONE page buffer
        fits its budget share — the rewrite that lets a Q1/Q6-shaped
        pipeline stream an arbitrarily large table through fixed-size
        resident buffers (the SF100 on-ramp). count=False lets the
        static audit ask without bumping the rewrite counter;
        row_bytes overrides the width (fused chains govern by their
        WIDEST row — a generated-join chain's output page is wider
        than its scan)."""
        cap = MB.rows_cap(
            row_bytes or _row_bytes(types), self._budget(),
            self._fault_rows(), MB.SCAN_SHARE_DIV,
        )
        if cap is None or self.page_rows <= cap:
            return self.page_rows
        if count:
            self.memory_chunked_pipelines += 1
        return max(cap, SH.LADDER_MIN)

    def _join_parts(self, node: P.HashJoin, left_types, right_types,
                    est_build: Optional[int] = None,
                    row_b: Optional[int] = None):
        """Grace-partition pass count for a materialized join build:
        the legacy session thresholds (spill_bytes byte threshold,
        max_join_build_rows kernel ceiling) and the governor's
        model-driven sizing — one pass's build materialization must fit
        its budget share AND stay under the device fault line. Returns
        (parts, governed): governed means the MODEL forced chunking
        beyond what the thresholds asked for. Shared verbatim by the
        static audit (membudget.audit), so prediction and execution
        cannot drift."""
        if not (
            self._keys_partitionable(right_types, node.right_keys)
            and self._keys_partitionable(left_types, node.left_keys)
        ):
            return 1, False
        if est_build is None:
            est_build = self.estimate_rows(node.right)
        if row_b is None:
            row_b = _row_bytes(right_types)
        parts = 1
        if self.spill_bytes is not None:
            parts = self._spill_partitions(est_build * row_b)
        if self.max_build_rows:
            # kernel-size ceiling, independent of the byte threshold
            parts = max(
                parts,
                _next_pow2(-(-est_build // self.max_build_rows)),
            )
        budget = self._budget()
        # est_build * 2: a grace pass sizes its per-pass build chunks
        # with 2x slack over the expected 1/parts occupancy (partition-
        # hash fluctuation, _exec_join_partitioned) — the governed caps
        # must hold for the SLACKED buffer, or a "governed" pass lands
        # right back on the fault line
        gparts = SH.parts_for(
            est_build * 2, row_b,
            rows_cap=self._fault_rows(),
            bytes_cap=budget // MB.BUILD_SHARE_DIV if budget else None,
        )
        return max(parts, gparts), gparts > parts

    def output_types(self, node: P.PhysicalNode) -> List[T.SqlType]:
        """Static output channel types (reference: PlanNode.getOutputSymbols
        + TypeProvider)."""
        if isinstance(node, P.TableScan):
            schema = self.catalogs[node.catalog].table_schema(node.table)
            return [schema.column_type(c) for c in node.columns]
        if isinstance(node, (P.Values, P.RemoteSource)):
            return list(node.types)
        if isinstance(node, (P.Filter, P.Limit, P.Sort, P.TopN, P.Output)):
            return self.output_types(node.source)
        if isinstance(node, P.Project):
            return [e.type for e in node.exprs]
        if isinstance(node, P.Aggregation):
            if node.step == "partial":
                # keys followed by accumulator state columns (reference:
                # AggregationNode.Step.PARTIAL emits intermediate types)
                src = self.output_types(node.source)
                out = [src[c] for c in node.group_channels]
                for spec, in_t in zip(
                    node.aggregates, self._agg_in_types(node)
                ):
                    for st in S.state_layout(spec.function, in_t):
                        out.append(st.type)
                return out
            if node.step == "final":
                origin = self._partial_origin(node)
                src = self.output_types(origin.source)
                out = [
                    self.output_types(node.source)[i]
                    for i in range(len(node.group_channels))
                ]
                for spec in node.aggregates:
                    in_t = (None if spec.channel is None
                            else src[spec.channel])
                    out.append(S.result_type(
                        spec.function, in_t,
                        tuple(src[c] for c in spec.extra_channels),
                    ))
                return out
            src = self.output_types(node.source)
            out = [src[c] for c in node.group_channels]
            for spec in node.aggregates:
                in_t = None if spec.channel is None else src[spec.channel]
                out.append(S.result_type(
                    spec.function, in_t,
                    tuple(src[c] for c in spec.extra_channels),
                ))
            return out
        if isinstance(node, P.Exchange):
            return self.output_types(node.source)
        if isinstance(node, P.MarkDistinct):
            return self.output_types(node.source) + [
                T.BOOLEAN for _ in node.mark_channel_sets
            ]
        if isinstance(node, P.Window):
            from presto_tpu.ops import window as W

            src = self.output_types(node.source)
            out = list(src)
            for fn in node.functions:
                in_t = (
                    None if fn.arg_channel is None else src[fn.arg_channel]
                )
                out.append(W.result_type(fn, in_t))
            return out
        if isinstance(node, P.HashJoin):
            left = self.output_types(node.left)
            if node.join_type in ("semi", "anti"):
                return left + [T.BOOLEAN]
            return left + self.output_types(node.right)
        if isinstance(node, P.CrossJoin):
            return self.output_types(node.left) + self.output_types(
                node.right)
        if isinstance(node, P.UniqueId):
            return self.output_types(node.source) + [T.BIGINT]
        if isinstance(node, P.GroupId):
            return self.output_types(node.source) + [T.BIGINT]
        if isinstance(node, P.Unnest):
            out = self.output_types(node.source) + [node.element_type]
            if node.with_ordinality:
                out.append(T.BIGINT)
            return out
        if isinstance(node, P.Union):
            return self.output_types(node.sources[0])
        raise TypeError(f"unknown node: {node!r}")

    # ------------------------------------------------------------- execute
    def pages(self, node: P.PhysicalNode) -> Iterator[Page]:
        """Stream pages for a node, collecting per-node stats when an
        EXPLAIN ANALYZE run enabled them (reference: OperatorContext
        wall/row accounting feeding PlanPrinter)."""
        # result-cache points (presto_tpu/cache/): a designated
        # cacheable subtree serves from / populates the shared store;
        # the inflight guard lets the miss path re-enter this method
        # for the real stream. One dict probe when caching is on, zero
        # overhead (empty-dict falsy check) when off.
        if self._cache_points:
            entry = self._cache_points.get(id(node))
            if entry is not None and \
                    id(node) not in self._cache_inflight:
                yield from self._cached_pages(node, entry)
                return
        impl = self._pages_impl(node)
        if self._collect_stats is None:
            for page in impl:
                self._account_page(page)
                yield page
            return
        import time as _time

        st = self._collect_stats.setdefault(
            id(node), NodeStats(type(node).__name__)
        )
        while True:
            t0 = _time.perf_counter()
            try:
                page = next(impl)
                if self.stats_drain:
                    # force real completion so wall_s is device time,
                    # not dispatch time (on an earlier TPU runtime
                    # block_until_ready returned at dispatch; only
                    # a D2H read drained the queue).
                    # Every next() ends drained, so the time measured
                    # here is exactly this node's own marginal work.
                    from presto_tpu.devsync import drain as _drain

                    _drain(page)
            except StopIteration:
                st.wall_s += _time.perf_counter() - t0
                break
            st.wall_s += _time.perf_counter() - t0
            st.pages += 1
            st.row_counts.append(self._deferred_rows(page))
            self._account_page(page)
            yield page

    def _deferred_rows(self, page: Page):
        """One page's row count for the per-node accounting: a device
        value, resolved after the run (deferred-sync rule). The page
        brings it where the launch that made it counted it (Page.rows;
        a boundary that passes the page through unchanged hands the
        same count on); where it brings none, page.num_rows() is two
        eager programs dispatched from here. Host-served pages (cache
        replay / RemoteSource at the host sink) count host-side
        instead — num_rows() on a numpy page would implicitly re-stage
        the valid mask, an un-metered crossing the transfer auditor
        exists to kill."""
        v = page.valid
        if isinstance(v, np.ndarray):
            return int(XF.np_host(v).sum())
        if page.rows is not None:
            self.row_counts_launched += 1
            return page.rows
        self.row_counts_eager += 1
        with XF.eager("num-rows"):
            return page.num_rows()

    def _scan_chain(self, node: P.PhysicalNode, *, through_joins: bool,
                    stored_joins: bool = False):
        """Walk a Filter/Project/Exchange chain (and, when
        through_joins, generated-join-eligible HashJoins; with
        stored_joins also the ones whose build is a stored table,
        _stored_join_info, those that ride on an earlier build marked
        by _ride_stored_joins: the one-chip fused stream alone asks
        for them) down to its TableScan. THE one chain walker shared by
        the generated-join eligibility check and the fused-pipeline
        builder. Returns (scan, chain top-down) with HashJoins as
        (node, info) tuples, or None when any node breaks the chain."""
        chain: List = []
        cur = node
        while True:
            if isinstance(cur, (P.Filter, P.Exchange, P.Project)):
                chain.append(cur)
                cur = cur.source
            elif through_joins and isinstance(cur, P.HashJoin):
                left_types = self.output_types(cur.left)
                info = self._generated_join_info(cur, left_types)
                if info is None and stored_joins:
                    info = self._stored_join_info(cur, left_types)
                if info is None:
                    return None
                chain.append((cur, info))
                cur = cur.left
            elif isinstance(cur, P.TableScan):
                if stored_joins:
                    chain = self._ride_stored_joins(cur, chain)
                return cur, chain
            else:
                return None

    def _ride_stored_joins(self, scan: P.TableScan, chain: List) -> List:
        """A stored join rides on the build that carries its key. In a
        _scan_chain's links (top-down) a StoredJoin link J1 rides on an
        earlier one J0 when both are inner joins, J1's pivot probe
        channel is one J0 appended to the page (directly, or through a
        join that itself rides on J0), only joins that ride on J0 lie
        between them (any other link, a Filter, a Project, an Exchange
        or another join, ends the run), and J0's build has no more
        rows than the probe table has slots, so the lookup is made
        fewer times, never more. J1 is then probed once a BUILD row inside J0's build
        program (_stored_build_page) and emits no step: J0's page
        carries its columns after J0's own, in the chain's order, and
        J0's step gathers them all at once. A rider's key pair whose
        probe side lies below J0's columns becomes a pair of J0's
        step. Read from the chain and from nothing else; a chain
        without such a pair of links comes back as it is."""
        if sum(map(_stored_link, chain)) < 2:
            return chain
        out = list(chain)
        probe_rows = int(self.catalogs[scan.catalog].row_count(scan.table))
        budget = self._budget()
        head = None     # the run's J0: index in out, its first channel
        for i in range(len(out) - 1, -1, -1):
            link = out[i]
            if not (_stored_link(link) and link[0].join_type == "inner"):
                head = None
                continue
            jnode, info = link
            first = len(self.output_types(jnode.left))
            if head is not None and head[1] <= info.pivot_ch < first:
                j0, base = head
                hnode, hinfo = out[j0]
                at = first - base    # the rider's columns on J0's page
                types = self.output_types(jnode)[base:]
                nbytes = _stored_join_bytes(hinfo.cap, hinfo.rows, types)
                if not budget or nbytes <= budget // MB.BUILD_SHARE_DIV:
                    rider = StoredRider(
                        node=jnode, info=info,
                        pivot_ch=info.pivot_ch - base,
                        pairs=tuple((lk - base, rk)
                                    for lk, rk in info.extra_pairs
                                    if lk >= base))
                    out[j0] = (hnode, hinfo._replace(
                        extra_pairs=hinfo.extra_pairs + tuple(
                            (lk, at + rk) for lk, rk in info.extra_pairs
                            if lk < base),
                        nbytes=nbytes, riders=hinfo.riders + (rider,)))
                    out[i] = (jnode, info._replace(rides=True))
                    continue
            head = (i, first) if info.rows <= probe_rows else None
        return out

    def _chain_holds_cache_point(self, scan, chain) -> bool:
        """A chain member that is a live result-cache point must stay
        an observable pages() boundary (fusing through it would
        bypass _cached_pages entirely — no hit, no population); an
        INFLIGHT point is its own miss-path collection, where fusion
        is exactly what we want."""
        if not self._cache_points:
            return False
        for link in chain + [scan]:
            n = link[0] if isinstance(link, tuple) else link
            if id(n) in self._cache_points and \
                    id(n) not in self._cache_inflight:
                return True
        return False

    def _chain_steps(self, chain) -> List:
        """A _scan_chain's links (top-down) as the bottom-up list of
        page transforms a fused scan program applies to the page it
        generates (_apply_steps): ("map", page -> page) for a Filter
        or Project, ("join", page -> page) for a build-free generated
        join, ("joinw", page -> (page, multi_flag)) for a windowed
        one, ("sjoin", (page, build) -> page) for the probe of a
        stored build (_apply_steps hands it the chain's builds in
        this order; _fused_stream makes them; a join that rides on an
        earlier build, _ride_stored_joins, has no step: that build's
        step appends its columns). THE one step list of
        the one-chip fused stream and of the mesh executor's fused
        scan round (dist/executor.py)."""
        steps: List = []
        for nd in reversed(chain):
            if _stored_link(nd):
                jnode, info = nd
                if info.rides:
                    continue
                steps.append(("sjoin", functools.partial(
                    _stored_join_page, info.pivot_ch, info.extra_pairs,
                    jnode.join_type)))
            elif isinstance(nd, tuple):
                jnode, info = nd
                kern, windowed = self.generated_join_kernel(jnode, info)
                steps.append(("joinw" if windowed else "join", kern))
                self.generated_joins_used += 1
            else:
                fn = _node_replay_fn(nd)
                if fn is not None:
                    steps.append(("map", fn))
        return steps

    def _fused_stream(self, node: P.PhysicalNode, agg_tail=None,
                      key_extra=None) -> Optional[Iterator[Page]]:
        """Whole-pipeline fusion: when `node` is a chain of Filter /
        Project / Exchange / build-free generated joins over a
        TableScan of an on-device generator, compile the ENTIRE
        per-page pipeline — generation included — into ONE XLA program
        per split and stream its outputs.

        Reference: operator/ScanFilterAndProjectOperator.java fuses
        scan+filter+project for the same reason (avoid materializing
        between operators); the TPU translation goes further and fuses
        the whole driver loop for the chain, so a page pays ONE kernel
        launch instead of one per node (per-launch overhead dominates
        small per-node kernels).
        Returns None when the subtree has any non-fusable node.

        ``agg_tail`` extends the fusion THROUGH partial aggregation
        (see _fused_partial_tail): a ("map", fn, None) tail appends a
        plain page transform (global partial states), an
        ("aggflag", fn, merge) tail appends a grouped partial step
        whose overflow flag joins the deferred ladder — scan→filter→
        project→partial-agg in ONE program per split (~6
        launches total for Q1 SF1 instead of ~8 per page). ``merge``
        is the state-merge kernel the split-batched scan carries
        partial state through. ``key_extra`` salts the jit key with
        the caller's boost-dependent parameters.

        Split batching (split_batch_size) then folds the
        per-SPLIT loop itself into XLA: batches of splits run as ONE
        program — lax.scan with the partial-agg state as carry for agg
        tails, a vmapped [B, n_pad] stack emitted as one page for
        page-emitting chains — so the whole multi-split scan phase of
        a Q1/Q6-shaped query pays ceil(splits/B) launches instead of
        one per split."""
        if not self.use_jit:
            return None
        walked = self._scan_chain(node, through_joins=True,
                                  stored_joins=True)
        if walked is None:
            return None
        cur, chain = walked
        if self._chain_holds_cache_point(cur, chain):
            return None
        if not chain and agg_tail is None:
            return None  # a bare scan already runs as one program
        conn = self.catalogs[cur.catalog]
        # structural gate: fuse ONLY when pages() is exactly the base
        # per-split generation loop — a connector (or wrapper: caching,
        # DCN hash-split masking, instance-level instrumentation) that
        # overrides pages() transforms the stream in ways inlined
        # generation would silently bypass. Wrappers whose pages() IS
        # the base loop over their own splits() (the worker's
        # round-robin SplitFilterConnector) declare fused_scan_ok —
        # the fused stream respects their splits()/prune_splits().
        base_pages = (
            getattr(type(conn), "pages", None) is Connector.pages
            or getattr(type(conn), "fused_scan_ok", False)
        )
        if not base_pages or "pages" in vars(conn):
            return None
        names = tuple(cur.columns)
        # what a split's columns come from: a read of the table the
        # connector holds on the device (connectors/cached.py), or the
        # connector's traceable generator. A stored table loads at its
        # first touch, here, so the budget below already has it
        stored = getattr(conn, "stored_source", None)
        src = stored(cur.table, names, SH.bucket(self.page_rows)) \
            if stored is not None else None
        if src is None:
            if conn.gen_body(cur.table, 8, names) is None:
                return None
            src = _GeneratedSource(conn, cur.table, names)
        # a join over a stored table (_stored_join_info): its lookup
        # structure, built once a statement (_stored_build), is handed
        # to every launch beside the source's buffers, bottom-up as
        # the step list probes them (a join that rides on another's
        # build is made with it and probed inside it)
        builds = []
        for link in reversed(chain):
            if _stored_link(link) and not link[1].rides:
                built = self._stored_build(link[0], link[1])
                if built is None:
                    return None  # a column holds NULLs: through pages()
                builds.append(built)
        builds = tuple(builds)
        nb = len(builds)
        # the programs made below stay in the jit cache: they close
        # over the source's reads, never over the source, whose args
        # are a stored table's buffers (a write has to free them)
        reads = src.reads
        schema = conn.table_schema(cur.table)
        scan_types = tuple(schema.column_type(c) for c in names)
        dicts = getattr(conn, "_dicts", {}).get(cur.table, {})
        scan_dicts = tuple(dicts.get(c) for c in names)
        # generation-chunked splits (membudget.py): one split's padded
        # buffer fits its budget share AT THE CHAIN'S WIDEST ROW — a
        # generated-join chain emits left+right columns per slot, so
        # the output page, not the scan, is the binding width
        chain_row_b = max(
            _row_bytes(scan_types), _row_bytes(self.output_types(node))
        )
        splits = conn.splits(
            cur.table,
            self._governed_target_rows(scan_types,
                                       row_bytes=chain_row_b),
        )
        if cur.constraint:
            splits = conn.prune_splits(cur.table, splits, cur.constraint)

        steps = self._chain_steps(chain)
        batch_merge = None
        if agg_tail is not None:
            kind, fn, batch_merge = agg_tail
            steps.append((kind, fn))
            self.fused_partial_aggs += 1

        def make_page(datas, valid, n_pad, count):
            # canonical split shape: generation is padded to the ladder
            # bucket; rows past the split's real count mask out here
            # (generators have no bound — the dist scan relies on the
            # same property), so every tail split of every scale factor
            # reuses one program per bucket instead of minting a shape
            valid = valid & (
                jnp.arange(n_pad, dtype=jnp.int64) < count
            )
            return Page(blocks=tuple(
                Block(data=d, type=t, nulls=None, dictionary=dic)
                for d, t, dic in zip(datas, scan_types, scan_dicts)
            ), valid=valid)

        def run_split(gen_fn, built, n_pad, start, count):
            datas, valid = gen_fn(start)
            return _apply_steps(make_page(datas, valid, n_pad, count),
                                steps, built)

        def launch_args(a):
            # a launch's arguments: the source's buffers (none for a
            # generator), the chain's stored builds (none without a
            # stored join), then the splits' starts and counts
            return a[:len(a) - 2 - nb], a[len(a) - 2 - nb:-2]

        def run_one(n_pad, *a):
            src_a, built = launch_args(a)
            return run_split(reads.body(n_pad, *src_a), built, n_pad,
                             *a[-2:])

        def jit_one(n_pad):
            def make():
                return functools.partial(run_one, n_pad)

            if nb:
                return self._jit(
                    ("stored_probe", node, key_extra, cur.table, n_pad),
                    make=make)
            if src.args:
                return self._jit(
                    ("stored", node, key_extra, cur.table, n_pad),
                    make=make)
            return self._jit(
                ("fused", node, key_extra, cur.table, n_pad), make=make)

        def count_stored(n_splits, n_pad):
            if src.args:
                self.resident_splits_scanned += n_splits
                self.resident_bytes_scanned += (
                    n_splits * n_pad * src.slot_bytes)

        scan_row_b = chain_row_b

        # cross-query launch batching (ISSUE 17): when the concurrent
        # server attached a LaunchBatcher and the session didn't force
        # it off, per-split launches first offer themselves to the
        # shared batch point — compatible launches from OTHER queries
        # (equal frozen plan nodes hash equal, so identical statements
        # across clients share a key) gang into one vmapped step.
        # (a stored source's launches run solo: ganging them is not
        # built)
        xq_on = (
            self.launch_batcher is not None
            and self.cross_query_batching not in
            (False, None, "false", "off")
            and not src.args and not nb
        )

        def make_xq_fn(n_pad, B):
            # shared batched program: generation vmapped over the
            # stacked [B, n_pad] slots, then DEMUXED IN-PROGRAM — the
            # jitted fn returns one (page, flags) pytree per slot, so
            # every ganged query walks away with exactly the page its
            # solo launch would have produced (row parity is
            # structural, not reassembled on the host)
            gen_b = reads.batch(n_pad)

            def post(datas, valid, count):
                return _apply_steps(
                    make_page(datas, valid, n_pad, count), steps)

            def run_xq(starts, counts):
                datas, valid = gen_b(starts)
                out = jax.vmap(post)(datas, valid, counts)
                return tuple(
                    jax.tree_util.tree_map(lambda x, i=i: x[i], out)
                    for i in range(B)
                )

            return run_xq

        def launch_xq(split):
            """Offer one split to the cross-query batch point; returns
            the demuxed page, or None when the solo path should run
            (batching off, oversized bucket, lone leader, or a chain
            that does not trace under vmap)."""
            n_pad = SH.bucket(split.row_count)
            cap = min(SH.SPLIT_BATCH_MAX,
                      SH.SPLIT_BATCH_ROWS_MAX // max(n_pad, 1))
            if cap < 2:
                return None  # one slot already rides the fault line
            gkey = ("xq", node, key_extra, cur.table, n_pad)

            def make_batched(entries):
                # EXACT width, not the split-batch bucket: a rounded-up
                # lane is dead compute the full n_pad rows wide, which
                # on a compute-bound backend erases the dispatch win.
                # Widths are small (cap <= SPLIT_BATCH_MAX) so the
                # per-width program count is bounded and warm after the
                # first gang at each width.
                B = len(entries)
                jkey = ("xq_batch", node, key_extra, cur.table,
                        n_pad, B)
                run_xq = self._jit(
                    jkey, make=lambda: make_xq_fn(n_pad, B))
                starts = np.zeros(B, np.int64)
                counts = np.zeros(B, np.int64)
                for j, (s0, c0) in enumerate(entries):
                    starts[j] = s0
                    counts[j] = c0
                try:
                    # metered h2d: 2xB int64 slot descriptors per
                    # shared launch (exec/xfer.py choke point),
                    # attributed to the leader
                    out = run_xq(
                        XF.to_device(starts, label="batch-starts"),
                        XF.to_device(counts, label="batch-starts"))
                except Exception:
                    # conservative escape (the stream_batched shape):
                    # a chain that does not trace under vmap demotes
                    # every participant to its solo path
                    self._jit_drop(jkey)
                    self.split_batch_fallbacks += 1
                    raise
                return [out[j] for j in range(len(entries))]

            res = self.launch_batcher.submit(
                gkey, split.start_row, split.row_count, cap,
                self.cross_query_batch_wait_ms, make_batched)
            if res is None:
                return None
            page, flags, width, waited_ms, leader = res
            if leader:
                # ONE launch covers every ganged query — only the
                # leader pays it (make_batched ran on this executor
                # and the launch point counted it), so aggregate
                # program_launches measures real dispatches
                self.cross_query_batches += 1
            self.cross_query_batched_queries += 1
            self.queries_per_launch = max(
                self.queries_per_launch, width)
            self.batch_gather_wait_ms += int(waited_ms)
            self.splits_scanned += 1
            # this query's slot share of the stacked batch buffer
            self.peak_memory_bytes = max(
                self.peak_memory_bytes, n_pad * scan_row_b
            )
            self._pending_overflow.extend(flags)
            return page

        def launch_one(split):
            solo_mark = contextlib.nullcontext()
            if xq_on:
                page = launch_xq(split)
                if page is not None:
                    return page
                # solo fallthrough still seeds the train: same-key
                # arrivals linger behind this execution exactly as
                # behind a batched step (launch_batcher.solo_inflight)
                n_pad = SH.bucket(split.row_count)
                solo_mark = self.launch_batcher.solo_inflight(
                    ("xq", node, key_extra, cur.table, n_pad))
            n_pad = SH.bucket(split.row_count)
            run_fused = jit_one(n_pad)
            with solo_mark:
                page, flags = run_fused(
                    *src.args, *builds,
                    jnp.int64(split.start_row),
                    jnp.int64(split.row_count),
                )
            count_stored(1, n_pad)
            # the generation buffer lives INSIDE the fused program and
            # never passes _account_page — account it here so
            # peak_device_bytes stays honest for fused pipelines
            self.peak_memory_bytes = max(
                self.peak_memory_bytes, n_pad * scan_row_b
            )
            self.splits_scanned += 1
            self._pending_overflow.extend(flags)
            return page

        live = [s for s in splits if s.row_count]

        def stream_single():
            for split in live:
                yield launch_one(split)

        bmax = 0
        if len(live) > 1:
            n_pad_all = max(SH.bucket(s.row_count) for s in live)
            bmax = self._split_batch_max(
                n_pad_all, scanned=agg_tail is not None,
                row_bytes=chain_row_b)
        if bmax < 2:
            return stream_single()

        # ---------------- split-batched execution (one program per
        # batch of splits). One canonical program per
        # (pipeline, n_pad, batch bucket): full batches are the pow-2
        # bmax, the tail batch is its own bucket, padded slots carry
        # count=0 so every generated row masks out.
        def or_flags(flags):
            out = jnp.zeros((), dtype=jnp.bool_)
            for f in flags:
                out = out | f
            return out

        def build_batch_fn():
            # every run_batch below is called with the source's buffers
            # (none for a generator) before the splits' starts and
            # counts
            if agg_tail is None:
                # page-emitting chain: vmap the fused body over the
                # stacked [B, n_pad] batch; the batch emits as ONE
                # page of B*n_pad slots (the exact concatenation of
                # the per-split pages), so downstream per-page
                # programs amortize their launches by B too
                def run_batch(*a):
                    src_a, built = launch_args(a)

                    def post(datas, valid, count):
                        return _apply_steps(
                            make_page(datas, valid, n_pad_all, count),
                            steps, built,
                        )

                    datas, valid = reads.batch(n_pad_all, *src_a)(a[-2])
                    pages, flags = jax.vmap(post)(datas, valid, a[-1])
                    return (
                        _merge_leading(pages),
                        tuple(jnp.any(f) for f in flags),
                    )

                return run_batch
            if steps[-1][0] == "map":
                # global partial-agg tail: scan over splits, stacking
                # the 1-row state pages — the batch emits exactly the
                # concat of the per-split states, so parity with the
                # unbatched driver loop is bit-exact
                def run_batch(*a):
                    src_a, built = launch_args(a)
                    gen_fn = reads.body(n_pad_all, *src_a)

                    def body(_, x):
                        page, flags = run_split(
                            gen_fn, built, n_pad_all, x[0], x[1])
                        return 0, (page, or_flags(flags))

                    _, (states, flags) = jax.lax.scan(
                        body, 0, (a[-2], a[-1]))
                    return _merge_leading(states), (jnp.any(flags),)

                return run_batch
            # grouped partial-agg tail: lax.scan over splits with the
            # partial-aggregation STATE as carry — generation,
            # filtering, and accumulation never return to the host.
            # The carry is one merge-capacity state page; each split's
            # partial states fold in through the same merge kernel the
            # host _FoldBuffer uses, and every overflow (agg, join
            # window, merge) ORs into one deferred flag per batch.
            pre = steps[:-1]
            tail_fn = steps[-1][1]

            def run_batch(*a):
                src_a, built = launch_args(a)
                gen_fn = reads.body(n_pad_all, *src_a)
                starts, counts = a[-2:]

                def one_state(start, count):
                    datas, valid = gen_fn(start)
                    page, flags = _apply_steps(
                        make_page(datas, valid, n_pad_all, count), pre,
                        built)
                    st, ovf = tail_fn(page)
                    return st, or_flags(flags) | ovf

                # split 0 seeds the carry (merged alone into the carry
                # capacity, so init and body share one state shape)
                st0, f0 = one_state(starts[0], counts[0])
                acc, m0 = batch_merge(st0)

                def body(carry, x):
                    acc, ovf = carry
                    st, f = one_state(x[0], x[1])
                    acc2, mo = batch_merge(concat_all([acc, st]))
                    return (acc2, ovf | f | mo), None

                (acc, ovf), _ = jax.lax.scan(
                    body, (acc, f0 | m0),
                    (starts[1:], counts[1:]),
                )
                return acc, (ovf,)

            return run_batch

        def stream_batched():
            i = 0
            while i < len(live):
                chunk = live[i:i + bmax]
                if len(chunk) == 1:
                    # a lone tail split reuses the per-split program
                    # instead of padding a 2-batch (a padded slot
                    # still runs the full generator)
                    yield launch_one(chunk[0])
                    i += 1
                    continue
                B = SH.split_batch_bucket(len(chunk))
                tail = (node, key_extra, cur.table, n_pad_all, B)
                if nb:
                    key = ("stored_probe_batch", *tail)
                    run_batch = self._jit(
                        ("stored_probe_batch", *tail),
                        make=build_batch_fn)
                elif src.args:
                    key = ("stored_batch", *tail)
                    run_batch = self._jit(
                        ("stored_batch", *tail), make=build_batch_fn)
                else:
                    key = ("fused_batch", *tail)
                    run_batch = self._jit(
                        ("fused_batch", *tail), make=build_batch_fn)
                starts = np.zeros(B, np.int64)
                counts = np.zeros(B, np.int64)
                for j, s in enumerate(chunk):
                    starts[j] = s.start_row
                    counts[j] = s.row_count
                try:
                    # metered h2d: 2xB int64 split descriptors per
                    # batched launch (exec/xfer.py choke point)
                    page, flags = run_batch(
                        *src.args, *builds,
                        XF.to_device(starts, label="batch-starts"),
                        XF.to_device(counts, label="batch-starts"))
                except Exception:
                    if i > 0:
                        raise
                    # conservative escape: a chain that does not trace
                    # under vmap/scan (custom kernels, host callbacks)
                    # runs the per-split loop instead — nothing has
                    # been yielded yet, so the stream restarts whole
                    self._jit_drop(key)
                    self.split_batch_fallbacks += 1
                    yield from stream_single()
                    return
                self.splits_scanned += len(chunk)
                count_stored(len(chunk), n_pad_all)
                self._pending_overflow.extend(flags)
                # vmapped batches materialize the [B, n_pad] stack;
                # scanned (agg-tail) batches carry one split at a time
                live_rows = (
                    n_pad_all if agg_tail is not None
                    else B * n_pad_all
                )
                self.peak_memory_bytes = max(
                    self.peak_memory_bytes, live_rows * scan_row_b
                )
                yield page
                i += len(chunk)

        return stream_batched()

    def _fused_partial_tail(self, node: P.Aggregation, layouts,
                            cap: Optional[int], max_iters: Optional[int]):
        """The partial-aggregation tail step for _fused_stream — a
        (kind, fn, batch_merge) triple — or None when the shape should
        not fuse. Global aggregations always qualify. Grouped ones
        qualify unless fusing would bypass the join-output compaction
        stream (_agg_source_pages): big group capacity AND a join in
        the chain — there the blocking agg's per-sparse-page cost
        dwarfs the saved launches. Everywhere else the fused tail does
        EXACTLY the per-page work of the unfused driver loop, minus
        the launches. ``batch_merge`` (grouped tails only) is the
        state-merge kernel the split-batched lax.scan carries partial
        state through — the in-program analog of the host
        _FoldBuffer's merge."""
        mode = self.agg_fusion
        if mode in (False, None, "false", "off") or not self.use_jit:
            return None
        if mode == "auto" and jax.default_backend() != "tpu":
            return None
        layouts_t = tuple(tuple(l) for l in layouts)
        if not node.group_channels:
            return ("map", functools.partial(
                _partial_global_agg, node.aggregates, layouts_t), None)
        if cap is None:
            return None
        if (node.capacity > A.MATMUL_AGG_MAX_GROUPS
                and _subtree_has_join(node.source)):
            return None
        raw = functools.partial(
            _partial_agg_page, node.group_channels, node.aggregates,
            layouts_t, collect_k=self._collect_k_eff,
        )
        merge_raw = functools.partial(
            _merge_partials_page, node.aggregates, layouts_t,
            len(node.group_channels), collect_k=self._collect_k_eff,
        )
        return (
            "aggflag",
            functools.partial(_fused_agg_step, raw, cap, max_iters),
            functools.partial(_fused_merge_step, merge_raw, cap,
                              max_iters),
        )

    def _split_batch_max(self, n_pad: int, scanned: bool,
                         row_bytes: int = 0) -> int:
        """Effective max splits per batched launch for one fused
        stream, or 0 when split batching is off. split_batch_size
        resolution: "auto" engages on TPU only (the win is the
        per-launch overhead, which CPU doesn't pay, while the
        scanned/vmapped programs cost real CPU compile time — the
        pallas_join_enabled policy); an int forces that max on any
        backend. vmapped page batches (scanned=False) additionally
        bound B*n_pad under the kernel fault line; the lax.scan
        agg paths carry one split at a time and are exempt. The
        result is floored to a power of two so full batches land on
        the shapes.py ladder and only the tail batch pads."""
        mode = self.split_batch
        if mode in (False, None, 0, "false", "off", "0"):
            return 0
        if not self.use_jit:
            return 0
        if mode == "auto":
            if jax.default_backend() != "tpu":
                return 0
            cap = SH.SPLIT_BATCH_MAX
        else:
            cap = int(mode)
        if not scanned and n_pad > 0:
            cap = min(cap, SH.SPLIT_BATCH_ROWS_MAX // max(n_pad, 1))
            # governed: the stacked [B, n_pad] batch buffer fits its
            # budget share too (membudget.py), not just the row line
            budget = self._budget()
            if budget and row_bytes:
                cap = min(
                    cap,
                    max((budget // MB.SCAN_SHARE_DIV)
                        // (n_pad * row_bytes), 1),
                )
        if cap < 2:
            return 0
        return 1 << (cap.bit_length() - 1)

    def _pages_impl(self, node: P.PhysicalNode) -> Iterator[Page]:
        if isinstance(node, (P.Filter, P.Project, P.HashJoin)):
            fused = self._fused_stream(node)
            if fused is not None:
                yield from fused
                return
        if isinstance(node, P.TableScan):
            conn = self.catalogs[node.catalog]
            # generation-chunked scan (membudget.py): page size shrinks
            # so one generated buffer fits its budget share — the same
            # stream shape, smaller resident chunks
            yield from conn.pages(
                node.table, node.columns,
                target_rows=self._governed_target_rows(
                    self.output_types(node)
                ),
                constraint=node.constraint,
            )
            return
        if isinstance(node, P.RemoteSource):
            # DCN ingest (reference: ExchangeOperator): the registered
            # supplier yields deserialized host pages; stage on device
            # unless the pages feed only result decode (the host sink)
            serve_host = id(node) in self._host_sink_ids
            for page in self.remote_sources[node.key]():
                yield page if serve_host else XF.to_device(
                    page, label="remote-source")
            return
        if isinstance(node, P.Values):
            cols = list(zip(*node.rows)) if node.rows else [
                [] for _ in node.types
            ]
            yield Page.from_arrays(
                [list(c) for c in cols], list(node.types)
            )
            return
        if isinstance(node, P.Filter):
            fn = self._jit(
                ("filter", node.predicate),
                lambda page: evaluate_filter(node.predicate, page, jnp),
            )
            for page in self.pages(node.source):
                yield fn(page)
            return
        if isinstance(node, P.Project):
            fn = self._jit(
                ("project", node.exprs),
                functools.partial(_project_page, node.exprs),
            )
            for page in self.pages(node.source):
                yield fn(page)
            return
        if isinstance(node, P.Aggregation):
            yield from self._exec_aggregation(node)
            return
        if isinstance(node, P.HashJoin):
            yield from self._exec_join(node)
            return
        if isinstance(node, P.CrossJoin):
            right_pages = list(self.pages(node.right))
            if not right_pages:
                return
            build_all = concat_all(right_pages)
            # modest static build capacity (cross-join output is
            # probe_cap x build_cap — capacity-sized builds would explode
            # quadratically); dropped rows raise the deferred overflow
            # flag and the query retries with boosted capacity
            bcap = min(
                _next_pow2(build_all.capacity),
                _next_pow2(4096 * self._capacity_boost),
            )
            self._pending_overflow.append(build_all.num_rows() > bcap)
            build = compact_page(build_all, bcap)
            fn = self._jit(
                ("cross", build.capacity),
                _cross_join_page,
            )
            for page in self.pages(node.left):
                yield fn(page, build)
            return
        if isinstance(node, P.UniqueId):
            offset = 0
            for page in self.pages(node.source):
                ids = Block(
                    data=jnp.arange(page.capacity, dtype=jnp.int64) + offset,
                    type=T.BIGINT,
                )
                offset += page.capacity
                yield Page(blocks=page.blocks + (ids,), valid=page.valid)
            return
        if isinstance(node, P.Unnest):
            for page in self.pages(node.source):
                dic = page.block(node.array_channel).dictionary
                fn = self._jit(
                    ("unnest", node.array_channel, node.element_type,
                     node.with_ordinality, dic, page.capacity),
                    functools.partial(
                        _unnest_page, node.array_channel,
                        node.element_type, node.with_ordinality,
                    ),
                )
                yield fn(page)
            return
        if isinstance(node, P.GroupId):
            # one replica per grouping set: absent keys nulled, gid
            # appended (reference: GroupIdOperator's page replication)
            fns = [
                self._jit(
                    ("groupid", node.key_channels, mask, si),
                    functools.partial(_group_id_page, node.key_channels,
                                      mask, si),
                )
                for si, mask in enumerate(node.set_masks)
            ]
            for page in self.pages(node.source):
                for fn in fns:
                    yield fn(page)
            return
        if isinstance(node, P.Union):
            for src in node.sources:
                yield from self.pages(src)
            return
        if isinstance(node, P.MarkDistinct):
            pages = list(self.pages(node.source))
            if not pages:
                return
            merged = concat_all(pages) if len(pages) > 1 else pages[0]
            self._account_page(merged)
            fn = self._jit(
                ("markdistinct", node.mark_channel_sets),
                functools.partial(
                    _mark_distinct_page, node.mark_channel_sets
                ),
                static_argnums=(1, 2),
            )
            # boost rides as a static arg so the retry ladder actually
            # deepens probing (a boost baked into the partial would be
            # invisible to the jit cache key)
            out, overflow = fn(
                merged, _next_pow2(merged.capacity),
                64 * self._capacity_boost,
            )
            self._pending_overflow.append(overflow)
            yield out
            return
        if isinstance(node, P.Window):
            from presto_tpu.ops import window as W

            pages = list(self.pages(node.source))
            if not pages:
                return
            merged = concat_all(pages) if len(pages) > 1 else pages[0]
            src_types = self.output_types(node.source)
            out_types = tuple(self.output_types(node)[len(src_types):])
            fn = self._jit(
                ("window", node.partition_channels, node.order_keys,
                 node.functions, out_types, merged.capacity),
                functools.partial(
                    W.window_page, node.partition_channels,
                    node.order_keys, node.functions, out_types,
                ),
            )
            yield fn(merged)
            return
        if isinstance(node, P.TopN):
            # streaming top-N (reference: TopNOperator's bounded heap):
            # per page, keep the local top-N, then merge with the running
            # candidate set — never materializes the whole input
            running = None
            for page in self.pages(node.source):
                local_fn = self._jit(
                    ("topn_local", node.keys, node.limit, page.capacity),
                    functools.partial(sort_page, sort_keys=node.keys,
                                      limit=node.limit),
                )
                local = local_fn(page)
                if running is None:
                    running = local
                    continue
                merge_fn = self._jit(
                    ("topn_merge", node.keys, node.limit,
                     running.capacity, local.capacity),
                    functools.partial(_topn_merge, node.keys, node.limit),
                    # both the running candidate set and the local
                    # top-N die at the merge: the chained per-page
                    # merges reuse one HBM allocation in place
                    donate_argnums=(0, 1),
                )
                running = merge_fn(running, local)
            if running is not None:
                yield running
            return
        if isinstance(node, P.Sort):
            pages = list(self.pages(node.source))
            if not pages:
                return
            merged = concat_all(pages)
            self._account_page(merged)
            key = ("sort_page", node.keys, None, merged.capacity)
            fn = self._jit(
                key, functools.partial(sort_page, sort_keys=node.keys)
            )
            yield fn(merged)
            return
        if isinstance(node, P.Limit):
            # running row count stays a DEVICE scalar (deferred-sync rule:
            # a host read here would poison every later launch); no early
            # exit, but every page is a cheap mask update
            consumed = jnp.int64(0)
            fn = self._jit(
                ("limit", node.count, node.offset),
                functools.partial(_limit_with_count, node.count,
                                  node.offset),
            )
            for page in self.pages(node.source):
                out, consumed = fn(page, consumed)
                yield out
            return
        if isinstance(node, P.Output):
            yield from self.pages(node.source)
            return
        if isinstance(node, P.Exchange):
            # single-device execution: every exchange is a no-op pass-
            # through (one device holds everything); DistExecutor overrides
            # with the collective implementations
            yield from self.pages(node.source)
            return
        raise TypeError(f"unknown node: {node!r}")

    def execute(self, node: P.PhysicalNode):
        """Materialize results: (column_names, list of row tuples).

        Reference analog: testing/MaterializedResult via LocalQueryRunner.

        Runs the whole plan with no host synchronization (see __init__),
        then checks the accumulated capacity-overflow flags once; on
        overflow the query re-runs with 4x capacities (query-scope analog
        of the reference's per-operator retry).
        """
        names = (
            list(node.names) if isinstance(node, P.Output) else None
        )
        self._capacity_boost = 1  # per-query; grows only across retries
        self.capacity_boost_retries = 0
        self.profile_store_hits = 0
        if self.trace is None:
            # untraced queries pin the span counter at 0; traced ones
            # reset at obs.attach (the DCN coordinator's stage spans
            # precede this root-fragment execute and must survive it)
            self.trace_spans = 0
        prof_key = self._seed_profile(node)
        self.peak_memory_bytes = 0
        self.spill_partitions_used = 0
        self.host_spill_pages = 0
        self.host_spill_bytes_used = 0
        self.disk_spill_pages = 0
        self.skew_chunks_used = 0
        self.device_oom_retries = 0
        self._oom_divisor = 1
        # generated/pallas counters accumulate for the executor's
        # lifetime (tests assert before/after deltas); snapshot them so
        # EXPLAIN ANALYZE can report THIS query's engagement
        self._joins_counter_base = (
            self.generated_joins_used, self.pallas_joins_used
        )
        cc_base = CC.snapshot()
        oom_left = self.device_oom_attempts
        # pre-compile plan verification (exec/plan_check.py): schema-
        # consistent edges, ladder/fault-line capacities, canonical
        # jit-key material — auto-on under pytest, off on the hot
        # serving path (plan_check session property)
        self._verify_plan(node)
        # result-cache points (presto_tpu/cache/): pages() serves the
        # selected subtrees from the shared store; a whole-plan hit
        # replays with zero compiles and zero launches
        self._select_cache_points(node)
        # transfer plane (ISSUE 12, exec/xfer.py): fresh per-query
        # gauges, and the host-serve sink — pages of the root (and of
        # anything under its Output pass-through chain) feed ONLY row
        # decode, so a cache replay there serves host pages with zero
        # crossings
        self._reset_transfer_gauges()
        self._host_sink_ids = self._sink_chain_ids(node)
        # lifecycle tracing (obs/trace.py): spans record at attempt/
        # page boundaries on the driver thread only — one `is None`
        # check is the entire cost with tracing off. Tracing borrows
        # the EXPLAIN ANALYZE per-node accounting for operator spans;
        # per-page cost is two perf_counter calls plus retaining one
        # deferred row count per (node, page): the one the page
        # brings from the launch that made it (Page.rows: over a mesh
        # every program returns it, so nothing is dispatched here),
        # else page.num_rows(), two eager programs (one device: the
        # host is far ahead, they cost nothing end to end). No device
        # sync until after the run, where _resolve_row_counts reads
        # them in one pull (the reference always collects
        # OperatorStats; execute() retains every output page anyway,
        # so the handles are marginal). query_trace_enabled=false
        # drops all of it for latency-critical serving.
        tr = self.trace
        own_stats = False
        if tr is not None and self._collect_stats is None:
            self._collect_stats = {}
            own_stats = True
        exec_span = None
        outer_attempt = self._attempt_span
        if tr is not None:
            # the statement's own run is a phase (it opens where the
            # plan phase ends); a plan-time scalar subquery's run
            # nests under the open plan span
            exec_span = (
                tr.phase("execute", type(node).__name__)
                if self.trace_parent is None else
                tr.begin("execute", type(node).__name__,
                         parent=self.trace_parent))
            self.trace_spans += 1
        _prev_sink = XF.swap_sink(self)
        try:
            attempts = 0
            while attempts < 6:
                self._begin_attempt()
                if self._collect_stats is not None:
                    # drop failed-attempt stats
                    self._collect_stats.clear()
                att_span = None
                if tr is not None:
                    att_span = self._attempt_span = tr.begin(
                        "attempt", f"a{attempts}", parent=exec_span,
                        boost=self._capacity_boost)
                    self.trace_spans += 1
                try:
                    self._maybe_inject_oom()
                    out_pages = []
                    for page in self.pages(node):
                        self._check_deadline()
                        out_pages.append(page)
                    overflow = self._overflow_flagged()
                    rows: List[tuple] = []
                    if not overflow:
                        for page in out_pages:
                            rows.extend(_decode_result_page(page))
                except QueryDeadlineExceeded:
                    if tr is not None:
                        tr.end(att_span, outcome="deadline")
                    raise
                except Exception as e:  # noqa: BLE001 - ladder gate
                    # device-OOM degradation: a RESOURCE_EXHAUSTED /
                    # allocation fault re-enters under a HALVED budget
                    # — an HBM-model miss becomes a slow correct query
                    # instead of a crashed one. Anything else (and an
                    # exhausted OOM budget) raises through.
                    if tr is not None:
                        tr.end(att_span, outcome="device-fault")
                    oom_left = self._absorb_device_fault(e, oom_left)
                    continue
                if overflow:
                    # re-enter at the next rung of the SHARED ladder
                    # (shapes.py): boosted sizes coincide with a larger
                    # query's first-attempt shapes, so the retry reuses
                    # cached programs instead of minting fresh ones
                    if tr is not None:
                        tr.end(att_span, outcome="overflow",
                               **self._agg_sizing_attrs())
                    self._capacity_boost = SH.next_boost(
                        self._capacity_boost)
                    self.capacity_boost_retries += 1
                    attempts += 1
                    continue
                if tr is not None:
                    self._trace_operators(tr, att_span)
                    tr.end(att_span, outcome="ok", rows=len(rows),
                           launches=dict(self._launches_by_label),
                           exchange_launches=self.exchange_launches,
                           mesh_fused_rounds=self.mesh_fused_rounds,
                           mesh_batched_rounds=(
                               self.mesh_batched_rounds),
                           row_counts_launched=(
                               self.row_counts_launched),
                           row_counts_eager=self.row_counts_eager,
                           resident_splits_scanned=(
                               self.resident_splits_scanned),
                           resident_bytes_scanned=(
                               self.resident_bytes_scanned),
                           join_builds=self.join_builds,
                           join_probes_at_build=(
                               self.join_probes_at_build),
                           join_build_rows=self.join_build_rows,
                           join_build_bytes=self.join_build_bytes,
                           **self._agg_sizing_attrs())
                # overflow-free attempt: completed cache streams are
                # safe to publish (decode above already paid the sync)
                self._publish_cache_pending()
                if prof_key is not None:
                    self._record_profile(prof_key, len(rows))
                return names, rows
            raise RuntimeError(
                "capacity overflow persisted after 6 boosted retries"
            )
        finally:
            XF.swap_sink(_prev_sink)
            # release materialized intermediates (HBM/host pages) the
            # moment the query is done
            self._release_stream_cache()
            self._cache_points = {}
            self._cache_pending = []
            self._snap_compile_counters(cc_base)
            # what is timed after this belongs to no attempt of this
            # run (the next statement's trace numbers its spans anew)
            self._attempt_span = outer_attempt
            if tr is not None:
                tr.end(exec_span, boost=self._capacity_boost)
            if own_stats:
                self._collect_stats = None

    def _agg_sizing_attrs(self) -> Dict[str, object]:
        """What the attempt's grouped aggregation was sized to, for
        the attempt span: of several aggregations the costliest (most
        passes, then largest capacity). Empty without one."""
        if not self._agg_sizings:
            return {}
        sz = max(self._agg_sizings, key=lambda z: (z.parts, z.cap))
        return {"agg_parts": sz.parts, "agg_cap": sz.cap,
                "agg_compact_rows": sz.compact_rows,
                "agg_sized_by": sz.sized_by}

    def _begin_attempt(self) -> None:
        """Per-attempt reset shared by every overflow-ladder driver
        (execute(), stream_fragment()): deferred flags, materialized
        intermediates (cached pages may embed overflow-truncated
        results), and the per-attempt gather/fusion counters — a
        retried attempt re-defers and re-materializes from scratch, so
        cumulative counts would break the exactly-one-gather-per-
        carried-column accounting. Unpublished result-cache streams
        drop too: they may embed the overflow that forced this retry."""
        self._pending_overflow = []
        self._release_stream_cache()
        self._cache_pending = []
        self._cache_inflight = set()
        self.gathers_deferred = 0
        self.gathers_materialized = 0
        self.fused_partial_aggs = 0
        self.program_launches = 0
        self.device_launches = 0
        self.exchange_launches = 0
        self.mesh_fused_rounds = 0
        self.mesh_batched_rounds = 0
        self.row_counts_launched = 0
        self.row_counts_eager = 0
        self.dispatch_wall_us = 0
        self.device_wait_us = 0
        self.resident_splits_scanned = 0
        self.resident_bytes_scanned = 0
        self.join_builds = 0
        self.join_probes_at_build = 0
        self.join_build_rows = 0
        self.join_build_bytes = 0
        self.join_build_wall_us = 0
        self._launches_by_label = {}
        self._agg_sizings = []
        self.splits_scanned = 0
        self.queries_per_launch = 0
        self.memory_chunked_pipelines = 0
        self.buffers_donated = 0

    # -------------------------------------------------- result cache
    def _select_cache_points(self, node: P.PhysicalNode) -> None:
        """Per-query cache-point selection (cache/rules.py): maximal
        cacheable subtrees containing a materializing operator,
        gated by _cache_subtree_ok — the distributed executor allows
        only REPLICATED subtrees (mesh-sharded mid-plan pages cannot
        host-replay; replicated interiors can, the ISSUE 15 mesh
        residency rule).

        Keys are salted with the EXECUTOR config that can change a
        successful subtree's output without appearing in the plan:
        collect_k bounds collect-state aggregates (array_agg & family)
        and page_rows shapes the replayed page stream itself — the
        store is process-shared, so two sessions with different
        settings must never address one entry."""
        self._cache_points = {}
        if self.result_cache is None:
            return
        from presto_tpu.cache import select_cache_points

        from presto_tpu.cache.rules import stream_watermark

        salt = f"k{self.collect_k}.p{self.page_rows}"
        self._cache_points = {
            i: (f"{key}:{salt}", n, tables,
                stream_watermark(tables, self.catalogs),
                snap,
                # family keys carry the same executor salt as entry
                # keys: siblings under different collect_k/page_rows
                # must never answer each other
                (f"{fam[0]}:{salt}", fam[1])
                if fam is not None else None)
            for i, (key, n, tables, snap, fam) in select_cache_points(
                node, self.catalogs,
                allow=self._cache_subtree_ok,
                subsumable=self.cache_subsumption,
            ).items()
        }

    def count_warm_load(self, loaded: int, drops: int) -> None:
        """Fold one warm-start pass's outcome onto this executor's
        counter surface (runner.apply_session drives the pass; the
        counters live here so EXPLAIN ANALYZE / /metrics render them
        through the one registry snapshot)."""
        self.cache_warm_loads += loaded
        self.cache_manifest_drops += drops

    def _cache_subtree_ok(self, node: P.PhysicalNode) -> bool:
        """Whether a subtree's page stream may become a cache point.
        The base executor's pages are always ordinary single-stream
        Pages — everything is allowed; the DistExecutor narrows to
        replicated subtrees (mesh-sharded pages cannot host-replay)."""
        return True

    def _cached_pages(self, node: P.PhysicalNode,
                      entry) -> Iterator[Page]:
        """Serve one cache point: replay stored host pages on a hit
        (no compile, no launch, one device_put per page); on a miss,
        stream the real subtree (re-entrant through pages() via the
        inflight guard) while collecting, and stage the completed
        stream for publication after the attempt proves overflow-free.
        An abandoned stream (downstream Limit stopped consuming) never
        reaches the staging append, so partial page sets cannot be
        published."""
        key, _node_ref, tables, watermark, snap, family = entry
        tr = self.trace
        t0 = tr.now() if tr is not None else 0.0
        host_pages = self.result_cache.get_pages(key)
        label = type(node).__name__
        if host_pages is not None:
            self.result_cache_hits += 1
            # replayed pages still pass the per-query accounting: the
            # memory limit holds whether a page came off the device or
            # out of the cache, and EXPLAIN ANALYZE shows the replay's
            # pages/rows on this node (its subtree honestly shows
            # nothing — nothing executed; the Counters line carries
            # the result_cache_hits that explain why)
            st = None
            if self._collect_stats is not None:
                st = self._collect_stats.setdefault(
                    id(node), NodeStats(label))
            # the first redundant crossing the transfer auditor
            # surfaced (ISSUE 12 satellite): a hit whose pages feed
            # only statement serialization used to device_put every
            # host page and then pull it straight back at decode —
            # the host sink serves the stored pages as-is instead
            # (h2d_bytes == d2h_bytes == 0 on such a replay,
            # counter-pinned in tests/test_result_cache.py)
            serve_host = id(node) in self._host_sink_ids
            for hp in host_pages:
                dp = hp if serve_host else self._stage_replay(hp)
                self._account_page(dp)
                if st is not None:
                    st.pages += 1
                    st.row_counts.append(self._deferred_rows(dp))
                yield dp
            if tr is not None:
                tr.complete("cache", f"hit:{label}", t0, tr.now(),
                            pages=len(host_pages), key=key)
                self.trace_spans += 1
            return
        if family is not None:
            # subsumption rewrite (ISSUE 19): a cached SIBLING whose
            # filter descriptor CONTAINS this one answers by replaying
            # its (wider) pages through this node's own predicate — a
            # residual re-filter over cached pages instead of a rescan
            sib = self.result_cache.probe_family(family[0], family[1])
            wider = (self.result_cache.get_pages(sib[0])
                     if sib is not None else None)
            if wider is not None:
                self.result_cache_hits += 1
                self.cache_subsumed_hits += 1
                self.result_cache.count_subsumed()
                if tr is not None:
                    tr.complete("cache", f"subsume:{label}", t0,
                                tr.now(), key=key, wider=sib[0])
                    self.trace_spans += 1
                # stitch the wider pages UNDER this Filter via the
                # RemoteSource supplier path (the same ingest the
                # exchange plane replays through), then run the node's
                # own predicate over them — the residual filter
                skey = f"subsume:{id(node)}"
                rs = P.RemoteSource(
                    types=tuple(self.output_types(node.source)),
                    key=skey, origin=node.source,
                )
                synthetic = dataclasses.replace(node, source=rs)
                self.remote_sources[skey] = (
                    lambda pages=wider: iter(pages))
                collected: List = []
                try:
                    for page in self.pages(synthetic):
                        collected.append(page)
                        yield page
                finally:
                    self.remote_sources.pop(skey, None)
                # the narrow result publishes under its EXACT key, so
                # the next identical query hits without the rewrite
                self._cache_pending.append(
                    (key, collected, tables, watermark, snap, family))
                return
        self.result_cache_misses += 1
        if tr is not None:
            tr.complete("cache", f"miss:{label}", t0, tr.now(),
                        key=key)
            self.trace_spans += 1
        self._cache_inflight.add(id(node))
        try:
            collected = []
            for page in self.pages(node):
                collected.append(page)
                yield page
        finally:
            self._cache_inflight.discard(id(node))
        self._cache_pending.append(
            (key, collected, tables, watermark, snap, family))

    def _stage_replay(self, page: Page) -> Page:
        """Re-stage one replayed host page for a DEVICE consumer —
        overridable so the DistExecutor can commit replays as
        properly mesh-replicated arrays instead of device-0 pages."""
        return XF.to_device(page, label="cache-replay")

    def _publish_cache_pending(self) -> None:
        """Publish the attempt's completed cache streams — called by
        the drivers exactly once per SUCCESSFUL (overflow-free)
        attempt, which is also where the engine syncs anyway, so the
        store's per-page D2H reads stay off the deferred-sync hot
        path."""
        pending, self._cache_pending = self._cache_pending, []
        cache = self.result_cache
        if cache is None:
            return
        for key, pages, tables, watermark, snap, family in pending:
            self.result_cache_evictions += cache.put_pages(
                key, pages, tables, watermark=watermark,
                snap=snap, family=family,
            )

    def _overflow_flagged(self) -> bool:
        """Whether any of the attempt's deferred overflow flags is
        set — the ONE host sync of the deferred-sync discipline (see
        __init__): the flags are read together in one metered pull
        (no eager program folds them first) and OR-ed on the host."""
        if not self._pending_overflow:
            return False
        flags = XF.to_host(self._pending_overflow,
                           label="overflow-flag")
        return any(bool(np.any(f)) for f in flags)

    def stream_fragment(self, node: P.PhysicalNode, emit,
                        cancelled=lambda: False,
                        on_attempt=None) -> List:
        """Stream a plan fragment's pages through ``emit`` under the
        SAME query-scope overflow ladder as execute() — for drivers
        that ship results incrementally (server/worker.py's task
        runtime) instead of materializing rows. Returns the emit()
        results of the last (overflow-free) attempt; a truncated page
        set can never escape because results publish only per
        completed attempt. ``on_attempt`` (optional) is called at the
        start of EVERY attempt — drivers whose emit writes to
        external, tiered storage (the spooled-exchange buffers) reset
        it there so a boosted retry never double-publishes. Raises
        after 6 boosted retries."""
        self._capacity_boost = 1
        self.capacity_boost_retries = 0
        self.profile_store_hits = 0
        if self.trace is None:
            self.trace_spans = 0
        # profile seeding mirrors execute(): a repeated fragment shape
        # starts at its settled capacity bucket on the worker too
        prof_key = self._seed_profile(node)
        self.device_oom_retries = 0
        self._oom_divisor = 1
        cc_base = CC.snapshot()
        oom_left = self.device_oom_attempts
        # same pre-compile verification as execute(): a shipped
        # fragment is a plan tree too (worker-side task runtime)
        self._verify_plan(node)
        # and the same result-cache point selection: a repeated leaf
        # fragment replays on the worker too (split identity rides in
        # the SplitFilterConnector's snapshot token, so two tasks of
        # one fragment on different shares can never share a key)
        self._select_cache_points(node)
        # transfer plane: fragment pages feed emit() (host
        # serialization) directly, so the fragment root chain is the
        # host-serve sink — a worker-side cache replay never re-stages
        self._reset_transfer_gauges()
        self._host_sink_ids = self._sink_chain_ids(node)
        _prev_sink = XF.swap_sink(self)
        tr = self.trace
        outer_attempt = self._attempt_span
        try:
            attempts = 0
            while attempts < 6:
                self._begin_attempt()
                if on_attempt is not None:
                    on_attempt()
                att_span = None
                if tr is not None:
                    att_span = self._attempt_span = tr.begin(
                        "attempt", f"a{attempts}",
                        boost=self._capacity_boost)
                    self.trace_spans += 1
                try:
                    self._maybe_inject_oom()
                    out: List = []
                    for page in self.pages(node):
                        if cancelled():
                            if tr is not None:
                                tr.end(att_span, outcome="cancelled")
                            return out
                        self._check_deadline()
                        out.append(emit(page))
                except QueryDeadlineExceeded:
                    if tr is not None:
                        tr.end(att_span, outcome="deadline")
                    raise
                except Exception as e:  # noqa: BLE001 - ladder gate
                    # same device-OOM degradation as execute(): retry
                    # under a halved budget so the worker's fragment
                    # degrades to chunked execution instead of failing
                    # the task (the coordinator's long-poll tolerates
                    # the delay)
                    if tr is not None:
                        tr.end(att_span, outcome="device-fault")
                    oom_left = self._absorb_device_fault(e, oom_left)
                    continue
                if not self._overflow_flagged():
                    if tr is not None:
                        tr.end(att_span, outcome="ok", pages=len(out),
                               **self._agg_sizing_attrs())
                    # publication mirrors the emit discipline: only a
                    # completed overflow-free attempt's streams cache
                    self._publish_cache_pending()
                    if prof_key is not None:
                        self._record_profile(prof_key, None,
                                             pages_out=len(out))
                    return out
                # same shared-ladder re-entry as execute(): fragment
                # retries land on rungs the cache already paid for
                if tr is not None:
                    tr.end(att_span, outcome="overflow",
                           **self._agg_sizing_attrs())
                self._capacity_boost = SH.next_boost(self._capacity_boost)
                self.capacity_boost_retries += 1
                attempts += 1
            raise RuntimeError(
                "fragment capacity overflow persisted after 6 boosted "
                "retries"
            )
        finally:
            XF.swap_sink(_prev_sink)
            # close materialized intermediates (incl. disk-tier spill
            # dirs) the moment the fragment is done — never rely on
            # __del__ timing (same discipline as execute())
            self._release_stream_cache()
            self._cache_points = {}
            self._cache_pending = []
            self._snap_compile_counters(cc_base)
            self._attempt_span = outer_attempt

    def _snap_compile_counters(self, base) -> None:
        """Record this query's compile-cost delta (see compilecache.py;
        process-wide counters, so concurrent queries share attribution)."""
        d = CC.delta(base)
        self.programs_compiled = d["programs_compiled"]
        self.program_cache_hits = d["program_cache_hits"]
        self.compile_wall_s = d["compile_wall_s"]

    def _release_stream_cache(self) -> None:
        """Invalidate materialized intermediates, CLOSING each PageStore
        explicitly (disk-tier stores hold presto_tpu_spill_* temp dirs
        whose cleanup must not rely on __del__ timing)."""
        for store in self._stream_cache.values():
            try:
                store.close()
            except Exception:  # noqa: BLE001 - best-effort close; a
                pass           # failed spill-dir sweep must not mask
                # the query's own result/error path
        self._stream_cache = {}
        # a stored join's lookup structures live as long (a retried
        # attempt rebuilds what it still needs)
        self._stored_builds = {}

    def _account_page(self, page: Page) -> None:
        size = page_bytes(page)
        # streaming model: at most a handful of pages per operator are
        # live at once; the high-water proxy is the largest single page
        # times the plan's pipeline depth, tracked coarsely as a running
        # peak of per-page footprints
        self.peak_memory_bytes = max(self.peak_memory_bytes, size)
        if (
            self.max_memory_bytes is not None
            and size > self.max_memory_bytes
        ):
            raise MemoryBudgetExceeded(
                f"page footprint {size} bytes exceeds query memory limit "
                f"{self.max_memory_bytes} (reference: "
                f"ExceededMemoryLimitException)"
            )

    def execute_with_stats(self, node: P.PhysicalNode):
        """EXPLAIN ANALYZE support: run the query collecting per-node
        wall time / page count / output rows. Row counts stay device-side
        during the run and resolve here (one sync at the end)."""
        self._collect_stats = {}
        try:
            names, rows = self.execute(node)
            self._resolve_row_counts()
            stats = dict(self._collect_stats)
        finally:
            self._collect_stats = None
        # query-level execution counters ride under a string key (node
        # entries key by id(node), an int — no collision); PlanPrinter
        # renders them as a trailing Counters line. The gather/fusion
        # counters are per-attempt (reset in _begin_attempt, so they
        # describe the successful attempt); the lifetime-cumulative
        # join counters report as THIS query's delta over the snapshot
        # execute() took.
        base_gen, base_pal = getattr(self, "_joins_counter_base", (0, 0))
        # registry-driven (exec/counters.py): every declared counter
        # surfaces here — and therefore in EXPLAIN ANALYZE text,
        # which renders all keys — with no per-counter hand wiring.
        # The lifetime-cumulative join counters override
        # to THIS query's delta over the snapshot execute() took.
        ctr = CTRS.snapshot(self)
        ctr["generated_joins_used"] = self.generated_joins_used - base_gen
        ctr["pallas_joins_used"] = self.pallas_joins_used - base_pal
        # computed entries (counters.COMPUTED_COUNTERS):
        # splits_per_launch > 1 means the per-split driver loop folded
        # into XLA; peak_device_bytes is the attempt's
        # largest single device buffer (membudget.py); warmed runs
        # report programs_compiled=0 with the wall under compile_wall_s
        ctr["splits_per_launch"] = (
            round(self.splits_scanned / self.program_launches, 1)
            if self.program_launches else 0.0
        )
        ctr["compile_wall_s"] = self.compile_wall_s
        # transfer ledger (ISSUE 12, exec/xfer.py): the float wall of
        # this query's metered host<->device crossings; the byte/count
        # gauges ride in the registry snapshot above
        ctr["transfer_wall_s"] = round(self.transfer_wall_s, 6)
        ctr["peak_device_bytes"] = self.peak_memory_bytes
        ctr["deadline_ms_remaining"] = (
            int((self.query_deadline - time.monotonic()) * 1000)
            if self.query_deadline is not None else -1
        )
        stats["counters"] = ctr
        return names, rows, stats

    # -------------------------------------------------------- aggregation
    def _agg_in_types(self, node: P.Aggregation) -> List[Optional[T.SqlType]]:
        src = self.output_types(node.source)
        return [
            None if s.channel is None else src[s.channel]
            for s in node.aggregates
        ]

    def _partial_origin(self, node: P.Aggregation) -> P.Aggregation:
        """The partial-step aggregation feeding a final-step one (possibly
        through exchanges or a DCN RemoteSource); needed to recover
        original input types."""
        src = node.source
        while isinstance(src, P.Exchange):
            src = src.source
        if isinstance(src, P.RemoteSource) and src.origin is not None:
            src = src.origin
        if not (isinstance(src, P.Aggregation) and src.step == "partial"):
            raise TypeError(
                "final-step aggregation must consume a partial-step one"
            )
        return src

    @property
    def _collect_k_eff(self) -> int:
        """Collect-state slots per group for this attempt: the session
        bound scaled by the overflow-retry boost, so a group exceeding
        array_agg_max_elements lands on the same boosted-retry ladder
        as every other capacity (SURVEY §8.2.1)."""
        return self.collect_k * self._capacity_boost

    def _agg_extra_types(self, node: P.Aggregation):
        """Per-aggregate extra input types (map_agg's value column),
        resolved against the aggregation's source schema."""
        src = self.output_types(node.source)
        return tuple(
            tuple(src[c] for c in spec.extra_channels)
            for spec in node.aggregates
        )

    def _exec_agg_partial(self, node: P.Aggregation) -> Iterator[Page]:
        """Partial step only: one state page per input page (reference:
        AggregationNode.Step.PARTIAL before the exchange). When the
        source is a fusable scan chain, the WHOLE pipeline — generation
        through partial aggregation — compiles to one program per split
        (this is the path shipped-plan worker fragments execute)."""
        in_types = self._agg_in_types(node)
        layouts = [
            S.state_layout(s.function, t)
            for s, t in zip(node.aggregates, in_types)
        ]
        pcap = _next_pow2(node.capacity * self._capacity_boost)
        tail = self._fused_partial_tail(
            node, layouts, pcap, 64 * self._capacity_boost,
        )
        if tail is not None:
            fused = self._fused_stream(
                node.source, agg_tail=tail,
                key_extra=("partial", node.group_channels,
                           node.aggregates, pcap,
                           64 * self._capacity_boost,
                           self._collect_k_eff),
            )
            if fused is not None:
                yield from fused
                return
        if not node.group_channels:
            fn = self._jit(
                ("gagg_partial", node.aggregates,
                 tuple(tuple(l) for l in layouts)),
                functools.partial(
                    _partial_global_agg, node.aggregates,
                    tuple(tuple(l) for l in layouts)
                ),
            )
            for page in self.pages(node.source):
                yield fn(page)
            return
        cap = _next_pow2(node.capacity * self._capacity_boost)
        max_iters = 64 * self._capacity_boost
        fn = self._jit(
            ("agg_partial", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), self._collect_k_eff),
            functools.partial(
                _partial_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts),
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
        )
        for page in self.pages(node.source):
            out, overflow = fn(
                page, min(cap, _next_pow2(page.capacity)), max_iters
            )
            self._pending_overflow.append(overflow)
            yield out

    def _exec_agg_final(self, node: P.Aggregation) -> Iterator[Page]:
        """Final step: merge partial-state pages after an exchange."""
        origin = self._partial_origin(node)
        in_types = self._agg_in_types(origin)
        layouts = [
            S.state_layout(s.function, t)
            for s, t in zip(node.aggregates, in_types)
        ]
        pages = list(self.pages(node.source))
        if not node.group_channels:
            merged = (
                concat_all(pages) if pages
                else _empty_state_page(node.aggregates, layouts,
                                      collect_k=self._collect_k_eff)
            )
            fn = self._jit(
                ("gagg_final", node.aggregates,
                 tuple(tuple(l) for l in layouts), tuple(in_types)),
                functools.partial(
                    _final_global_agg, node.aggregates,
                    tuple(tuple(l) for l in layouts), tuple(in_types)
                ),
            )
            yield fn(merged)
            return
        if not pages:
            return
        merged = concat_all(pages) if len(pages) > 1 else pages[0]
        fn = self._jit(
            ("agg_final", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), tuple(in_types),
             self._agg_extra_types(origin), self._collect_k_eff),
            functools.partial(
                _final_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts), tuple(in_types),
                collect_k=self._collect_k_eff,
                extra_types=self._agg_extra_types(origin),
            ),
            static_argnums=(1, 2),
        )
        fcap = min(
            _next_pow2(node.capacity * self._capacity_boost),
            _next_pow2(merged.capacity),
        )
        out, overflow = fn(merged, fcap, 64 * self._capacity_boost)
        self._pending_overflow.append(overflow)
        yield out

    # ------------------------------------------------ IVM kernel plane
    def ivm_delta_states(self, partial_node: P.Aggregation) -> List:
        """Run a view's partial-step aggregation over the delta
        window (the executor's catalogs hold the pinned
        StreamWindowConnector) and return HOST copies of its
        partial-state pages — the O(new rows) half of an incremental
        view refresh (streaming/ivm.py). Rides stream_fragment's
        overflow ladder, the fused scan→partial-agg path where the
        chain fuses, and the same canonical jit-cache entries a cold
        single-step run compiles."""
        return self.stream_fragment(
            partial_node,
            emit=lambda p: XF.to_host(p, label="ivm-delta"),
        )

    def ivm_fold_finalize(self, node: P.Aggregation, state_pages,
                          cap_hint: Optional[int] = None):
        """Merge partial-state pages (host pytrees: the persisted
        settled state plus this refresh's delta states) into ONE
        settled partial state and finalize it — the other half of an
        IVM refresh. Reuses the exact agg_merge / agg_final kernels
        (and canonical jit keys) the single-step aggregation path
        compiles, under a local boost ladder: a state overflow re-
        stages and retries at the next shapes.py rung, same escape as
        every other capacity decision. Returns
        ``(settled_host_state_page, final_host_page)`` — the settled
        state is pulled to host BEFORE finalization because the
        final-step program donates its input buffer on TPU.

        ``cap_hint`` (the view's OBSERVED group cardinality from its
        last finalize) sizes the settled state tightly: the planner's
        capacity estimate derives from the LOG's row count and would
        pin an ever-growing state page to O(log) slots — the refresh
        must stay O(delta) + O(groups), so the state compacts to the
        observed cardinality and true growth overflows onto the boost
        ladder like every other capacity decision."""
        if not state_pages:
            raise ValueError("ivm_fold_finalize needs >=1 state page")
        in_types = self._agg_in_types(node)
        layouts = [
            S.state_layout(s.function, t)
            for s, t in zip(node.aggregates, in_types)
        ]
        layouts_t = tuple(tuple(l) for l in layouts)
        nkeys = len(node.group_channels)
        boost = 1
        for _ in range(6):
            max_iters = 64 * boost
            collect_k = self.collect_k * boost
            merge_fn = self._jit(
                ("agg_merge", node.aggregates, layouts_t, nkeys,
                 collect_k),
                functools.partial(
                    _merge_partials_page, node.aggregates, layouts_t,
                    nkeys, collect_k=collect_k,
                ),
                static_argnums=(1, 2),
                donate_argnums=(0,),
            )
            final_fn = self._jit(
                ("agg_final", node.group_channels, node.aggregates,
                 layouts_t, tuple(in_types),
                 self._agg_extra_types(node), collect_k),
                functools.partial(
                    _final_agg_page, node.group_channels,
                    node.aggregates, layouts_t, tuple(in_types),
                    collect_k=collect_k,
                    extra_types=self._agg_extra_types(node),
                ),
                static_argnums=(1, 2),
                donate_argnums=(0,),
            )
            # re-stage per attempt: the merge program donates its
            # concat input, so a boosted retry must rebuild it
            staged = [XF.to_device(p, label="ivm-state")
                      for p in state_pages]
            merged = (concat_all(staged) if len(staged) > 1
                      else staged[0])
            self._account_page(merged)
            base = (cap_hint if cap_hint else node.capacity)
            cap = _next_pow2(max(base, 8) * boost)
            mcap = min(cap, _next_pow2(merged.capacity))
            settled, ovf = merge_fn(merged, mcap, max_iters)
            if bool(ovf):
                boost = SH.next_boost(boost)
                continue
            # host copy FIRST: final_fn donates the settled buffer
            settled_host = XF.to_host(settled, label="ivm-state")
            fcap = min(cap, _next_pow2(settled.capacity))
            final, ovf = final_fn(settled, fcap, max_iters)
            if bool(ovf):
                boost = SH.next_boost(boost)
                continue
            return settled_host, XF.to_host(final, label="ivm-final")
        raise RuntimeError(
            "IVM state fold overflow persisted after 6 boosted retries"
        )

    def _exec_aggregation(self, node: P.Aggregation) -> Iterator[Page]:
        if node.step == "partial":
            yield from self._exec_agg_partial(node)
            return
        if node.step == "final":
            yield from self._exec_agg_final(node)
            return
        in_types = self._agg_in_types(node)
        layouts = [
            S.state_layout(s.function, t)
            for s, t in zip(node.aggregates, in_types)
        ]
        if not node.group_channels:
            yield self._exec_global_agg(node, in_types, layouts)
            return

        sizing = self._agg_sizing(node)
        self._agg_sizings.append(sizing)
        if sizing.governed:
            self.memory_chunked_pipelines += 1
        if sizing.parts > 1:
            yield from self._exec_agg_partitioned(
                node, sizing.parts, in_types, layouts
            )
            return
        cap = sizing.cap
        partial_fn = self._jit(
            ("agg_partial", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), self._collect_k_eff),
            functools.partial(
                _partial_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts),
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
        )
        # boosted retries also deepen the hash-probe iteration budget:
        # when cap is already clipped at the page capacity the only
        # remaining overflow source is unresolved probing after max_iters
        # lockstep rounds, which more capacity alone cannot fix
        max_iters = 64 * self._capacity_boost
        # Incremental fold: buffered partial pages merge into one
        # bounded state page instead of one giant concat — a 6-page
        # pipeline with a 2M capacity estimate otherwise concats 6M+
        # slots and crosses the >=4M-row fault line (and wastes
        # memory even where it doesn't fault). fold_cap deliberately
        # undersizes vs the planner estimate; true high-cardinality
        # group-bys overflow onto the boosted-retry ladder (and, when
        # spill is on, onto partitioned passes).
        fold_cap = min(cap, _next_pow2((1 << 20) * self._capacity_boost))
        fr = self._fault_rows()
        if fr and self._keys_partitionable(
                self.output_types(node.source), node.group_channels):
            # governed: acc + flush batch + one page stays under the
            # device fault line even at full boost — safe to PIN only
            # because true high-cardinality states have an escape (the
            # boost-scaled partitioned path above). Non-partitionable
            # keys (strings) have no such rewrite: they keep the
            # legacy boost-growing cap, same exposure as before the
            # governor, rather than a pin that can never converge
            fold_cap = min(fold_cap, max(fr >> 2, 8192))
        merge_fn = self._jit(
            ("agg_merge", node.aggregates,
             tuple(tuple(l) for l in layouts),
             len(node.group_channels), self._collect_k_eff),
            functools.partial(
                _merge_partials_page, node.aggregates,
                tuple(tuple(l) for l in layouts),
                len(node.group_channels),
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
            # the fold accumulator concat is dead after the merge —
            # donation reuses its HBM for the merged state in place
            donate_argnums=(0,),
        )
        fold = _FoldBuffer(self, merge_fn, fold_cap, max_iters,
                           2 * fold_cap)
        # scan→filter→project→partial-agg as ONE program per split when
        # the source chain fuses (the fused stream's state pages feed
        # the same fold/final machinery)
        tail = self._fused_partial_tail(node, layouts, cap, max_iters)
        fused = (
            self._fused_stream(
                node.source, agg_tail=tail,
                key_extra=("single", node.group_channels,
                           node.aggregates, cap, max_iters,
                           self._collect_k_eff),
            )
            if tail is not None and node.group_channels else None
        )
        if fused is not None:
            for out in fused:
                fold.add(out)
        else:
            for page in self._agg_source_pages(node):
                # distinct groups <= rows: clip the capacity to the page
                out, overflow = partial_fn(
                    page, min(cap, _next_pow2(page.capacity)), max_iters
                )
                self._pending_overflow.append(overflow)
                fold.add(out)
        merged = fold.final_merged()
        if merged is None:
            return
        final_fn = self._jit(
            ("agg_final", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), tuple(in_types),
             self._agg_extra_types(node), self._collect_k_eff),
            functools.partial(
                _final_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts), tuple(in_types),
                collect_k=self._collect_k_eff,
                extra_types=self._agg_extra_types(node),
            ),
            static_argnums=(1, 2),
            # the fold's settled state page dies at the final merge —
            # the fold chain and the finisher share one HBM allocation
            donate_argnums=(0,),
        )
        fcap = min(
            _next_pow2(node.capacity * self._capacity_boost),
            _next_pow2(merged.capacity),
        )
        out, overflow = final_fn(merged, fcap, max_iters)
        self._pending_overflow.append(overflow)
        yield out

    def _agg_sizing(self, node: P.Aggregation) -> AggSizing:
        """First-attempt sizing of a blocking grouped aggregation: THE
        one rule behind the partition decision, the compaction buffer
        and the single path's group capacity, shared verbatim by the
        static audit (membudget.audit) so prediction and execution
        cannot drift.

        The planner's capacity has no selectivity model and routinely
        over-estimates 100x (Q3 SF1: a 4M-slot bound for 11k groups out
        of 30k joined rows), and every sort, scatter and partition pass
        of the grouped path is paid per SLOT. So the first attempt
        (boost 1) sizes all three from what it assumes it will see —
        min(planner capacity, agg_optimistic_rows) — and a statement
        with more groups or valid rows than that flags overflow, pays
        one cheap failed attempt, and re-enters boosted. A boosted
        attempt is evidence the optimistic size was wrong: it sizes
        from the planner's boost-scaled bounds again, where the
        partitioned paths are the escape for a state the governed fold
        (pinned under the fault line) can never hold."""
        boost = self._capacity_boost
        src_types = self.output_types(node.source)
        keys = node.group_channels
        cap = _next_pow2(node.capacity * boost)
        sized_by = "estimate" if boost == 1 else "boost"
        opt = self.agg_optimistic_rows
        if opt and _next_pow2(opt * boost) < cap:
            cap = _next_pow2(opt * boost)
            if boost == 1:
                sized_by = "optimistic"
        budget = self._budget()
        fr = self._fault_rows()

        parts = 1
        if self._keys_partitionable(src_types, keys):
            est_rows = self.estimate_rows(node.source)
            if boost == 1:
                # the fold (or the compaction buffer) holds the single
                # path's state to cap however many pages feed it
                merged_slots = min(est_rows, cap)
            else:
                # unmerged partial pages of cap_est slots each (the
                # planner's bound, boost-scaled); never under cap_est,
                # or an under-estimated est_rows lets the ladder climb
                # forever without the escape engaging
                cap_est = _next_pow2(max(node.capacity, 8) * boost)
                n_pages = max(-(-est_rows // max(self.page_rows, 1)), 1)
                merged_slots = max(
                    min(est_rows, n_pages * cap_est), cap_est)
            state_types = [src_types[c] for c in keys]
            for spec, in_t in zip(node.aggregates,
                                  self._agg_in_types(node)):
                state_types.extend(
                    st.type for st in S.state_layout(spec.function, in_t)
                )
            state_row_b = _row_bytes(state_types)
            if self.spill_bytes is not None:
                parts = self._spill_partitions(merged_slots * state_row_b)
                if parts > 1:
                    sized_by = "spill_bytes"
            # governed (membudget.py): the state must fit its budget
            # share and the single path's governed FOLD cap (fr >> 2),
            # not the raw fault line — a state the fold can never hold
            # must partition, or boosted retries would never converge
            rows_cap = max(fr >> 2, 8192) if fr else None
            bytes_cap = budget // MB.BUILD_SHARE_DIV if budget else None
            gparts = SH.parts_for(merged_slots, state_row_b,
                                  rows_cap=rows_cap, bytes_cap=bytes_cap)
            if gparts > parts:
                by_rows = SH.parts_for(merged_slots, state_row_b,
                                       rows_cap=rows_cap, bytes_cap=None)
                sized_by = "rows_cap" if by_rows == gparts else "bytes_cap"
                parts = gparts

        # compaction pays where grouping cost is slot-proportional: the
        # packed-argsort path _group_ids takes above the matmul limit
        # for keys that are not all dictionary- or boolean-coded. Dense
        # ids (Q5's n_name) cost near nothing per sparse page, and a
        # compacting argsort over the page would cost more than it
        # saves. An accumulator past the governed buffer ceiling (2M
        # slots on the chip) or its budget share is not built: the
        # partitioned and plain paths take dense streams.
        compact_rows = 0
        if (self.agg_compact and cap > A.MATMUL_AGG_MAX_GROUPS
                and not all(T.is_string(src_types[c])
                            or isinstance(src_types[c], T.BooleanType)
                            for c in keys)
                and _subtree_has_join(node.source)):
            basis = cap if boost == 1 else _next_pow2(
                max(node.capacity, 8))
            rows = _next_pow2(
                max(opt or (1 << 18), basis, 8192) * boost)
            ceiling = MB.rows_cap(
                _row_bytes(src_types), budget,
                fr or SH.SAFE_BUFFER_ROWS, MB.BUILD_SHARE_DIV)
            if rows <= ceiling:
                compact_rows = rows
        return AggSizing(cap, compact_rows, parts, sized_by)

    def _agg_source_pages(self, node: P.Aggregation) -> Iterator[Page]:
        """Aggregation input stream, densified through a rolling
        compaction buffer where _agg_sizing asks for one: join output
        pages keep probe capacity but are usually mostly-invalid
        (build filters + match rate), and every sort/scatter in the
        blocking aggregation scales with SLOT count, not valid rows.
        Each input page merge-compacts into one accumulator page (a
        stable argsort + output-sized gathers — cheap), so the
        aggregation usually runs ONCE over one dense page instead of
        once per sparse page plus merges. Rows beyond the accumulator
        flag overflow and ride the boosted-retry ladder (reference
        analog: every Presto operator re-compacts via PageBuilder —
        pages are always dense there)."""
        C = self._agg_sizing(node).compact_rows
        if not C:
            yield from self.pages(node.source)
            return
        first, merge = self._stream_compact_fns(node, C)
        acc = None
        for page in self.pages(node.source):
            if acc is None or page.capacity > C:
                # a page wider than the accumulator compacts alone
                # first: the merge's concat then never passes 2C slots
                # (C <= 2M keeps it under the fault line), and both
                # argsorts stay as small as their inputs allow
                page, overflow = first(page)
                self._pending_overflow.append(overflow)
            if acc is None:
                acc = page
                continue
            acc, overflow = merge(acc, page)
            self._pending_overflow.append(overflow)
        if acc is not None:
            yield acc

    def _stream_compact_fns(self, node: P.Aggregation, C: int):
        """The two kernels of _agg_source_pages' rolling buffer with
        its capacity bound in: ``first(page)`` and ``merge(acc, page)``
        each return the dense page and its dropped-rows flag. How they
        are made is the one thing an executor over a mesh overrides.
        Bare kernels: ONE canonical entry each serves every stream."""
        first = self._jit(
            ("stream_compact1",), _compact_with_flag,
            static_argnums=(1,),
        )
        merge = self._jit(
            ("stream_compact2",), _merge_compact_flag,
            static_argnums=(2,),
        )
        return (lambda page: first(page, C),
                lambda acc, page: merge(acc, page, C))

    def _exec_agg_partitioned(
        self, node: P.Aggregation, parts: int, in_types, layouts
    ) -> Iterator[Page]:
        """Partition-wise grouped aggregation (spill analog): group-key
        hash partitions keep per-partition state ~1/P of the one-shot
        size; partitions are disjoint so the union of outputs is exact.
        Two strategies (reference: SpillableHashAggregationBuilder's
        partition-and-merge):
          - parts <= 32: SINGLE source pass, P device-resident
            accumulators folded incrementally — the source (often an
            expensive join) executes once and every buffer stays small;
          - larger P: one pass per partition re-streaming the source
            (recomputation instead of spill files — generator scans are
            free, SURVEY §8.2.6) with O(1/P) working set."""
        if parts <= 32:
            # budget check: the fold path keeps ~3 buffers per partition
            # resident (~6x the capacity estimate in state rows); under
            # an explicit query memory budget that exceeds the point of
            # spilling — fall through to the O(1/P) multi-pass instead
            cap_est = _next_pow2(node.capacity * self._capacity_boost)
            pcap_est = _next_pow2(max(cap_est // parts * 2, 1024))
            src_types = self.output_types(node.source)
            state_types = [src_types[c] for c in node.group_channels]
            for layout in layouts:
                state_types.extend(st.type for st in layout)
            resident = 3 * parts * pcap_est * _row_bytes(state_types)
            if (
                self.max_memory_bytes is None
                or resident <= self.max_memory_bytes
            ):
                yield from self._exec_agg_partition_fold(
                    node, parts, in_types, layouts
                )
                return
        self.spill_partitions_used = max(self.spill_partitions_used, parts)
        pfilter = self._partition_filter(node.group_channels, parts)
        cap = _next_pow2(node.capacity * self._capacity_boost)
        pcap = SH.chunk_bucket(cap, parts)
        max_iters = 64 * self._capacity_boost
        partial_fn = self._jit(
            ("agg_partial", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), self._collect_k_eff),
            functools.partial(
                _partial_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts),
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
        )
        final_fn = self._jit(
            ("agg_final", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), tuple(in_types),
             self._agg_extra_types(node), self._collect_k_eff),
            functools.partial(
                _final_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts), tuple(in_types),
                collect_k=self._collect_k_eff,
                extra_types=self._agg_extra_types(node),
            ),
            static_argnums=(1, 2),
            donate_argnums=(0,),  # per-pass fold state dies here
        )
        nkeys = len(node.group_channels)
        merge_fn = self._jit(
            ("agg_merge", node.aggregates,
             tuple(tuple(l) for l in layouts),
             len(node.group_channels), self._collect_k_eff),
            functools.partial(
                _merge_partials_page, node.aggregates,
                tuple(tuple(l) for l in layouts), nkeys,
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
            donate_argnums=(0,),  # fold concat dead after the merge
        )
        src_stream = self._source_stream(node.source)
        for p in range(parts):
            pj = jnp.uint64(p)
            # incremental fold: buffered partial pages merge into one
            # pcap-sized state page whenever they pile up, so per-pass
            # memory is O(pcap), not O(pages x pcap)
            fold = _FoldBuffer(self, merge_fn, pcap, max_iters, 4 * pcap)
            for page in src_stream():
                f = pfilter(page, pj)
                out, overflow = partial_fn(
                    f, min(pcap, _next_pow2(page.capacity)), max_iters
                )
                self._pending_overflow.append(overflow)
                fold.add(out)
            if not fold.saw_input:
                return
            merged = fold.final_merged()
            fcap = min(pcap, _next_pow2(merged.capacity))
            out, overflow = final_fn(merged, fcap, max_iters)
            self._pending_overflow.append(overflow)
            yield out

    def _exec_agg_partition_fold(
        self, node: P.Aggregation, parts: int, in_types, layouts
    ) -> Iterator[Page]:
        """Single-pass partitioned aggregation: every source page is
        partial-aggregated, split into P partitions by group-key hash
        over the PARTIAL page's key channels, compacted, and folded into
        per-partition accumulators. Memory is O(P * pcap) and every
        individual buffer stays ~3*pcap — small enough for the
        >=4M-row fault line — while the source streams exactly once
        (crucial when it is a join pipeline, not a free generator
        re-scan)."""
        self.spill_partitions_used = max(self.spill_partitions_used, parts)
        nkeys = len(node.group_channels)
        # partial output pages carry the keys at channels 0..nkeys-1
        pfilter = self._partition_filter(tuple(range(nkeys)), parts)
        cap = _next_pow2(node.capacity * self._capacity_boost)
        pcap = SH.chunk_bucket(cap, parts)
        max_iters = 64 * self._capacity_boost
        partial_fn = self._jit(
            ("agg_partial", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), self._collect_k_eff),
            functools.partial(
                _partial_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts),
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
        )
        merge_fn = self._jit(
            ("agg_merge", node.aggregates,
             tuple(tuple(l) for l in layouts),
             len(node.group_channels), self._collect_k_eff),
            functools.partial(
                _merge_partials_page, node.aggregates,
                tuple(tuple(l) for l in layouts), nkeys,
                collect_k=self._collect_k_eff,
            ),
            static_argnums=(1, 2),
            donate_argnums=(0,),  # fold concat dead after the merge
        )
        final_fn = self._jit(
            ("agg_final", node.group_channels, node.aggregates,
             tuple(tuple(l) for l in layouts), tuple(in_types),
             self._agg_extra_types(node), self._collect_k_eff),
            functools.partial(
                _final_agg_page, node.group_channels, node.aggregates,
                tuple(tuple(l) for l in layouts), tuple(in_types),
                collect_k=self._collect_k_eff,
                extra_types=self._agg_extra_types(node),
            ),
            static_argnums=(1, 2),
            donate_argnums=(0,),  # per-partition fold state dies here
        )

        folds = [
            _FoldBuffer(self, merge_fn, pcap, max_iters, 2 * pcap)
            for _ in range(parts)
        ]
        for page in self._agg_source_pages(node):
            out, overflow = partial_fn(
                page, min(cap, _next_pow2(page.capacity)), max_iters
            )
            self._pending_overflow.append(overflow)
            piece_cap = min(
                _next_pow2(
                    max(out.capacity // parts * 2, 256)
                    * self._capacity_boost
                ),
                _next_pow2(out.capacity),
            )
            for p in range(parts):
                f = pfilter(out, jnp.uint64(p))
                self._pending_overflow.append(f.num_rows() > piece_cap)
                folds[p].add(compact_page(f, piece_cap))
        for fold in folds:
            merged = fold.final_merged()
            if merged is None:
                continue
            fcap = min(pcap, _next_pow2(merged.capacity))
            out, overflow = final_fn(merged, fcap, max_iters)
            self._pending_overflow.append(overflow)
            yield out

    def _exec_global_agg(self, node, in_types, layouts) -> Page:
        partial_fn = self._jit(
            ("gagg_partial", node.aggregates,
             tuple(tuple(l) for l in layouts)),
            functools.partial(
                _partial_global_agg, node.aggregates,
                tuple(tuple(l) for l in layouts)
            ),
        )
        tail = self._fused_partial_tail(node, layouts, None, None)
        fused = (
            self._fused_stream(
                node.source, agg_tail=tail,
                key_extra=("global", node.aggregates,
                           tuple(tuple(l) for l in layouts)))
            if tail is not None else None
        )
        if fused is not None:
            partials = list(fused)
        else:
            partials = [partial_fn(p) for p in self.pages(node.source)]
        if not partials:
            partials = [
                _empty_state_page(node.aggregates, layouts,
                                      collect_k=self._collect_k_eff)
            ]
        merged = _concat_states(partials)
        final_fn = self._jit(
            ("gagg_final", node.aggregates,
             tuple(tuple(l) for l in layouts), tuple(in_types)),
            functools.partial(
                _final_global_agg, node.aggregates,
                tuple(tuple(l) for l in layouts), tuple(in_types)
            ),
        )
        return final_fn(merged)

    # ------------------------------------------------- spill / partitions
    def estimate_rows(self, node: P.PhysicalNode) -> int:
        """Static (host-only) row-count upper estimate for spill planning
        (reference analog: the stats AddExchanges consults; ours derives
        from connector row counts — no selectivity model, conservative)."""
        if isinstance(node, P.TableScan):
            return self.catalogs[node.catalog].row_count(node.table)
        if isinstance(node, P.Values):
            return len(node.rows)
        if isinstance(node, P.Limit):
            return min(node.count + node.offset,
                       self.estimate_rows(node.source))
        if isinstance(node, P.TopN):
            return min(node.limit, self.estimate_rows(node.source))
        if isinstance(node, P.Aggregation):
            if not node.group_channels:
                return 1
            return min(node.capacity, self.estimate_rows(node.source))
        if isinstance(node, P.HashJoin):
            left = self.estimate_rows(node.left)
            if node.join_type in ("semi", "anti"):
                return left
            return max(left, self.estimate_rows(node.right))
        if isinstance(node, P.CrossJoin):
            return self.estimate_rows(node.left) * max(
                self.estimate_rows(node.right), 1
            )
        if isinstance(node, P.Union):
            return sum(self.estimate_rows(s) for s in node.sources)
        if isinstance(node, P.GroupId):
            return self.estimate_rows(node.source) * len(node.set_masks)
        if isinstance(node, P.Unnest):
            # expansion factor unknown statically; modest heuristic
            return self.estimate_rows(node.source) * 4
        if isinstance(node, P.RemoteSource):
            # adaptive execution (ISSUE 15): an OBSERVED exchange row
            # count stamped by the stage-boundary re-planner beats any
            # static estimate — downstream grace partitioning and
            # governor shares then size from measured cardinality
            if node.est_rows is not None:
                return max(int(node.est_rows), 1)
            # fragment edge: estimate from the producer's root when it
            # rides along (origin) — a conservative over-estimate (the
            # FULL producer output; a repartition consumer sees ~1/N),
            # which sizes non-leaf join builds sensibly instead of
            # starting every stage-DAG buffer at the 1-row floor
            if node.origin is not None:
                return self.estimate_rows(node.origin)
            return 1
        kids = node.children()
        return self.estimate_rows(kids[0]) if kids else 1

    def _partition_filter(self, keys: Tuple[int, ...], parts: int,
                          keep_nulls: bool = False):
        """Jitted page transform keeping only rows whose key hash lands in
        partition p (p is traced: one compile serves every pass).

        Partitioning uses the HIGH hash bits: the group-by/join hash
        tables bucket on the low bits (h & (cap-1), ops/agg.py), and
        parts is a power of two — low-bit partitioning would fix those
        bits and cluster every pass's keys into cap/parts slots,
        inflating probe chains ~parts-fold.

        keep_nulls=True routes null-key rows into EVERY pass: semi/anti
        joins need the global "build side contains NULL" fact per pass
        for NOT IN three-valued logic (a null build row otherwise lands
        in exactly one partition and the other passes wrongly emit
        unmatched probe rows as definite non-matches)."""

        def fn(page: Page, p):
            blocks = [page.block(c) for c in keys]
            cols, nulls = K.block_key_columns(blocks)
            h = H.hash_columns(cols, nulls)
            keep = ((h >> jnp.uint64(32)) % jnp.uint64(parts)) == p
            if keep_nulls:
                any_null = jnp.zeros(page.valid.shape, dtype=jnp.bool_)
                for b in blocks:
                    if b.nulls is not None:
                        any_null = any_null | b.nulls
                keep = keep | any_null
            return Page(blocks=page.blocks, valid=page.valid & keep)

        return self._jit(("partfilter", keys, parts, keep_nulls), fn)

    def _spill_partitions(self, est_bytes: int) -> int:
        if self.spill_bytes is None or est_bytes <= self.spill_bytes:
            return 1
        return min(_next_pow2(-(-est_bytes // self.spill_bytes)), 256)

    def _keys_partitionable(self, types, keys) -> bool:
        """Partition hashing is value-consistent only for non-dictionary
        columns (dictionary codes are page-local); string keys disable
        partitioned mode for the operator."""
        return not any(T.is_string(types[c]) for c in keys)

    def _cheap_to_recompute(self, node: P.PhysicalNode) -> bool:
        """Whether re-executing this subtree per pass is acceptable:
        pure scan pipelines recompute pages from row indices (generator
        connectors, SURVEY §8.2.6) or restage from the connector's own
        host store — no join/agg/sort work is repeated."""
        if isinstance(node, (P.TableScan, P.Values)):
            return True
        if isinstance(
            node, (P.Filter, P.Project, P.Exchange, P.Limit, P.Output)
        ):
            return self._cheap_to_recompute(node.source)
        if isinstance(node, P.Union):
            return all(self._cheap_to_recompute(s) for s in node.sources)
        return False

    def _source_stream(self, node: P.PhysicalNode):
        """A callable yielding a fresh page stream for node, for
        operators that consume a source MULTIPLE times (partitioned
        passes). Expensive subtrees materialize once into a PageStore
        (device page list, or host RAM above host_spill_bytes) and
        restream from it — the fix for partitioned passes compounding
        recomputation down a join/agg pipeline (reference: PagesIndex /
        FileSingleStreamSpiller; SURVEY §6.4)."""
        if self._cheap_to_recompute(node):
            return lambda: self.pages(node)
        from presto_tpu.exec.pagestore import PageStore

        # keyed by the (frozen, hashable) plan node itself: identical
        # subtrees share one materialization, and a key can never alias
        # a different plan the way a recycled id() could
        key = node
        if key not in self._stream_cache:
            # NOTE: estimate_rows is a heuristic, not an upper bound —
            # a many-to-many join can exceed max(left, right); a wrong
            # device-tier pick costs HBM headroom, never correctness
            est = self.estimate_rows(node) * _row_bytes(
                self.output_types(node)
            )
            budget = self._budget()
            store_share = (
                budget // MB.STORE_SHARE_DIV if budget else None
            )
            if (self.disk_spill_bytes is not None
                    and est > self.disk_spill_bytes):
                tier = "disk"
            elif (self.host_spill_bytes is not None
                    and est > self.host_spill_bytes):
                tier = "host"
            elif store_share is not None and est > store_share:
                # governed overflow home (membudget.py): an
                # intermediate that cannot stay HBM-resident under the
                # budget stages to host RAM — and past several budgets'
                # worth, to the pagestore disk tier — even when no
                # explicit spill threshold was configured
                tier = (
                    "disk"
                    if est > max(budget * 4, MB.CPU_BUDGET) else "host"
                )
                self.memory_chunked_pipelines += 1
            else:
                tier = "device"
            store = PageStore(tier, spill_dir=self.spill_path)
            for page in self.pages(node):
                store.put(page)
            if tier == "host":
                self.host_spill_pages += store.page_count
                self.host_spill_bytes_used += store.bytes
            elif tier == "disk":
                self.disk_spill_pages += store.page_count
            self._stream_cache[key] = store
        return self._stream_cache[key].stream

    # --------------------------------------------------------------- join
    def _generated_join_info(self, node: P.HashJoin, left_types):
        """Eligibility for the build-free GENERATED join: the build
        subtree is a Filter/Project/Exchange chain over a TableScan of a
        connector that can (a) invert the join-key column in closed form
        (Connector.key_inverse) and (b) generate its columns at
        arbitrary row indices (Connector.gen_at). Then probe keys map to
        build TABLE rows arithmetically and the carried columns are
        GENERATED at those rows — the join holds zero device state: no
        hash table, no searchsorted, no HBM gathers, no capacity
        overflow, no partitioning at any scale factor.

        This is the TPU-native collapse of the reference's
        HashBuilderOperator + LookupJoinOperator for deterministic
        generator tables ("scan == generate", SURVEY §8.2.6, taken to
        its logical end: "lookup == generate")."""
        if not self.generated_join:
            return None
        if node.join_type not in ("inner", "left"):
            return None
        walked = self._scan_chain(node.right, through_joins=False)
        if walked is None:
            return None
        cur, chain = walked

        if not all(_plain_int(left_types[c]) for c in node.left_keys):
            return None
        from presto_tpu.expr.ir import InputRef

        def resolve(ch: int) -> Optional[int]:
            # build-root channel -> scan channel through the projects
            for nd in chain:
                if isinstance(nd, P.Project):
                    e = nd.exprs[ch]
                    if not isinstance(e, InputRef):
                        return None
                    ch = e.channel
            return ch

        conn = self.catalogs[cur.catalog]
        n_rows = conn.row_count(cur.table)
        gen = conn.gen_at(cur.table, cur.columns)
        if gen is None or n_rows <= 0:
            return None
        # ONE key must invert in closed form; the remaining key pairs
        # become equality checks against the generated build columns
        inv, pivot, window, gen_keys = None, None, 1, None
        for j, rk in enumerate(node.right_keys):
            sc = resolve(rk)
            if sc is None:
                continue
            inv = conn.key_inverse(cur.table, cur.columns[sc])
            if inv is not None:
                pivot = j
                break
        if inv is None and self._capacity_boost == 1:
            # windowed inverse (slot-structured fact tables): the pivot
            # key pins an L-slot candidate window; the OTHER keys must
            # resolve to scan columns so the kernel can generate them
            # per candidate and pick the unique full-key match. A probe
            # row matching >1 candidates (key set not unique in data)
            # raises the deferred flag and the boosted retry takes the
            # general join — windowed is ineligible at boost > 1.
            for j, rk in enumerate(node.right_keys):
                sc = resolve(rk)
                if sc is None:
                    continue
                wi = conn.key_window_inverse(cur.table, cur.columns[sc])
                if wi is None:
                    continue
                extra_sc = [
                    resolve(rkk)
                    for jj, rkk in enumerate(node.right_keys) if jj != j
                ]
                if not extra_sc or any(s is None for s in extra_sc):
                    # no extra keys to pin the line (near-certain
                    # multi-match), or unresolvable ones
                    continue
                inv, window = wi
                pivot = j
                gen_keys = conn.gen_at(
                    cur.table, tuple(cur.columns[s] for s in extra_sc)
                )
                break
        if inv is None or (window > 1 and gen_keys is None):
            return None
        extra_pairs = tuple(
            (lk, rk)
            for j, (lk, rk) in enumerate(
                zip(node.left_keys, node.right_keys))
            if j != pivot
        )
        schema = conn.table_schema(cur.table)
        scan_types = tuple(schema.column_type(c) for c in cur.columns)
        dicts = getattr(conn, "_dicts", {}).get(cur.table, {})
        scan_dicts = tuple(dicts.get(c) for c in cur.columns)
        # replay the chain top-down over generated pages (bottom-up in
        # plan order = reversed walk order)
        chain_fns = [
            fn for fn in (
                _node_replay_fn(nd) for nd in reversed(chain)
            ) if fn is not None
        ]
        return (node.left_keys[pivot], extra_pairs, inv, window,
                gen_keys, gen, scan_types, scan_dicts,
                tuple(chain_fns), n_rows)

    @staticmethod
    def generated_join_kernel(node: P.HashJoin, info):
        """The ONE place the _generated_join_info tuple meets the
        kernels: returns (page_fn, windowed). Plain mode: page -> page.
        Windowed: page -> (page, multi_flag) — the caller must defer
        multi_flag to the overflow ladder. Shared by the local executor,
        the dist executor's shard_map wrapping, and the fused-pipeline
        builder."""
        (pivot_ch, extra_pairs, inv, window, gen_keys, gen,
         scan_types, scan_dicts, chain_fns, n_rows) = info
        if window == 1:
            return functools.partial(
                _generated_join_page, pivot_ch, extra_pairs,
                node.join_type, inv, gen, scan_types, scan_dicts,
                chain_fns, n_rows,
            ), False
        return functools.partial(
            _generated_join_window_page, pivot_ch, extra_pairs,
            node.join_type, inv, window, gen_keys, gen, scan_types,
            scan_dicts, chain_fns, n_rows,
        ), True

    def _stored_join_info(self, node: P.HashJoin, left_types
                          ) -> Optional[StoredJoin]:
        """Eligibility for a join whose build side is a STORED table
        (connectors/cached.py: what is stored is looked up, never
        generated) probed as a step of the fused scan: an inner or
        left equi-join whose build subtree is a Filter / Project /
        Exchange chain over a scan of a table its catalog stores, on
        plain integer keys of which one scans a column the connector
        declares unique. That key is looked up in a direct-address
        table over the stored keys (_stored_build_page; the other key
        pairs are equality checks on the row found), so a probe row
        matches at most one build row and the output is the probe page
        extended in place. The table has STORED_JOIN_KEY_SPREAD
        entries a stored slot; a key set wider than that, or one that
        is not unique after all, raises the build's deferred flag, and
        the boosted retry is not eligible: it takes the materialized
        join (_exec_join), as does a build whose structure outgrows
        the governor's build share (_join_parts' share), which it
        partitions."""
        if self._capacity_boost > 1 or not self.use_jit:
            return None
        if node.join_type not in ("inner", "left"):
            return None
        walked = self._scan_chain(node.right, through_joins=False)
        if walked is None:
            return None
        scan, chain = walked
        conn = self.catalogs[scan.catalog]
        stores = getattr(conn, "stores", None)
        if stores is None or not stores(scan.table):
            return None
        right_types = self.output_types(node.right)

        if not all(_plain_int(left_types[c]) for c in node.left_keys) \
                or not all(_plain_int(right_types[c])
                           for c in node.right_keys):
            return None
        pivot = next((j for j, rk in enumerate(node.right_keys)
                      if self._scan_column_unique(node.right, rk)), None)
        if pivot is None:
            return None
        rows = int(conn.row_count(scan.table))
        cap = SH.bucket(max(rows, 1)) * STORED_JOIN_KEY_SPREAD
        nbytes = _stored_join_bytes(cap, rows, right_types)
        budget = self._budget()
        if budget and nbytes > budget // MB.BUILD_SHARE_DIV:
            return None
        return StoredJoin(
            pivot_ch=node.left_keys[pivot],
            build_key_ch=node.right_keys[pivot],
            extra_pairs=tuple(
                (lk, rk) for j, (lk, rk) in enumerate(
                    zip(node.left_keys, node.right_keys)) if j != pivot),
            scan=scan,
            chain_fns=tuple(
                fn for fn in (_node_replay_fn(nd)
                              for nd in reversed(chain))
                if fn is not None),
            rows=rows, cap=cap, nbytes=nbytes)

    def _stored_build(self, node: P.HashJoin, info: StoredJoin):
        """The lookup structure of one stored join (_stored_build_page:
        the direct-address table, its key floor, the build side's
        page), built ONCE an attempt by one program over the whole
        stored table, whose buffers are the program's arguments. The
        joins that ride on it (_ride_stored_joins) are built first and
        probed inside that program, their builds its arguments after
        the table's. None where a table cannot be read as a fused
        source (a column holds NULLs). Its flag (keys wider than the
        table, or a duplicate key) joins the deferred ladder."""
        riding = tuple((r.node.right, r.info.build_key_ch, r.pivot_ch,
                        r.pairs) for r in info.riders)
        key = (node.right, info.build_key_ch, *riding)
        built = self._stored_builds.get(key)
        if built is not None:
            return built
        ridden = []
        for r in info.riders:
            rbuilt = self._stored_build(r.node, r.info)
            if rbuilt is None:
                return None
            ridden.append(rbuilt)
        t0 = time.perf_counter()
        scan = info.scan
        conn = self.catalogs[scan.catalog]
        names = tuple(scan.columns)
        with annotation(f"join_build:{scan.table}"):
            src = conn.stored_source(scan.table, names)
            if src is None:
                return None
            schema = conn.table_schema(scan.table)
            scan_types = tuple(schema.column_type(c) for c in names)
            dicts = getattr(conn, "_dicts", {}).get(scan.table, {})
            scan_dicts = tuple(dicts.get(c) for c in names)
            fn = self._jit(
                ("stored_build", node.right, info.build_key_ch,
                 src.rows, info.cap, *riding),
                functools.partial(
                    _stored_build_page, src.reads, scan_types,
                    scan_dicts, info.chain_fns, info.build_key_ch,
                    src.rows, info.cap,
                    tuple((r.pivot_ch, r.pairs) for r in info.riders)))
            built, flag = fn(*src.args, *ridden)
        self._pending_overflow.append(flag)
        self._stored_builds[key] = built
        self.peak_memory_bytes = max(self.peak_memory_bytes,
                                     info.nbytes)
        wall = time.perf_counter() - t0
        self.join_builds += 1
        self.join_probes_at_build += len(ridden)
        self.join_build_rows += src.rows
        self.join_build_bytes += info.nbytes
        self.join_build_wall_us += int(round(wall * 1e6))
        if self.trace is not None:
            self.span_ending_now(
                "join_build", scan.table, wall, table=scan.table,
                rows=src.rows, capacity=info.cap, structure="direct",
                bytes=info.nbytes,
                riders=[r.info.scan.table for r in info.riders])
        return built

    def _exec_join_generated(self, node: P.HashJoin, info
                             ) -> Iterator[Page]:
        self.generated_joins_used += 1
        kern, windowed = self.generated_join_kernel(node, info)
        if not windowed:
            fn = self._jit(("genjoin", node), kern)
            for page in self.pages(node.left):
                yield fn(page)
            return
        fn = self._jit(("genjoin_win", node), kern)
        for page in self.pages(node.left):
            out, multi = fn(page)
            # >1 in-window matches for some probe row: the key set is
            # not unique in the data — retry takes the general join
            self._pending_overflow.append(multi)
            yield out

    def _exec_join(self, node: P.HashJoin) -> Iterator[Page]:
        """Page-level join execution: lazy (late-materialization) items
        produced along the probe spine materialize HERE, at the chain
        boundary — every deferred build column pays its one gather."""
        for item in self._exec_join_items(node):
            yield self._materialize_lazy(item)

    def _exec_join_items(self, node: P.HashJoin, want_lazy: bool = False):
        """Yields Page or latemat.LazyPage items for a join node. The
        single-pass general/unique sort paths defer build sides
        (inner/left joins) and consume the probe side through
        _lazy_pages so chained joins compose row-id indirections; every
        other path (generated, Pallas-unique, partitioned, semi/anti,
        right/full) yields materialized Pages as before.

        want_lazy: the consumer is a lazy-aware parent join — defer
        unconditionally. Otherwise (the chain boundary, where the
        caller materializes immediately) defer only when the probe
        items are themselves lazy: deferring the boundary join's own
        side is then free (the finish program runs anyway), while for
        a single un-chained join it would only add a launch."""
        left_types = self.output_types(node.left)
        right_types = self.output_types(node.right)
        gj = self._generated_join_info(node, left_types)
        if gj is not None:
            yield from self._exec_join_generated(node, gj)
            return
        # <=1 match per probe row when ANY build key scans a connector-
        # declared unique column (equality on a unique column alone
        # pins the row): join output can never exceed the probe page,
        # so output capacities stay exact (FK joins — the TPC-H common
        # case)
        unique_build = any(
            self._scan_column_unique(node.right, k)
            for k in node.right_keys
        )
        parts, governed = self._join_parts(node, left_types, right_types)
        if parts > 1:
            if governed:
                # the GOVERNOR (not a session threshold) rewrote this
                # join into grace-partition passes sized to fit
                self.memory_chunked_pipelines += 1
            yield from self._exec_join_partitioned(
                node, parts, left_types, right_types, unique_build
            )
            return
        build_pages = list(self.pages(node.right))
        if not build_pages:
            build_pages = [_empty_page(right_types)]
        build_all = concat_all(build_pages)
        # capacity-based sizing, not row count: reading num_rows() to the
        # host mid-query would trigger the post-D2H degradation (see
        # __init__); capacity is a static upper bound on rows
        build = compact_page(build_all, _next_pow2(build_all.capacity))
        self._account_page(build)  # the query's largest materialization
        if self._pallas_join_eligible(node, build, left_types,
                                      right_types):
            yield from self._pallas_join_pass(node, build, left_types)
            return
        allow = (self._late_mat_on()
                 and node.join_type in ("inner", "left"))
        probe_src = (
            self._lazy_pages(node.left) if allow
            else self.pages(node.left)
        )
        defer = "never"
        if allow:
            defer = "always" if want_lazy else "chain"
        yield from self._join_pass(
            node, build, probe_src, left_types,
            unique_build=unique_build, defer=defer,
        )

    # --------------------------------------- late materialization driver
    def _late_mat_on(self) -> bool:
        """late_materialization_enabled resolution: "auto" engages on
        TPU only (gather bandwidth is the win; CPU pays compile cost
        for nothing), True/False are explicit overrides."""
        mode = self.late_mat
        if mode in (False, None, "false", "off"):
            return False
        if mode == "auto":
            return jax.default_backend() == "tpu"
        return True

    def _lazy_probe_ok(self, node: P.PhysicalNode) -> bool:
        """Whether a probe-side subtree may stream lazy items instead of
        Pages. The DistExecutor narrows this to fully-replicated
        subtrees (sharded nodes route through shard_map paths that
        speak Pages)."""
        return self._late_mat_on()

    def _lazy_pages(self, node: P.PhysicalNode):
        """A join's probe-side stream: latemat.LazyPage items when the
        subtree is an eligible join-chain segment, plain Pages
        otherwise. Whole-chain fusion (generated joins) wins over
        laziness — a fused chain has no gathers to defer.

        Items bypass pages(), so interior chain nodes get no per-node
        EXPLAIN ANALYZE stats (the chain's wall lands on the top join);
        memory accounting is preserved by accounting every interior
        item here."""
        if isinstance(node, P.HashJoin) and self._lazy_probe_ok(node):
            fused = self._fused_stream(node)
            if fused is not None:
                for page in fused:
                    self._account_page(page)
                    yield page
                return
            for item in self._exec_join_items(node, want_lazy=True):
                self._account_page(
                    item.reduced if isinstance(item, LM.LazyPage)
                    else item
                )
                yield item
            return
        if (isinstance(node, P.Filter) and self._lazy_probe_ok(node)
                and _filter_chain_has_join(node)):
            fused = self._fused_stream(node)
            if fused is not None:
                for page in fused:
                    self._account_page(page)
                    yield page
                return
            yield from self._lazy_filter(node)
            return
        yield from self.pages(node)

    def _lazy_filter(self, node: P.Filter):
        """Filter over a lazy join chain: lift exactly the deferred
        channels the predicate reads (prune.expr_channels — the
        liveness set), remap the predicate onto the reduced layout, and
        flip validity bits without materializing anything else."""
        refs = tuple(sorted(PR.expr_channels(node.predicate)))
        for item in self._lazy_pages(node.source):
            if isinstance(item, Page):
                fn = self._jit(
                    ("filter", node.predicate),
                    functools.partial(_replay_filter, node.predicate),
                )
                yield fn(item)
                continue
            lz = self._lazy_lift(item, refs)
            pred = PR.remap_expr(
                node.predicate, {c: lz.phys(c) for c in refs}
            )
            fn = self._jit(
                ("filter_lazy", pred, lz.mat),
                functools.partial(_replay_filter, pred),
            )
            yield dataclasses.replace(lz, reduced=fn(lz.reduced))

    def _lazy_lift(self, lz: LM.LazyPage, channels) -> LM.LazyPage:
        """Materialize the named logical channels of a lazy page (one
        gather each) — downstream join keys and filter references, the
        'needed as values NOW' set. No-op when already materialized."""
        need = tuple(sorted(set(channels) - set(lz.mat)))
        if not need:
            return lz
        self.gathers_materialized += len(need)
        maps = tuple(s.channel_map for s in lz.sides)
        fn = self._jit(
            ("latemat_lift", lz.signature(), need,
             tuple(s.build.capacity for s in lz.sides)),
            functools.partial(LM.lift_page, lz.mat, maps, need),
        )
        reduced = fn(lz.reduced, *[s.build for s in lz.sides])
        _, new_mat, new_maps, keep = LM.lift_layout(lz.mat, maps, need)
        return LM.LazyPage(
            reduced=reduced, width=lz.width, mat=new_mat,
            sides=tuple(
                LM.LazySide(lz.sides[i].build, new_maps[i])
                for i in keep
            ),
        )

    def _materialize_lazy(self, item):
        """Chain-boundary materialization: every still-deferred column
        gathers exactly once through its side's composed row ids."""
        if isinstance(item, Page):
            return item
        if not item.sides:
            return item.reduced  # mat covers all channels, in order
        self.gathers_materialized += sum(
            len(s.channel_map) for s in item.sides
        )
        maps = tuple(s.channel_map for s in item.sides)
        fn = self._jit(
            ("latemat_fin", item.signature(),
             tuple(s.build.capacity for s in item.sides)),
            functools.partial(
                LM.finish_page, item.mat, maps, item.width
            ),
        )
        return fn(item.reduced, *[s.build for s in item.sides])

    # ---------------------------------------------------- Pallas paths
    def _pallas_mode_allows(self) -> bool:
        """pallas_join_enabled: "off" never; "force" always (on a CPU
        the kernel runs in interpret mode — the test path); "auto" on
        a TPU only, where the kernel lowers through Mosaic (the
        interpreted kernel exists for testing, not speed)."""
        return self._tristate_on(self.pallas_join)

    @staticmethod
    def _pallas_interpret() -> bool:
        """Interpret mode is the CPU test path only: on a TPU the dim
        probe lowers through Mosaic."""
        return jax.default_backend() != "tpu"

    def _pallas_join_eligible(self, node, build: Page, left_types,
                              right_types) -> bool:
        """Unique-key fast path: inner/left joins on ONE u64-encodable
        key whose build side scans a connector-declared UNIQUE column —
        <=1 match per probe row, so the probe page extends in place with
        no match expansion at all. Boosted retries fall back to the
        general join (the overflow flag may have come from the Pallas
        table build)."""
        from presto_tpu.ops import pallas_join as PJ

        if self._capacity_boost > 1:
            return False
        if node.join_type not in ("inner", "left"):
            return False
        if len(node.left_keys) != 1 or len(node.right_keys) != 1:
            return False
        for t in (left_types[node.left_keys[0]],
                  right_types[node.right_keys[0]]):
            if T.is_string(t) or t.is_dictionary_encoded:
                # dictionary codes are not comparable across sides
                # without the merged-universe canonicalization the
                # general path does
                return False
            if isinstance(t, T.DecimalType) and not t.is_short:
                # long decimals encode as (hi, lo) limb pairs — one u64
                # key cannot carry them
                return False
        if build.capacity > PJ.DIM_MAX_BUILD:
            return False
        if not self._pallas_mode_allows():
            return False
        return self._scan_column_unique(node.right, node.right_keys[0])

    def _radix_join_eligible(self, node, build: Page) -> bool:
        """The Pallas dim probe (ops/pallas_join.py) as the general
        range finder for inner/left/right/full equi-joins: any key
        count/types, duplicate build keys, builds of up to
        DIM_MAX_BUILD rows (star-schema dimension builds); above that
        the sort join. Boosted retries fall back to the sort join —
        the overflow may have been a tile overfull in the Pallas table
        build."""
        if self._capacity_boost > 1:
            return False
        if node.join_type not in ("inner", "left", "right", "full"):
            return False
        from presto_tpu.ops import pallas_join as PJ

        if build.capacity > PJ.DIM_MAX_BUILD:
            return False
        return self._pallas_mode_allows()

    def _scan_column_unique(self, n: P.PhysicalNode, ch: int) -> bool:
        """Whether channel ch of node n provably carries a unique table
        column (shared walker: P.scan_column_unique, also used by the
        planner's join ordering)."""
        return P.scan_column_unique(n, ch, self.catalogs)

    def _pallas_join_pass(self, node, build: Page,
                          left_types) -> Iterator[Page]:
        from presto_tpu.ops import pallas_join as PJ

        self.pallas_joins_used += 1
        layout = PJ.plan_layout(build.capacity)
        interpret = self._pallas_interpret()
        index, build_ovf = self._jit(
            ("pallas_ubuild", node.right_keys[0], build.capacity),
            functools.partial(
                _pallas_unique_build, node.right_keys[0], layout
            ),
        )(build)
        self._pending_overflow.append(build_ovf)
        fn = self._jit(
            ("pallas_probe", node.left_keys[0], node.join_type,
             build.capacity, interpret),
            functools.partial(
                _pallas_probe_page, node.left_keys[0], node.join_type,
                layout, interpret,
            ),
        )
        for page in self.pages(node.left):
            yield fn(page, build, index)

    def _exec_join_partitioned(
        self, node: P.HashJoin, parts: int, left_types, right_types,
        unique_build: bool = False,
    ) -> Iterator[Page]:
        """Grace-style partition-wise join: P passes, each streaming both
        sides filtered to hash(key) % P == p, so the build materialization
        is ~1/P of the single-pass size. Skewed partitions raise the
        deferred overflow flag and the query retries on the boosted
        capacity ladder — where INNER joins take the per-partition
        REBALANCING path instead of growing buffers (SURVEY §6.7):
        a genuinely hot join key cannot be split by key hash, so the hot
        partition's build rows are chunked by POSITION into passes whose
        buffers stay at the unboosted (fault-line-safe) size, each chunk
        probed by the full partition probe stream; inner-join output is
        the disjoint union over chunks (every build row lives in exactly
        one chunk). Reading exact partition sizes is a host sync, which
        is admissible here because the retry boundary already paid the
        one D2H read that triggers the post-read degradation."""
        self.spill_partitions_used = max(self.spill_partitions_used, parts)
        semi = node.join_type in ("semi", "anti")
        bfilter = self._partition_filter(node.right_keys, parts,
                                         keep_nulls=semi)
        pfilter = self._partition_filter(node.left_keys, parts)
        right_stream = self._source_stream(node.right)
        left_stream = self._source_stream(node.left)
        rebalance = (
            self.join_skew_rebalance
            and (self._capacity_boost > 1 or self.skew_preengaged)
            and node.join_type == "inner"
        )
        if rebalance and self._capacity_boost == 1:
            # adaptive pre-engagement (ISSUE 15): the stage-boundary
            # re-planner saw a hot partition in the upstream spool
            # histogram, so the rebalanced chunking starts on the
            # FIRST attempt instead of being discovered via overflow
            self.skew_preempted += 1
        for p in range(parts):
            pj = jnp.uint64(p)
            if rebalance:
                yield from self._join_partition_rebalanced(
                    node, p, parts, bfilter, pfilter, right_stream,
                    left_stream, left_types, unique_build,
                )
                continue
            build_pages = []
            for pg in right_stream():
                f = bfilter(pg, pj)
                # compact each filtered build page to ~pg/parts before the
                # concat — this is where the memory actually shrinks
                pc = min(
                    _next_pow2(
                        max(pg.capacity // parts * 2, 1024)
                        * self._capacity_boost
                    ),
                    _next_pow2(pg.capacity),
                )
                self._pending_overflow.append(f.num_rows() > pc)
                build_pages.append(compact_page(f, pc))
            if not build_pages:
                build_pages = [_empty_page(right_types)]
            build_all = concat_all(build_pages)
            build = compact_page(build_all, _next_pow2(build_all.capacity))
            self._account_page(build)
            probe_pages = (
                pfilter(pg, pj) for pg in left_stream()
            )
            # partition-filtered probe pages are ~1/parts dense — scale
            # output capacities down accordingly or every pass's output
            # pages balloon to unpartitioned size (and a downstream
            # materialization would pin parts-times the real data)
            yield from self._join_pass(node, build, probe_pages,
                                       left_types,
                                       unique_build=unique_build,
                                       density=parts)

    def _join_partition_rebalanced(
        self, node: P.HashJoin, p: int, parts: int, bfilter, pfilter,
        right_stream, left_stream, left_types, unique_build: bool,
    ) -> Iterator[Page]:
        """One skew-rebalanced partition pass (see _exec_join_partitioned):
        exact per-page build counts (host reads — recovery mode), pieces
        packed greedily into chunks of at most the UNBOOSTED partition
        cap, oversized pieces split by slice_page, one probe pass per
        chunk."""
        from presto_tpu.ops.compact import slice_page

        pj = jnp.uint64(p)
        chunk_cap = 1024
        pieces: List[Page] = []
        for pg in right_stream():
            chunk_cap = max(
                chunk_cap,
                min(SH.chunk_bucket(pg.capacity, parts),
                    _next_pow2(pg.capacity)),
            )
            f = bfilter(pg, pj)
            # host sync, admissible on retry — metered (exec/xfer.py)
            n = int(XF.np_host(f.num_rows(), label="skew-count"))
            if n:
                pieces.append(compact_page(f, _next_pow2(max(n, 256))))
        # greedy pack: pieces accumulate into a chunk until it would
        # exceed chunk_cap; a single piece larger than chunk_cap splits
        # by position
        chunks: List[List[Page]] = [[]]
        room = chunk_cap
        for piece in pieces:
            rows = piece.capacity  # compacted: capacity ~ rows
            if rows > chunk_cap:
                for off in range(0, rows, chunk_cap):
                    chunks.append(
                        [slice_page(piece, off, chunk_cap)]
                    )
                # the split chunks are full (and `room` still described
                # the chunk BEFORE them): start a fresh chunk so later
                # pieces cannot pile onto a full slice and grow a chunk
                # to ~2x chunk_cap
                chunks.append([])
                room = chunk_cap
                continue
            if rows > room:
                chunks.append([])
                room = chunk_cap
            chunks[-1].append(piece)
            room -= rows
        chunks = [c for c in chunks if c]
        if not chunks:
            return  # empty inner partition: no output
        self.skew_chunks_used = max(self.skew_chunks_used, len(chunks))
        for chunk in chunks:
            build_all = concat_all(chunk)
            build = compact_page(
                build_all, _next_pow2(build_all.capacity)
            )
            self._account_page(build)
            probe_pages = (pfilter(pg, pj) for pg in left_stream())
            yield from self._join_pass(
                node, build, probe_pages, left_types,
                unique_build=unique_build, density=parts,
            )

    def _join_pass(
        self, node: P.HashJoin, build: Page, probe_pages, left_types,
        *, unique_build: bool = False, density: int = 1,
        defer: str = "never",
    ):
        """One build+probe pass (the whole join unless partitioned).

        unique_build: <=1 match per probe row — output sized to the probe
        page exactly. density: probe pages carry ~1/density real rows
        (partition-filtered passes); output capacity shrinks to match,
        with the deferred overflow flag + boosted retry guarding skew.
        defer: "always" emits this join's build side as a row-id
        indirection (latemat.LazyPage) instead of gathering its columns;
        "chain" defers only when the probe item is itself lazy (the
        finish program runs anyway, so deferring is free — while for a
        lone boundary join it would just add a launch); "never" is the
        eager path. Lazy probe items' deferred keys lift here — exactly
        the 'needed as a downstream join key' liveness contract."""
        if node.join_type in ("semi", "anti"):
            fn = self._jit(
                ("semi", node.left_keys, node.right_keys,
                 build.capacity),
                functools.partial(_semi_join_page, node.left_keys,
                                  node.right_keys),
            )
            for page in probe_pages:
                yield fn(page, build)
            return

        # Pallas path: same verified match expansion, but the
        # candidate ranges come from the open-addressing dim kernel
        # instead of searchsorted
        use_radix = self._radix_join_eligible(node, build)
        layout = interpret = None
        if use_radix:
            from presto_tpu.ops import pallas_join as PJ

            self.pallas_joins_used += 1
            layout = PJ.plan_layout(build.capacity)
            interpret = self._pallas_interpret()
        use_unique = (
            not use_radix and unique_build
            and node.join_type in ("inner", "left")
            and self._capacity_boost == 1
        )
        defer_allowed = (
            defer != "never" and node.join_type in ("inner", "left")
        )

        def probe_fn_for(pkeys, defer_item):
            if use_radix:
                return self._jit(
                    ("radix_probe", node.right_keys, node.join_type,
                     build.capacity, interpret, pkeys, defer_item),
                    functools.partial(
                        _probe_pallas_join_page, pkeys,
                        node.right_keys, node.join_type, layout,
                        interpret, defer_item,
                    ),
                    static_argnums=(3,),
                )
            if use_unique:
                # FK fast path: no expansion; a u64 hash collision
                # between distinct unique keys flags overflow and the
                # boosted retry takes the general expansion below
                return self._jit(
                    ("join_probe_unique", node.right_keys,
                     node.join_type, build.capacity, pkeys,
                     defer_item),
                    functools.partial(
                        _probe_join_page_unique, pkeys,
                        node.right_keys, node.join_type, defer_item,
                    ),
                    static_argnums=(3,),
                )
            return self._jit(
                ("join_probe", node.right_keys, node.join_type,
                 build.capacity, pkeys, defer_item),
                functools.partial(
                    _probe_join_page, pkeys, node.right_keys,
                    node.join_type, defer_item,
                ),
                static_argnums=(3,),
            )

        build_matched = jnp.zeros((build.capacity,), dtype=jnp.bool_)
        n_right = len(build.blocks)
        # governed output-capacity ceiling (membudget.py): a join
        # output page claims at most its budget share and stays under
        # the device fault line; a page whose naturally-sized output
        # would exceed it is position-chunked below
        out_row_b = _row_bytes(left_types) + _row_bytes(
            [b.type for b in build.blocks]
        )
        oc_cap = MB.rows_cap(
            out_row_b, self._budget(), self._fault_rows(),
            MB.PAGE_SHARE_DIV,
        )
        chunk_counted = False
        # canonical key encodings depend on the probe page's dictionaries
        # (merged-universe remap), which can differ across pages when the
        # probe side unions differently-coded streams — index per
        # dictionary signature, built once each (HashBuilderOperator
        # analog; one signature in the common case)
        indexes: Dict = {}
        for item in probe_pages:
            if isinstance(item, LM.LazyPage):
                # downstream-join-key liveness: lift exactly the key
                # channels this probe needs as values
                lz = self._lazy_lift(item, node.left_keys)
                page = lz.reduced
                pkeys = tuple(lz.phys(c) for c in node.left_keys)
            else:
                lz = None
                page = item
                pkeys = tuple(node.left_keys)
            sig = (pkeys,
                   tuple(page.block(c).dictionary for c in pkeys))
            if sig not in indexes:
                if use_radix:
                    index, b_ovf = self._jit(
                        ("radix_build", node.right_keys,
                         build.capacity, sig),
                        functools.partial(
                            _build_pallas_join_index, pkeys,
                            node.right_keys, layout,
                        ),
                    )(page, build)
                    # tile-overfull escape: boosted retries fall back
                    # to the sort join (eligibility checks the boost)
                    self._pending_overflow.append(b_ovf)
                else:
                    index = self._jit(
                        ("join_build", node.right_keys,
                         build.capacity, sig),
                        functools.partial(
                            _build_join_index, pkeys,
                            node.right_keys,
                        ),
                    )(page, build)
                indexes[sig] = index
            index = indexes[sig]
            # probe-relative sizing (many-to-one joins dominate), with a
            # build term for small-probe fan-out joins, clamped so the 2x
            # term cannot COMPOUND down a join chain (each join's output
            # page is the next probe's input; Q17's 7-join pipeline would
            # double 262k -> 4.2M and cross the >=4M-row kernel
            # fault line). Real fan-out beyond the clamp lands on the
            # overflow-retry ladder (up to 4^5 x).
            if unique_build:
                # output rows <= probe rows, exactly sized
                oc = page.capacity
            else:
                oc = page.capacity * 2
                if page.capacity <= 1 << 16:
                    oc = max(oc, build.capacity)
            oc = min(oc, max(4 * self.page_rows, 1 << 19))
            if density > 1:
                # 2x slack over the expected 1/density occupancy absorbs
                # partition-hash fluctuation without a boosted retry
                oc = max(oc * 2 // density, 8192)
            oc = _next_pow2(max(oc, 8192) * self._capacity_boost)
            slices = 1
            if oc_cap is not None and oc > oc_cap:
                # probe-side POSITION chunking (the governed rewrite):
                # slice the probe page so each slice keeps the full
                # per-probe-row output allowance inside a cap-sized
                # buffer. Boosted retries grow `oc`, hence the slice
                # count — capacity per probe row still climbs the
                # ladder while the buffer stays at the cap (except the
                # pathological tiny-probe/huge-fan-out corner, where
                # the LADDER_MIN slice floor binds and oc keeps the
                # allowance instead — slots must exist somewhere).
                # Both factors are powers of two, so slice shapes land
                # on the shared ladder and chunk programs are reused.
                slices = min(
                    oc // oc_cap,
                    max(page.capacity // SH.LADDER_MIN, 1),
                )
                oc = max(oc // slices, oc_cap)
                if slices > 1 and not chunk_counted:
                    # counted only when chunking actually happens (the
                    # LADDER_MIN floor can pin slices at 1, in which
                    # case oc simply keeps the allowance)
                    self.memory_chunked_pipelines += 1
                    chunk_counted = True
            defer_item = defer_allowed and (
                defer == "always" or lz is not None
            )
            pfn = probe_fn_for(pkeys, defer_item)
            # ceil-divide: a concat-produced probe page's capacity is a
            # SUM of buckets and need not be a multiple of the slice
            # count — floor division would silently drop the tail rows.
            # slice_page clamps the final slice; recomputing the slice
            # count from the ceil'd chunk keeps every chunk non-empty.
            ccap = -(-page.capacity // max(slices, 1))
            n_slices = -(-page.capacity // max(ccap, 1))
            for s in range(n_slices):
                chunk = (
                    page if n_slices == 1
                    else slice_page(page, s * ccap, ccap)
                )
                out, matched, overflow = pfn(chunk, build, index, oc)
                self._pending_overflow.append(overflow)
                build_matched = build_matched | matched
                if defer_item:
                    width_l = lz.width if lz is not None else (
                        page.channel_count
                    )
                    mat = lz.mat if lz is not None else tuple(
                        range(page.channel_count)
                    )
                    sides = (lz.sides if lz is not None else ()) + (
                        LM.LazySide(
                            build,
                            tuple((width_l + j, j)
                                  for j in range(n_right)),
                        ),
                    )
                    self.gathers_deferred += sum(
                        len(s.channel_map) for s in sides
                    )
                    yield LM.LazyPage(
                        reduced=out, width=width_l + n_right, mat=mat,
                        sides=sides,
                    )
                else:
                    yield out
        if node.join_type in ("right", "full"):
            # emit unmatched build rows with null left side (reference:
            # LookupOuterOperator draining unvisited positions)
            unmatched = build.valid & ~build_matched
            null_left = _null_blocks(left_types, build.capacity)
            page = Page(
                blocks=tuple(null_left) + build.blocks, valid=unmatched
            )
            yield page


# ---------------------------------------------------------------- kernels
# Module-level pure functions so functools.partial(...) stays hashable and
# jit caches hit across pages.


def _pallas_unique_build(key_ch, layout, build: Page):
    """Unique-key Pallas index over the IDENTITY u64 key encoding —
    in-kernel (lo, hi) equality IS key equality, so probe hits extend
    rows without re-verification."""
    from presto_tpu.ops import pallas_join as PJ

    blk = build.block(key_ch)
    bkeys = K.equality_encoding(blk)[0]
    bvalid = build.valid
    if blk.nulls is not None:
        bvalid = bvalid & ~blk.nulls
    tables, perm, ovf = PJ.build_index(
        bkeys.astype(jnp.uint64), bvalid, layout
    )
    return (tables, perm), ovf


def _pallas_probe_page(key_ch, join_type, layout, interpret, page: Page,
                       build: Page, index) -> Page:
    """Probe one page through the Pallas kernel: unique build keys mean
    <=1 match per probe row, so the output page is the probe page
    extended with gathered build columns (no expansion)."""
    from presto_tpu.ops import pallas_join as PJ

    tables, perm = index
    blk = page.block(key_ch)
    pkeys = K.equality_encoding(blk)[0]
    valid_key = page.valid
    if blk.nulls is not None:
        valid_key = valid_key & ~blk.nulls
    start, cnt = PJ.probe_index(
        pkeys.astype(jnp.uint64), tables, layout, interpret=interpret
    )
    hit = valid_key & (cnt > 0)
    rid = jnp.where(
        hit, perm[jnp.clip(start, 0, None)].astype(jnp.int32),
        jnp.int32(-1),
    )
    matched = rid >= 0
    safe = jnp.clip(rid, 0, build.capacity - 1).astype(jnp.int64)
    right_blocks = []
    for b in build.blocks:
        if isinstance(b.data, tuple):
            data = tuple(d[safe] for d in b.data)
        else:
            data = b.data[safe]
        nulls = b.nulls[safe] if b.nulls is not None else None
        if join_type == "left":
            nulls = ~matched if nulls is None else (nulls | ~matched)
        right_blocks.append(
            Block(data=data, type=b.type, nulls=nulls,
                  dictionary=b.dictionary)
        )
    out_valid = (
        page.valid & matched if join_type == "inner" else page.valid
    )
    return Page(blocks=page.blocks + tuple(right_blocks),
                valid=out_valid)


def _project_page(exprs, page: Page) -> Page:
    blocks = []
    for e in exprs:
        v = evaluate(e, page, jnp)
        data = v.data
        if not isinstance(data, tuple) and data.ndim == 0:
            data = jnp.broadcast_to(data, (page.capacity,))
        elif isinstance(data, tuple):
            data = tuple(
                jnp.broadcast_to(d, (page.capacity,)) if d.ndim == 0 else d
                for d in data
            )
        nulls = v.nulls
        if nulls is not None and nulls.ndim == 0:
            nulls = jnp.broadcast_to(nulls, (page.capacity,))
        dic = v.dictionary
        if (dic is None and T.is_string(e.type) and v.is_const
                and v.py_value is not None):
            # a PROJECTED string constant must be first-class: consuming
            # functions resolve constants against the column dictionary,
            # but as an output column the code needs its own one-entry
            # dictionary or it would decode as the bare code 0
            dic = Dictionary([v.py_value])
        blocks.append(
            Block(data=data, type=e.type, nulls=nulls, dictionary=dic)
        )
    return Page(blocks=tuple(blocks), valid=page.valid)


def _group_ids(group_channels, page: Page, cap: int, max_iters: int = 64):
    key_blocks = [page.block(c) for c in group_channels]
    # dense fast path: all keys dictionary-coded (unique values, no nulls) or
    # boolean, and the combined code space fits the capacity — group id is
    # computed arithmetically, no hash table at all (Q1: 2 flag columns).
    # Reference analog: BigintGroupByHash's small-range fast path.
    sizes = []
    for b in key_blocks:
        if (
            b.dictionary is not None
            and len(b.dictionary)
            and not b.dictionary.has_duplicate_values()
            and b.nulls is None
        ):
            sizes.append(len(b.dictionary))
        elif isinstance(b.type, T.BooleanType) and b.nulls is None:
            sizes.append(2)
        else:
            sizes = None
            break
    if sizes is not None:
        space = 1
        for s in sizes:
            space *= s
        if space <= cap:
            gid = jnp.zeros(page.valid.shape, dtype=jnp.int64)
            for b, s in zip(key_blocks, sizes):
                code = jnp.clip(b.data.astype(jnp.int64), 0, s - 1)
                gid = gid * s + code
            # size the output to the key space, not the caller's capacity:
            # downstream segment ops scale with the group capacity (XLA:TPU
            # expands them to dense [n, cap] one-hot products)
            return A.compute_groups_dense(
                gid, page.valid, space, out_capacity=_next_pow2(space),
                sizes=tuple(sizes),
            )
    key_cols, key_nulls = K.block_key_columns(key_blocks)
    if cap > A.MATMUL_AGG_MAX_GROUPS or page.valid.shape[0] >= (1 << 22):
        # High-cardinality group-bys take the packed-argsort path: its
        # sorted layout lets aggregate() run scatter-free (gather +
        # cumsum + boundary diffs — round-4: the hashed while_loop's
        # per-iteration scatters made Q3 SF1's aggregation 42s of a
        # 91s query). Also mandatory >= ~4M rows, where the
        # vectorized-probing while_loop kernel faults the XLA:TPU
        # runtime (observed on v5e regardless of table size).
        return A.compute_groups_sorted(
            key_cols, key_nulls, page.valid, cap
        )
    # small capacities: the probing hash table is cheap and its input-
    # order group ids feed the MXU one-hot matmul aggregation directly
    return A.compute_groups_hashed(
        key_cols, key_nulls, page.valid, cap, max_iters=max_iters
    )


def _state_reduce(st, blk, kind, apply_pre, reducer):
    """Run one primitive reduction with value-domain transforms.

    Dictionary-coded inputs (min/max need *value* order, not code order) are
    rank-transformed through Dictionary.sort_rank before reducing and mapped
    back after, and the dictionary rides along so decode stays correct.
    reducer(data, nulls) -> (vals, out_nulls).
    """
    if blk is None:
        return (*reducer(None, None), None)
    if isinstance(blk.data, tuple):
        raise NotImplementedError(
            "aggregation over long-decimal (p>18) input columns is not "
            "supported yet; decimal sums produce long-decimal *outputs* "
            "from short inputs (presto_tpu/exec/agg_states.py)"
        )
    dic = blk.dictionary
    if dic is not None and kind in (A.MIN, A.MAX) and len(dic):
        # xfercheck: raw-ok - trace-time LUT embedding
        rank = jnp.asarray(dic.sort_rank().astype(np.int64))
        # xfercheck: raw-ok - trace-time LUT embedding
        inv = jnp.asarray(np.argsort(dic.sort_rank()).astype(np.int64))
        data = rank[jnp.clip(blk.data, 0, len(dic) - 1)]
        vals, out_nulls = reducer(data, blk.nulls)
        vals = inv[jnp.clip(vals, 0, len(dic) - 1)].astype(blk.data.dtype)
        return vals, out_nulls, dic
    data = S.pre_transform(st.pre, blk.data) if apply_pre else blk.data
    vals, out_nulls = reducer(data, blk.nulls)
    return vals, out_nulls, dic


def _attach_dictionary(block: Block, dic) -> Block:
    if dic is None or block.dictionary is not None:
        return block
    if not block.type.is_dictionary_encoded:
        return block
    return Block(
        data=block.data, type=block.type, nulls=block.nulls, dictionary=dic
    )


def _mark_distinct_page(mark_channel_sets, page: Page, cap, max_iters):
    """Append first-occurrence marks per key set (MarkDistinctOperator):
    group ids over the key set, then scatter True at each group's
    representative row."""
    blocks: List[Block] = []
    overflow = jnp.zeros((), dtype=jnp.bool_)
    for chans in mark_channel_sets:
        groups = _group_ids(chans, page, cap, max_iters)
        idx = jnp.where(
            groups.group_valid, groups.rep_index, page.capacity
        )
        mark = jnp.zeros((page.capacity,), dtype=jnp.bool_)
        mark = mark.at[idx].set(True, mode="drop")
        blocks.append(Block(data=mark, type=T.BOOLEAN, nulls=None))
        overflow = overflow | groups.overflow
    return (
        Page(blocks=page.blocks + tuple(blocks), valid=page.valid),
        overflow,
    )


def _apply_agg_mask(spec, page: Page, blk: Optional[Block]):
    """Per-aggregate mask (AggSpec.mask): unmarked rows contribute
    nothing — expressed as null inputs, which every accumulator skips."""
    if spec.mask is None or blk is None:
        return blk
    inv = ~page.block(spec.mask).data
    nulls = inv if blk.nulls is None else (blk.nulls | inv)
    return Block(data=blk.data, type=blk.type, nulls=nulls,
                 dictionary=blk.dictionary)


def _hll_hashes(blk: Block) -> jnp.ndarray:
    """One u64 hash per row over the block's equality encoding (SQL-
    equal values hash equal, including dictionary canonicalization)."""
    cols = K.equality_encoding(blk)
    return H.hash_columns(cols, [None] * len(cols))


def _hll_contributing(groups, blk: Optional[Block]):
    contributing = groups.row_valid
    if blk is not None and blk.nulls is not None:
        contributing = contributing & ~blk.nulls
    return contributing


def _dense_keys_page(src: Page, group_channels, groups) -> Page:
    """Synthesize group-key columns arithmetically from the mixed-radix
    group id (dense path): avoids the rep_index scatter+gather, which
    XLA then dead-code-eliminates from the program."""
    out_cap = groups.group_valid.shape[0]
    gid = jnp.arange(out_cap, dtype=jnp.int64)
    codes = []
    for s in reversed(groups.dense_sizes):
        codes.append(gid % s)
        gid = gid // s
    codes.reverse()
    blocks = []
    for c, code in zip(group_channels, codes):
        b = src.block(c)
        blocks.append(
            Block(data=code.astype(b.data.dtype), type=b.type,
                  nulls=None, dictionary=b.dictionary)
        )
    return Page(blocks=tuple(blocks), valid=groups.group_valid)


def _agg_keys_page(src: Page, group_channels, groups) -> Page:
    if groups.dense_sizes is not None:
        return _dense_keys_page(src, group_channels, groups)
    return gather_rows(
        src.select_channels(group_channels),
        groups.rep_index,
        groups.group_valid,
    )


def _collect_encode(blk: Block):
    """Encode a block's values into int64 collect slots (ints/dates/
    bools/short decimals directly, dictionary codes as-is — the
    dictionary rides the state Block).

    Floats use an arithmetic sign/exponent/mantissa pack built from
    log2/exp2/floor only: an earlier TPU toolchain compiles NEITHER
    64-bit bitcast_convert_type NOR frexp/ldexp (probed round 4 —
    compiler SIGSEGV / unimplemented X64 rewrite), and its emulated
    float64 is range-limited (~1e38, f32-pair emulation), so the
    exponent fits comfortably in the 11-bit field. The pack is
    ORDER-PRESERVING (int64 order == float order), which is why
    approx_percentile needs no float special case. Values round-trip
    at full device precision; NaN encodes as +max (documented)."""
    data = blk.data
    if isinstance(data, tuple):
        raise NotImplementedError(
            "array_agg/map_agg over long-decimal (p>18) inputs is not "
            "supported"
        )
    if data.dtype in (jnp.float64, jnp.float32):
        x = data.astype(jnp.float64)
        ax = jnp.abs(x)
        safe = jnp.where(ax > 0, ax, 1.0)
        e = jnp.floor(jnp.log2(safe))
        # power-of-two scaling is exact; two correction steps absorb
        # any log2 boundary imprecision
        m = safe * jnp.exp2(-e - 1.0)
        for _ in range(2):
            hi = m >= 1.0
            lo = m < 0.5
            e = e + jnp.where(hi, 1.0, 0.0) - jnp.where(lo, 1.0, 0.0)
            m = jnp.where(hi, m * 0.5, jnp.where(lo, m * 2.0, m))
        frac = jnp.clip(
            ((m - 0.5) * float(2**53)).astype(jnp.int64),
            0, (1 << 52) - 1,
        )
        e_adj = jnp.clip(e.astype(jnp.int64) + 1100, 0, 2047)
        mag = (e_adj << jnp.int64(52)) | frac
        enc = jnp.where(
            ax == 0, jnp.int64(0), jnp.where(x < 0, -mag, mag)
        )
        return jnp.where(
            jnp.isnan(x), jnp.iinfo(jnp.int64).max, enc
        )
    return data.astype(jnp.int64)


def _collect_float_decode_device(enc: jnp.ndarray) -> jnp.ndarray:
    """Inverse of the float pack, on device (bitcast/ldexp-free):
    value = sign * 2^(e+1) * (0.5 + frac * 2^-53)."""
    mag = jnp.abs(enc)
    e = ((mag >> jnp.int64(52)) - jnp.int64(1100)).astype(jnp.float64)
    frac = (mag & jnp.int64((1 << 52) - 1)).astype(jnp.float64)
    m = 0.5 + frac * float(2.0**-53)
    val = m * jnp.exp2(e + 1.0)
    val = jnp.where(enc < 0, -val, val)
    return jnp.where(enc == 0, 0.0, val)


def _collect_partial_blocks(spec, layout, page, groups, out_cap,
                            collect_k):
    """Partial-step collect state. Null semantics per the reference:
    array_agg INCLUDES null elements (a parallel null-flag matrix rides
    the state); map_agg skips null KEYS but preserves null values;
    approx_percentile ignores nulls. A per-aggregate DISTINCT mask
    always excludes unmarked rows."""
    from presto_tpu.ops import collect as C

    blk = page.block(spec.channel)
    mask = None if spec.mask is None else page.block(spec.mask).data
    contributing = groups.row_valid
    if mask is not None:
        contributing = contributing & mask
    fn = spec.function
    if fn == "map_agg":
        if blk.nulls is not None:  # null keys are skipped
            contributing = contributing & ~blk.nulls
        vblk = page.block(spec.extra_channels[0])
        if vblk.dictionary is not None:
            raise NotImplementedError(
                "map_agg with dictionary-coded (varchar/complex) VALUE "
                "columns is not supported yet; keys may be any type"
            )
        sources = [
            (blk, None),
            (vblk, None),
            (None, vblk.nulls),  # value null flags
        ]
    elif fn == "approx_percentile":
        if blk.nulls is not None:  # percentile ignores nulls
            contributing = contributing & ~blk.nulls
        sources = [(blk, None)]
    else:  # array_agg: null elements included
        sources = [(blk, None), (None, blk.nulls)]
    blocks: List[Block] = []
    overflow = jnp.zeros((), dtype=jnp.bool_)
    for (vb, null_src), st in zip(sources, layout):
        if vb is not None:
            enc = _collect_encode(vb)
            dic = vb.dictionary
        else:
            enc = (null_src.astype(jnp.int64) if null_src is not None
                   else jnp.zeros(page.capacity, dtype=jnp.int64))
            dic = None
        vals, ovf = C.insert(
            groups.group_ids, contributing, out_cap, enc, collect_k
        )
        overflow = overflow | ovf
        blocks.append(Block(data=vals, type=st.type, nulls=None,
                            dictionary=dic))
    cnt, _ = A.aggregate(
        groups, A.COUNT, out_cap,
        jnp.zeros(page.capacity, dtype=jnp.int64),
        ~contributing,
    )
    blocks.append(Block(data=cnt, type=T.BIGINT, nulls=None))
    return blocks, overflow


def _collect_merge_blocks(spec, layout, merged, groups, out_cap, ch,
                          collect_k):
    """Merge partial collect states (grouped by output key): per
    collected column, concatenate member rows' slot vectors in row
    order; the count column segment-sums."""
    from presto_tpu.ops import collect as C

    n_collect = len(layout) - 1
    cnt_blk = merged.block(ch + n_collect)
    counts = cnt_blk.data
    blocks: List[Block] = []
    overflow = jnp.zeros((), dtype=jnp.bool_)
    for i in range(n_collect):
        blk = merged.block(ch + i)
        vals, ovf = C.merge(
            groups.group_ids, groups.row_valid, out_cap,
            blk.data, counts, collect_k,
        )
        overflow = overflow | ovf
        blocks.append(Block(data=vals, type=layout[i].type, nulls=None,
                            dictionary=blk.dictionary))
    ncnt, _ = A.aggregate(groups, A.SUM, out_cap, counts, None)
    blocks.append(Block(data=ncnt, type=T.BIGINT, nulls=None))
    return blocks, overflow


def _collect_finalize_block(spec, in_t, extra_t, state_blocks) -> Block:
    """Merged collect state -> the SQL result Block. The result Block
    carries TUPLE data ((vals2d, nulls2d, counts) for arrays; (k2d,
    v2d, vnulls2d, counts) for maps) decoded host-side at the client
    boundary (page.to_pylist) — collect results cannot feed further
    device expressions (documented divergence; reference arrays are
    first-class)."""
    from presto_tpu.ops import collect as C

    if spec.function == "approx_percentile":
        vals_blk, cnt_blk = state_blocks
        frac = float(spec.params[0]) if spec.params else 0.5
        # the float slot-encoding is order-preserving, so one int64
        # sort serves every element type
        picked = C.percentile_select(
            vals_blk.data, cnt_blk.data, frac,
            vals_blk.data.shape[1],
        )
        if T.is_floating(in_t):
            data = _collect_float_decode_device(picked).astype(
                np.dtype(in_t.numpy_dtype))
        else:
            data = picked.astype(np.dtype(in_t.numpy_dtype))
        return Block(data=data, type=in_t, nulls=cnt_blk.data == 0)
    if spec.function == "map_agg":
        # value columns are restricted to non-dictionary types (checked
        # at partial), so the Block's one dictionary slot carries keys
        k_blk, v_blk, vn_blk, cnt_blk = state_blocks
        out_t = T.MapType(in_t, extra_t[0] if extra_t else T.UNKNOWN)
        return Block(
            data=(k_blk.data, v_blk.data, vn_blk.data, cnt_blk.data),
            type=out_t,
            nulls=cnt_blk.data == 0, dictionary=k_blk.dictionary,
        )
    vals_blk, vn_blk, cnt_blk = state_blocks
    out_t = T.ArrayType(in_t)
    return Block(
        data=(vals_blk.data, vn_blk.data, cnt_blk.data), type=out_t,
        nulls=cnt_blk.data == 0, dictionary=vals_blk.dictionary,
    )


def _partial_agg_page(group_channels, aggregates, layouts, page: Page,
                      cap: int, max_iters: int = 64, collect_k: int = 1024):
    groups = _group_ids(group_channels, page, cap, max_iters)
    # dense fast path may size output below cap (see _group_ids)
    out_cap = groups.group_valid.shape[0]
    keys_page = _agg_keys_page(page, group_channels, groups)
    state_blocks: List[Block] = []
    for spec, layout in zip(aggregates, layouts):
        if spec.function in S.COLLECT_FNS:
            blocks, c_ovf = _collect_partial_blocks(
                spec, layout, page, groups, out_cap, collect_k
            )
            state_blocks.extend(blocks)
            groups.overflow = groups.overflow | c_ovf
            continue
        blk = None if spec.channel is None else page.block(spec.channel)
        blk = _apply_agg_mask(spec, page, blk)
        if spec.function == "approx_distinct":
            words = HLL.insert(
                groups.group_ids, _hll_contributing(groups, blk),
                out_cap, _hll_hashes(blk),
            )
            state_blocks.append(
                Block(data=words, type=T.HLL_STATE, nulls=None)
            )
            continue
        for st in layout:
            vals, out_nulls, dic = _state_reduce(
                st, blk, st.input_kind, True,
                lambda data, nulls, k=st.input_kind: A.aggregate(
                    groups, k, out_cap, data, nulls
                ),
            )
            state_blocks.append(
                Block(data=vals, type=st.type, nulls=out_nulls,
                      dictionary=dic)
            )
    out = Page(
        blocks=keys_page.blocks + tuple(state_blocks),
        valid=groups.group_valid,
    )
    return out, groups.overflow


def _merge_partials_page(aggregates, layouts, nkeys, merged: Page,
                         cap: int, max_iters: int = 64,
                         collect_k: int = 1024):
    """Merge partial-state pages into one partial-state page (group by
    keys, merge_kind reductions, NO finalize) — the incremental fold that
    keeps aggregation memory bounded (reference: InMemoryHashAggregation-
    Builder flushing partial results under memory pressure)."""
    key_channels = tuple(range(nkeys))
    groups = _group_ids(key_channels, merged, cap, max_iters)
    out_cap = groups.group_valid.shape[0]
    keys_page = _agg_keys_page(merged, key_channels, groups)
    out_blocks: List[Block] = []
    ch = nkeys
    for spec, layout in zip(aggregates, layouts):
        if spec.function in S.COLLECT_FNS:
            blocks, c_ovf = _collect_merge_blocks(
                spec, layout, merged, groups, out_cap, ch, collect_k
            )
            out_blocks.extend(blocks)
            groups.overflow = groups.overflow | c_ovf
            ch += len(layout)
            continue
        if spec.function == "approx_distinct":
            blk = merged.block(ch)
            ch += 1
            words = HLL.merge(
                groups.group_ids, groups.row_valid, out_cap, blk.data
            )
            out_blocks.append(
                Block(data=words, type=T.HLL_STATE, nulls=None)
            )
            continue
        for st in layout:
            blk = merged.block(ch)
            ch += 1
            vals, out_nulls, dic = _state_reduce(
                st, blk, st.merge_kind, False,
                lambda data, nulls, k=st.merge_kind: A.aggregate(
                    groups, k, out_cap, data, nulls
                ),
            )
            out_blocks.append(
                Block(data=vals, type=st.type, nulls=out_nulls,
                      dictionary=dic)
            )
    out = Page(
        blocks=keys_page.blocks + tuple(out_blocks),
        valid=groups.group_valid,
    )
    return out, groups.overflow


def _final_agg_page(group_channels, aggregates, layouts, in_types,
                    merged: Page, cap: int, max_iters: int = 64,
                    collect_k: int = 1024, extra_types=()):
    nkeys = len(group_channels)
    key_channels = tuple(range(nkeys))
    groups = _group_ids(key_channels, merged, cap, max_iters)
    out_cap = groups.group_valid.shape[0]
    keys_page = _agg_keys_page(merged, key_channels, groups)
    out_blocks: List[Block] = []
    ch = nkeys
    for idx, (spec, layout, in_t) in enumerate(
        zip(aggregates, layouts, in_types)
    ):
        if spec.function in S.COLLECT_FNS:
            blocks, c_ovf = _collect_merge_blocks(
                spec, layout, merged, groups, out_cap, ch, collect_k
            )
            groups.overflow = groups.overflow | c_ovf
            ch += len(layout)
            ext = extra_types[idx] if idx < len(extra_types) else ()
            out_blocks.append(
                _collect_finalize_block(spec, in_t, ext, blocks)
            )
            continue
        if spec.function == "approx_distinct":
            blk = merged.block(ch)
            ch += 1
            words = HLL.merge(
                groups.group_ids, groups.row_valid, out_cap, blk.data
            )
            out_blocks.append(
                Block(data=HLL.estimate(words), type=T.BIGINT,
                      nulls=None)
            )
            continue
        states = []
        state_dic = None
        for st in layout:
            blk = merged.block(ch)
            ch += 1
            vals, out_nulls, dic = _state_reduce(
                st, blk, st.merge_kind, False,
                lambda data, nulls, k=st.merge_kind: A.aggregate(
                    groups, k, out_cap, data, nulls
                ),
            )
            state_dic = state_dic or dic
            states.append((vals, out_nulls))
        out_t = S.result_type(spec.function, in_t)
        out_blocks.append(
            _attach_dictionary(
                S.finalize(spec.function, in_t, out_t, states), state_dic
            )
        )
    out = Page(
        blocks=keys_page.blocks + tuple(out_blocks),
        valid=groups.group_valid,
    )
    return out, groups.overflow


def _partial_global_agg(aggregates, layouts, page: Page) -> Page:
    blocks = []
    for spec, layout in zip(aggregates, layouts):
        blk = None if spec.channel is None else page.block(spec.channel)
        blk = _apply_agg_mask(spec, page, blk)
        if spec.function == "approx_distinct":
            contributing = page.valid
            if blk is not None and blk.nulls is not None:
                contributing = contributing & ~blk.nulls
            words = HLL.global_insert(contributing, _hll_hashes(blk))
            blocks.append(
                Block(data=words, type=T.HLL_STATE, nulls=None)
            )
            continue
        for st in layout:
            vals, is_null, dic = _state_reduce(
                st, blk, st.input_kind, True,
                lambda data, nulls, k=st.input_kind: A.global_aggregate(
                    k, page.valid, data, nulls
                ),
            )
            blocks.append(
                Block(
                    data=vals[None].astype(np.dtype(st.type.numpy_dtype)),
                    type=st.type,
                    nulls=is_null[None],
                    dictionary=dic,
                )
            )
    return Page(blocks=tuple(blocks), valid=jnp.ones((1,), dtype=jnp.bool_))


def _final_global_agg(aggregates, layouts, in_types, merged: Page) -> Page:
    out_blocks = []
    ch = 0
    for spec, layout, in_t in zip(aggregates, layouts, in_types):
        if spec.function == "approx_distinct":
            blk = merged.block(ch)
            ch += 1
            words = HLL.global_merge(merged.valid, blk.data)
            out_blocks.append(
                Block(data=HLL.estimate(words), type=T.BIGINT,
                      nulls=None)
            )
            continue
        states = []
        state_dic = None
        for st in layout:
            blk = merged.block(ch)
            ch += 1
            vals, is_null, dic = _state_reduce(
                st, blk, st.merge_kind, False,
                lambda data, nulls, k=st.merge_kind: A.global_aggregate(
                    k, merged.valid, data, nulls
                ),
            )
            state_dic = state_dic or dic
            states.append((vals[None], is_null[None]))
        out_t = S.result_type(spec.function, in_t)
        out_blocks.append(
            _attach_dictionary(
                S.finalize(spec.function, in_t, out_t, states), state_dic
            )
        )
    return Page(blocks=tuple(out_blocks),
                valid=jnp.ones((1,), dtype=jnp.bool_))


def _empty_state_page(aggregates, layouts, collect_k: int = 1024) -> Page:
    blocks = []
    for spec, layout in zip(aggregates, layouts):
        for st in layout:
            if isinstance(st.type, T.CollectStateType):
                blocks.append(
                    Block(
                        data=jnp.zeros((1, collect_k), dtype=jnp.int64),
                        type=st.type,
                        nulls=None,
                    )
                )
                continue
            if isinstance(st.type, T.HllStateType):
                blocks.append(
                    Block(
                        data=tuple(
                            jnp.zeros((1,), dtype=jnp.int64)
                            for _ in range(HLL.WORDS)
                        ),
                        type=st.type,
                        nulls=None,
                    )
                )
                continue
            blocks.append(
                Block(
                    data=jnp.zeros((1,), dtype=np.dtype(st.type.numpy_dtype)),
                    type=st.type,
                    nulls=jnp.ones((1,), dtype=jnp.bool_),
                )
            )
    return Page(blocks=tuple(blocks), valid=jnp.zeros((1,), dtype=jnp.bool_))


def _empty_page(types: List[T.SqlType], cap: int = 8) -> Page:
    blocks = []
    for t in types:
        if isinstance(t, T.DecimalType) and not t.is_short:
            data = (
                jnp.zeros((cap,), dtype=jnp.int64),
                jnp.zeros((cap,), dtype=jnp.int64),
            )
        else:
            data = jnp.zeros((cap,), dtype=np.dtype(t.numpy_dtype))
        dic = Dictionary([]) if t.is_dictionary_encoded else None
        blocks.append(Block(data=data, type=t, nulls=None, dictionary=dic))
    return Page(blocks=tuple(blocks), valid=jnp.zeros((cap,), dtype=jnp.bool_))


def _null_blocks(types: List[T.SqlType], cap: int) -> List[Block]:
    page = _empty_page(types, cap)
    return [
        Block(
            data=b.data,
            type=b.type,
            nulls=jnp.ones((cap,), dtype=jnp.bool_),
            dictionary=b.dictionary,
        )
        for b in page.blocks
    ]


def _replay_filter(predicate, page: Page) -> Page:
    return evaluate_filter(predicate, page, jnp)


def _node_replay_fn(nd):
    """Per-node page->page replay transform for chain re-execution over
    generated pages (None for pass-through nodes like local Exchange) —
    the ONE place chain-replay semantics live."""
    if isinstance(nd, P.Filter):
        return functools.partial(_replay_filter, nd.predicate)
    if isinstance(nd, P.Project):
        return functools.partial(_project_page, nd.exprs)
    return None


def _apply_steps(page: Page, steps, builds=()):
    """Run a fused scan program's step list (Executor._chain_steps,
    plus a partial-aggregation tail) over one generated page: the
    page and the deferred flags of its "joinw" / "aggflag" steps.
    ``builds``: the stored builds its "sjoin" steps probe, in the
    list's order."""
    flags = []
    builds = iter(builds)
    for kind, fn in steps:
        if kind in ("joinw", "aggflag"):
            page, flag = fn(page)
            flags.append(flag)
        elif kind == "sjoin":
            page = fn(page, next(builds))
        else:
            page = fn(page)
    return page, tuple(flags)


def _subtree_has_join(node: P.PhysicalNode) -> bool:
    if isinstance(node, (P.HashJoin, P.CrossJoin)):
        return True
    return any(_subtree_has_join(c) for c in node.children())


def _filter_chain_has_join(node: P.PhysicalNode) -> bool:
    """Whether a Filter(-over-Filter...) chain sits directly on a
    HashJoin — the shape the lazy-filter driver can stream without
    materializing (projects and blocking ops break the chain)."""
    cur = node
    while isinstance(cur, P.Filter):
        cur = cur.source
    return isinstance(cur, P.HashJoin)


def _fused_agg_step(raw, cap, max_iters, page: Page):
    """Partial-agg tail of a fused pipeline (kernel): distinct groups
    <= rows, so the group capacity clips to the page like the unfused
    driver loop does."""
    return raw(page, min(cap, _next_pow2(page.capacity)), max_iters)


def _fused_merge_step(merge_raw, cap, max_iters, page: Page):
    """State-merge step of the split-batched lax.scan (kernel): fold a
    carry + one split's partial states back into the carry capacity.
    The output capacity is a pure function of (cap, key structure) —
    never of the input page's capacity — so the scan carry keeps one
    static shape whether it was seeded from a lone state page or fed
    the concat of carry + state."""
    return merge_raw(page, cap, max_iters)


def _merge_leading(tree):
    """Collapse the leading batch dim of a stacked Page pytree:
    [B, n, ...] leaves become [B*n, ...] — the in-program equivalent
    of concat_all over the B per-split pages a batched launch covers
    (block metadata is static aux data and survives untouched)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
        tree,
    )


def _compact_with_flag(page: Page, cap: int):
    """compact_page plus the dropped-rows overflow flag (kernel)."""
    return (
        compact_page(page, cap),
        page.num_rows() > cap,
    )


def _merge_compact_flag(acc: Page, page: Page, cap: int):
    """Fold one more page into the rolling dense accumulator (kernel):
    concat + stable compaction back to cap, flagging dropped rows."""
    both = concat_all([acc, page])
    return (
        compact_page(both, cap),
        both.num_rows() > cap,
    )


def _extend_with_match(page: Page, bpage: Page, extra_pairs, join_type
                       ) -> Page:
    """The probe page extended in place by its matched build rows
    (kernel; the tail of the generated and the stored joins, whose
    probe rows match at most one build row): ``bpage`` holds the build
    row found for each probe slot, valid where the pivot key matched;
    the non-pivot key pairs are equality checks against it (SQL
    semantics: NULL on either side never matches); a left join keeps
    every probe row with a NULL build side where nothing matched."""
    matched = bpage.valid
    for lk, rk in extra_pairs:
        lblk, rblk = page.block(lk), bpage.block(rk)
        eq = lblk.data.astype(jnp.int64) == rblk.data.astype(jnp.int64)
        if lblk.nulls is not None:
            eq = eq & ~lblk.nulls
        if rblk.nulls is not None:
            eq = eq & ~rblk.nulls
        matched = matched & eq
    if join_type == "left":
        right_blocks = tuple(
            Block(
                data=b.data, type=b.type,
                nulls=(~matched if b.nulls is None
                       else (b.nulls | ~matched)),
                dictionary=b.dictionary,
            )
            for b in bpage.blocks
        )
        out_valid = page.valid
    else:  # inner
        right_blocks = bpage.blocks
        out_valid = page.valid & matched
    return Page(blocks=page.blocks + right_blocks, valid=out_valid)


def _generated_join_page(left_key_ch, extra_pairs, join_type, inv, gen,
                         scan_types, scan_dicts, chain_fns, n_rows,
                         page: Page) -> Page:
    """Build-free generated join (kernel): probe keys -> build table
    rows via the connector's closed-form inverse; carried build columns
    GENERATED at those rows; the build side's Filter/Project chain
    replayed over the generated blocks. Pure per-element compute — the
    output page is the probe page extended in place (<=1 match per
    probe row by the key_inverse uniqueness contract), so capacities
    are exact and no overflow flag exists."""
    kblk = page.block(left_key_ch)
    vals = kblk.data.astype(jnp.int64)
    ridx, found = inv(vals)
    if kblk.nulls is not None:
        found = found & ~kblk.nulls
    idx = jnp.clip(ridx, 0, max(n_rows - 1, 0))
    datas, gvalid = gen(idx)
    blocks = tuple(
        Block(data=d, type=t, nulls=None, dictionary=dic)
        for d, t, dic in zip(datas, scan_types, scan_dicts)
    )
    bpage = Page(blocks=blocks, valid=found & gvalid)
    for fn in chain_fns:
        bpage = fn(bpage)
    return _extend_with_match(page, bpage, extra_pairs, join_type)


def _stored_build_page(reads, scan_types, scan_dicts, chain_fns, key_ch,
                       rows: int, cap: int, riders, datas, valid,
                       *ridden):
    """A stored join's build (kernel): the whole stored table as ONE
    page (its buffers are the program's arguments), the build side's
    Filter / Project chain over it, the probes of the inner joins that
    ride on it (``riders``: each one's (pivot channel, key pairs) on
    this page; ``ridden``: their builds, arguments too), each the
    probe step's own kernel with this page as its probe page, so a
    row whose rider finds no match is dropped here, once, and its
    columns follow this build's own; then a direct-address table over
    the surviving rows' keys: entry ``key - floor`` holds the row's
    index, -1 where no row has the key (``floor``: the least key that
    survived, so the table spans the keys that can match and a filter
    that narrows them narrows it). Returns ((table, floor, page),
    flag): flag where a key lies past the table's ``cap`` entries or
    two rows share a key, which the caller defers to the overflow
    ladder (the retry takes the materialized join)."""
    datas, valid = reads.body(rows, datas, valid)(0)
    page = Page(blocks=tuple(
        Block(data=d, type=t, nulls=None, dictionary=dic)
        for d, t, dic in zip(datas, scan_types, scan_dicts)
    ), valid=valid)
    for fn in chain_fns:
        page = fn(page)
    for (pivot_ch, pairs), rbuilt in zip(riders, ridden):
        page = _stored_join_page(pivot_ch, pairs, "inner", page, rbuilt)
    kblk = page.block(key_ch)
    ok = page.valid
    if kblk.nulls is not None:
        ok = ok & ~kblk.nulls
    keys = kblk.data.astype(jnp.int64)
    floor = jnp.min(jnp.where(ok, keys, jnp.iinfo(jnp.int64).max))
    floor = jnp.where(jnp.any(ok), floor, jnp.int64(0))
    # modular: the true distance wherever key >= floor, huge below it
    off = keys.astype(jnp.uint64) - floor.astype(jnp.uint64)
    inside = ok & (off < jnp.uint64(cap))
    slot = jnp.where(inside, off, jnp.uint64(cap)).astype(jnp.int32)
    rid = jnp.arange(rows, dtype=jnp.int32)
    table = jnp.full((cap,), -1, dtype=jnp.int32).at[slot].set(
        rid, mode="drop")
    # of two rows with one key the scatter keeps one: the other finds
    # a stranger in its entry
    shared = inside & (table[jnp.minimum(slot, cap - 1)] != rid)
    page = Page(blocks=tuple(_carried_wide(b) for b in page.blocks),
                valid=page.valid)
    return (table, floor, page), jnp.any(ok & ~inside) | jnp.any(shared)


def _narrow_int(blk: Block):
    """The block's own integer dtype where it is narrower than 64
    bits (INTEGER, DATE, dictionary codes), else None."""
    if isinstance(blk.data, tuple):
        return None
    try:
        dt = jnp.dtype(blk.type.device_dtype)
    except NotImplementedError:
        return None
    if jnp.issubdtype(dt, jnp.integer) and dt.itemsize < 8:
        return dt
    return None


def _carried_wide(blk: Block) -> Block:
    """A build column as a stored join's probe is handed it: integer
    columns narrower than 64 bits are held as int64. The probe program
    gathers from its ARGUMENTS, and what the TPU compiler does with an
    argument decides the gather's cost: a 64-bit argument is split
    into halves that it keeps in the core's vector memory (7 ns a row
    gathered, the same on every run), a 32-bit argument of this size
    can stay in HBM (23 ns a row, and a level of its own every
    process: PERF.md, PR 44). _carried_narrow undoes it on the
    gathered rows; only the low half is gathered then."""
    dt = _narrow_int(blk)
    if dt is None or blk.data.dtype != dt:
        return blk
    return Block(data=blk.data.astype(jnp.int64), type=blk.type,
                 nulls=blk.nulls, dictionary=blk.dictionary)


def _carried_narrow(blk: Block) -> Block:
    dt = _narrow_int(blk)
    if dt is None or blk.data.dtype != jnp.int64:
        return blk
    return Block(data=blk.data.astype(dt), type=blk.type,
                 nulls=blk.nulls, dictionary=blk.dictionary)


def _stored_join_page(pivot_ch, extra_pairs, join_type, page: Page,
                      build) -> Page:
    """A stored join's probe (kernel), a step of the fused scan
    program: the probe key's entry of the build's direct-address table
    is the build row (exact: an entry is one key's), whose columns are
    gathered from the build page; the other key pairs are equality
    checks on that row. At most one match a probe row, so the output
    is the probe page extended in place, as a generated join's is."""
    table, floor, bpage = build
    cap = table.shape[0]
    kblk = page.block(pivot_ch)
    off = kblk.data.astype(jnp.int64).astype(jnp.uint64) - \
        floor.astype(jnp.uint64)
    bid = table[jnp.minimum(off, jnp.uint64(cap - 1)).astype(jnp.int32)]
    matched = (off < jnp.uint64(cap)) & (bid >= 0)
    if kblk.nulls is not None:
        matched = matched & ~kblk.nulls
    found = gather_rows(bpage, bid, matched)
    found = Page(blocks=tuple(_carried_narrow(b) for b in found.blocks),
                 valid=found.valid)
    return _extend_with_match(page, found, extra_pairs, join_type)


def _generated_join_window_page(left_key_ch, extra_pairs, join_type, inv,
                                window, gen_keys, gen, scan_types,
                                scan_dicts, chain_fns, n_rows,
                                page: Page):
    """Windowed generated join (kernel): the pivot key pins an L-slot
    candidate window of the slot-structured build table; the remaining
    key columns are GENERATED at each candidate to resolve the unique
    matching row, then the full carried columns generate at the
    resolved rows — fact⋈fact joins (ss ⋈ sr on ticket+item) with zero
    build state. Returns (page, multi_flag): multi_flag trips when some
    probe row matched >1 candidates (key set not unique in the data) —
    the caller defers it to the overflow ladder, whose retry takes the
    general expanding join."""
    kblk = page.block(left_key_ch)
    vals = kblk.data.astype(jnp.int64)
    base, found = inv(vals)
    if kblk.nulls is not None:
        found = found & ~kblk.nulls
    probe_extras = []
    for lk, _rk in extra_pairs:
        b = page.block(lk)
        if b.nulls is not None:
            found = found & ~b.nulls
        probe_extras.append(b.data.astype(jnp.int64))
    resolved = jnp.zeros_like(vals)
    any_match = jnp.zeros(vals.shape, dtype=jnp.bool_)
    multi = jnp.zeros(vals.shape, dtype=jnp.bool_)
    for k in range(window):
        cand = jnp.clip(base + k, 0, max(n_rows - 1, 0))
        in_range = (base + k >= 0) & (base + k < n_rows)
        kdatas, kvalid = gen_keys(cand)
        mk = found & kvalid & in_range
        for pv, kd in zip(probe_extras, kdatas):
            mk = mk & (pv == kd.astype(jnp.int64))
        multi = multi | (mk & any_match)
        resolved = jnp.where(mk & ~any_match, cand, resolved)
        any_match = any_match | mk
    datas, gvalid = gen(resolved)
    blocks = tuple(
        Block(data=d, type=t, nulls=None, dictionary=dic)
        for d, t, dic in zip(datas, scan_types, scan_dicts)
    )
    bpage = Page(blocks=blocks, valid=any_match & gvalid)
    for fn in chain_fns:
        bpage = fn(bpage)
    return _extend_with_match(page, bpage, (), join_type), jnp.any(multi)


def _build_join_index(left_keys, right_keys, page: Page, build: Page):
    """One-shot build index (kernel). The probe page supplies the static
    dictionary context for canonical key encodings."""
    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    _lcols, _lnulls, rcols, rnulls = _canonical_join_cols(lblocks, rblocks)
    return J.build_join_index(rcols, rnulls, build.valid)


def _probe_join_page(left_keys, right_keys, join_type, defer,
                     page: Page, build: Page, index, out_cap: int):
    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    lcols, lnulls, _rcols, _rnulls = _canonical_join_cols(lblocks, rblocks)
    m = J.hash_join_match(
        None, None, None, lcols, lnulls, page.valid, out_cap, index=index
    )
    return _assemble_join_output(join_type, page, build, m, defer=defer)


def _probe_join_page_unique(left_keys, right_keys, join_type, defer,
                            page: Page, build: Page, index,
                            out_cap: int):
    """FK-join (unique build keys) probe: no match expansion — the
    output page IS the probe page plus gathered build columns; for
    LEFT joins unmatched probe rows simply carry a null build side in
    the SAME page (no appended pad page). out_cap is ignored (output
    capacity == probe capacity by construction).

    defer=True (late materialization): the build side rides as ONE
    int64 row-id column instead of gathered values — and because the
    output rows ARE the probe rows, any indirections the probe page
    already carries pass through with zero gathers."""
    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    lcols, lnulls, _rcols, _rnulls = _canonical_join_cols(lblocks, rblocks)
    bcols, bvalid, sorted_hash, perm = index
    pcols, p_null = J._fold_nulls(lcols, lnulls, False)
    pvalid = page.valid & ~p_null
    phash = H.hash_columns(pcols, [None] * len(pcols))
    lo = jnp.searchsorted(sorted_hash, phash, side="left", method="sort")
    hi = jnp.searchsorted(sorted_hash, phash, side="right",
                          method="sort")
    bid, found, collision = J.unique_join_lookup(
        bcols, bvalid, perm, pcols, pvalid, lo, hi
    )
    # build_matched feeds only RIGHT/FULL outer emission, which this
    # kernel never serves (inner/left only) — a zeros stub keeps the
    # jit output signature without paying the scatter
    matched = jnp.zeros((build.capacity,), dtype=jnp.bool_)
    if defer:
        if join_type == "left":
            id_block = Block(data=bid, type=T.BIGINT, nulls=~found)
            out_valid = page.valid
        else:  # inner
            id_block = Block(data=bid, type=T.BIGINT, nulls=None)
            out_valid = page.valid & found
        out = Page(blocks=page.blocks + (id_block,), valid=out_valid)
        return out, matched, collision
    right_out = gather_rows(build, bid, found)
    if join_type == "left":
        # matched rows carry build values; unmatched carry NULL build
        right_blocks = tuple(
            Block(
                data=b.data, type=b.type,
                nulls=(~found if b.nulls is None else (b.nulls | ~found)),
                dictionary=b.dictionary,
            )
            for b in right_out.blocks
        )
        out_valid = page.valid
    else:  # inner
        right_blocks = right_out.blocks
        out_valid = page.valid & found
    out = Page(blocks=page.blocks + right_blocks, valid=out_valid)
    return out, matched, collision


def _build_pallas_join_index(left_keys, right_keys, layout, page: Page,
                            build: Page):
    """Pallas join index (kernel): hash-sorted build order + the
    layout-shaped per-unique-hash (start, count) tables. The probe page
    supplies the static dictionary context, as in _build_join_index."""
    from presto_tpu.ops import pallas_join as PJ

    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    _lcols, _lnulls, rcols, rnulls = _canonical_join_cols(lblocks, rblocks)
    bcols, b_null = J._fold_nulls(rcols, rnulls, False)
    bvalid = build.valid & ~b_null
    bhash = H.hash_columns(bcols, [None] * len(bcols))
    tables, perm, overflow = PJ.build_index(bhash, bvalid, layout)
    return (tuple(bcols), bvalid, perm, tables), overflow


def _probe_pallas_join_page(left_keys, right_keys, join_type, layout,
                           interpret, defer, page: Page, build: Page,
                           index, out_cap: int):
    """Probe one page through the Pallas range kernel, then the shared
    verified expansion (J.expand_matches) — identical output contract to
    _probe_join_page; only the range finder differs."""
    from presto_tpu.ops import pallas_join as PJ

    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    lcols, lnulls, _rcols, _rnulls = _canonical_join_cols(lblocks, rblocks)
    bcols, bvalid, perm, tables = index
    pcols, p_null = J._fold_nulls(lcols, lnulls, False)
    pvalid = page.valid & ~p_null
    phash = H.hash_columns(pcols, [None] * len(pcols))
    start, cnt = PJ.probe_index(
        phash, tables, layout, interpret=interpret
    )
    m = J.expand_matches(
        bcols, bvalid, perm, pcols, pvalid,
        jnp.clip(start, 0, None), cnt, out_cap,
    )
    return _assemble_join_output(join_type, page, build, m, defer=defer)


def _assemble_join_output(join_type, page: Page, build: Page,
                          m: J.JoinMatches, defer: bool = False):
    """Expand matches into the output page. defer=True (inner/left
    only) emits ONE int64 build row-id column instead of gathering the
    build blocks — probe columns (including any row-id indirections the
    probe page already carries) gather through probe_idx, which is
    exactly the indirection COMPOSITION of latemat.py."""
    out_valid = m.match
    left_out = gather_rows(page, m.probe_idx, out_valid)
    if defer:
        id_block = Block(
            data=m.build_idx.astype(jnp.int64), type=T.BIGINT,
            nulls=None,
        )
        out = Page(blocks=left_out.blocks + (id_block,),
                   valid=out_valid)
    else:
        right_out = gather_rows(build, m.build_idx, out_valid)
        out = Page(blocks=left_out.blocks + right_out.blocks,
                   valid=out_valid)
    if join_type in ("left", "full"):
        # unmatched probe rows with null build side, appended
        unmatched_valid = page.valid & (m.probe_match_count == 0)
        if defer:
            pad_id = Block(
                data=jnp.zeros((page.capacity,), dtype=jnp.int64),
                type=T.BIGINT,
                nulls=jnp.ones((page.capacity,), dtype=jnp.bool_),
            )
            pad = Page(
                blocks=page.blocks + (pad_id,), valid=unmatched_valid
            )
        else:
            null_right = [
                Block(
                    data=b.data,
                    type=b.type,
                    nulls=jnp.ones((page.capacity,), dtype=jnp.bool_),
                    dictionary=b.dictionary,
                )
                for b in gather_rows(
                    build,
                    jnp.zeros((page.capacity,), dtype=jnp.int64),
                    unmatched_valid,
                ).blocks
            ]
            pad = Page(
                blocks=page.blocks + tuple(null_right),
                valid=unmatched_valid,
            )
        out = concat_all([out, pad])
    return out, m.build_matched, m.overflow


def _unnest_page(array_channel, elem_type, with_ordinality,
                 page: Page) -> Page:
    """Static-shape UNNEST: output capacity = input capacity x L where
    L = max array length over the channel's dictionary (a compile-time
    constant — dictionaries are static aux data). Element values gather
    from a trace-time flat lut; shorter arrays mask out their padding
    (reference: UnnestOperator's per-row element loop, vectorized)."""
    blk = page.block(array_channel)
    dic = blk.dictionary
    vals = [tuple(v) for v in (dic.values if dic is not None else [])]
    n = max(len(vals), 1)
    L = max((len(v) for v in vals), default=0) or 1
    lens = np.zeros((n,), np.int64)
    string_elem = elem_type.is_dictionary_encoded
    if string_elem:
        uniq: dict = {}
        for v in vals:
            for x in v:
                if x is not None:
                    uniq.setdefault(x, len(uniq))
        edic = Dictionary(list(uniq))
        flat = np.zeros((n, L), np.int32)
    else:
        edic = None
        flat = np.zeros((n, L), np.dtype(elem_type.numpy_dtype))
    enull = np.ones((n, L), bool)
    for vi, v in enumerate(vals):
        lens[vi] = len(v)
        for k, x in enumerate(v):
            if x is None:
                continue
            enull[vi, k] = False
            flat[vi, k] = uniq[x] if string_elem else x
    cap = page.capacity
    idx = jnp.arange(cap * L, dtype=jnp.int64)
    i, k = idx // L, idx % L
    codes = jnp.clip(blk.data.astype(jnp.int64), 0, n - 1)[i]
    # xfercheck: raw-ok - trace-time LUT embedding
    valid = page.valid[i] & (k < jnp.asarray(lens)[codes])
    if blk.nulls is not None:
        valid = valid & ~blk.nulls[i]
    src = gather_rows(page, i, valid)
    eblock = Block(
        # xfercheck: raw-ok - trace-time LUT embedding
        data=jnp.asarray(flat)[codes, k],
        type=elem_type,
        # xfercheck: raw-ok - trace-time LUT embedding
        nulls=jnp.asarray(enull)[codes, k],
        dictionary=edic,
    )
    blocks = src.blocks + (eblock,)
    if with_ordinality:
        blocks += (Block(data=k + 1, type=T.BIGINT, nulls=None),)
    return Page(blocks=blocks, valid=valid)


def _group_id_page(key_channels, mask, set_index, page: Page) -> Page:
    """One grouping-set replica: null out keys absent from the set and
    append the constant gid channel."""
    blocks = list(page.blocks)
    for kc, keep in zip(key_channels, mask):
        if not keep:
            b = blocks[kc]
            blocks[kc] = Block(
                data=b.data, type=b.type,
                nulls=jnp.ones((page.capacity,), dtype=jnp.bool_),
                dictionary=b.dictionary,
            )
    gid = Block(
        data=jnp.full((page.capacity,), set_index, dtype=jnp.int64),
        type=T.BIGINT,
    )
    return Page(blocks=tuple(blocks) + (gid,), valid=page.valid)


def _cross_join_page(page: Page, build: Page) -> Page:
    nb = build.capacity
    out_cap = page.capacity * nb
    idx = jnp.arange(out_cap, dtype=jnp.int64)
    li = idx // nb
    ri = idx % nb
    valid = page.valid[li] & build.valid[ri]
    left = gather_rows(page, li, valid)
    right = gather_rows(build, ri, valid)
    return Page(blocks=left.blocks + right.blocks, valid=valid)


def _semi_join_page(left_keys, right_keys, page: Page, build: Page) -> Page:
    lblocks = [page.block(c) for c in left_keys]
    rblocks = [build.block(c) for c in right_keys]
    lcols, lnulls, rcols, rnulls = _canonical_join_cols(lblocks, rblocks)
    has_match, null_result = J.semi_join_mask(
        rcols, rnulls, build.valid, lcols, lnulls, page.valid
    )
    match_block = Block(
        data=has_match, type=T.BOOLEAN, nulls=null_result
    )
    return Page(blocks=page.blocks + (match_block,), valid=page.valid)


def _topn_merge(sort_keys, limit, running: Page, local: Page) -> Page:
    both = concat_all([running, local])
    return sort_page(both, sort_keys=sort_keys, limit=limit)


def _limit_with_count(count, offset, page: Page, consumed):
    """LIMIT across pages with the running total carried as a traced
    device scalar (reference: LimitOperator's remaining counter)."""
    rank = jnp.cumsum(page.valid.astype(jnp.int64)) - 1 + consumed
    keep = page.valid & (rank >= offset) & (rank < offset + count)
    return (
        page.with_valid(keep),
        consumed + jnp.sum(page.valid.astype(jnp.int64)),
    )


def _decode_result_page(page: Page) -> List[tuple]:
    """Decode device rows to Python values, normalizing engine-internal
    encodings (decimal unscaled ints -> Decimal strings stay as ints here;
    clients format)."""
    return page.to_pylist()
