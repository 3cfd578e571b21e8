"""Worker process: the /v1/task control plane + page-buffer data plane.

Reference: presto-main server/TaskResource.java (task create/status/
cancel), execution/SqlTaskManager.java (task registry + execution),
execution/buffer/OutputBuffer (token-indexed page buffer consumed by
HttpPageBufferClient with at-least-once + token-dedupe semantics).

The TPU-native shape: one worker process = one host driving its local
devices. A task carries a SERIALIZED physical-plan fragment
(dist/plan_serde.py — the reference's TaskUpdateRequest PlanFragment)
plus a split assignment; the worker deserializes and executes exactly
the subtree the coordinator planned, restricted to its split share
(round-robin or hash-co-partitioned scans), and buffers serialized
pages (dist/serde.py) for token-indexed fetch. Legacy peers may still
send (sql, role) for worker-side replay.

Stage-DAG tasks (dist/scheduler.py) extend the same surface with a
SPOOLED-EXCHANGE plane (reference: Project Tardigrade's spooled
shuffle, PartitionedOutputOperator + ExchangeClient):

  - a task whose payload carries ``outputPartitions``/``outputKeys``
    hash-partitions every result page host-side (dist/spool.py) and
    publishes the serialized partitions into PageStore host/disk tiers
    (_TaskSpool) — partition buffers OUTLIVE execution, so a lost
    downstream task replays from its upstream spools;
  - a task whose payload carries ``sources`` registers RemoteSource
    suppliers that fetch its input partitions from upstream tasks'
    spools over HTTP (worker-to-worker exchange — the coordinator
    never relays inter-stage pages);
  - ``GET /v1/task/{id}/results/{token}?part=p`` fetches one spool
    partition token-indexed; ``DELETE /v1/task/{id}/spool/{p}`` acks
    (releases) a consumed partition.

Route handling is factored into module-level ``route_task_*``
functions so the coordinator HTTP server can serve the same task +
spool data plane in-process (a coordinator+worker single-process
deployment, server/http_server.py).

Fault-injection hooks (SURVEY §6.3: inject at the host page proxy —
ICI collectives cannot be faulted): FAULT_DELAY_MS delays every
results fetch; FAULT_DROP_EVERY=n returns HTTP 500 on every nth fetch;
FAULT_KILL_AFTER_FETCHES=n hard-exits the worker PROCESS once n result
fetches have been served (worker death mid-query — the coordinator's
task-retry path re-dispatches the fragment to a survivor);
FAULT_SUBMIT_DROP_EVERY=n returns HTTP 500 on every nth task submit
(exercises the coordinator's submit retry);
FAULT_TASK_EXEC_DELAY_MS stalls task EXECUTION (a deterministic
straggler for the stage scheduler's speculation policy);
FAULT_SPOOL_CORRUPT_EVERY=n bit-flips a byte inside every nth served
results body (framing intact, page content corrupt — proves the
consumer-side PageWireError loud-fail + replay ladder end to end).
Each knob reads the
runtime `fault_config` posted via POST /v1/fault as an OVERLAY on the
environment: posted keys win (an explicit 0 disables an env-seeded
fault), absent keys fall back to the environment, and `{}` restores
pure env-ruled mode (tools/chaos.py reconfigures live workers between
iterations without reboots). Token-indexed re-fetch makes drops
recoverable
(at-least-once); kills are recoverable only with task_retry_attempts>0.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from presto_tpu.connectors.split_filter import SplitFilterConnector
from presto_tpu.dist import serde
from presto_tpu.exec import plan as P
from presto_tpu.exec import xfer as XF
from presto_tpu.obs import sanitizer as SAN
from presto_tpu.obs.sanitizer import make_lock, register_owner
from presto_tpu.session import Session


class _PartitionSpool:
    """One partition's spooled output: host-tier PageStore blobs while
    the task's resident budget lasts, disk-tier PageStore past it (the
    FileSingleStreamSpiller analog for exchange pages) — plus, on the
    device-exchange tier (ISSUE 13), LAZY entries holding the
    partitioned Page itself (device- or host-resident): same-process
    consumers take the Page with no serde at all, and wire bytes
    materialize only when an HTTP fetch (a DCN-remote consumer or a
    replay) actually needs them (dist/spool.spool_blob, metered d2h).
    Entries are (store, index) for materialized blobs and
    ("page", Page, est_bytes) for lazy ones."""

    def __init__(self, spill_dir: Optional[str] = None):
        from presto_tpu.exec.pagestore import PageStore

        self._host = PageStore(tier="host")
        self._disk: Optional[PageStore] = None
        self._spill_dir = spill_dir
        self._entries: List = []  # (store, index) | ("page", p, est)
        self._page_bytes = 0
        self.released = False
        # spool-stats plane (ISSUE 15): EXACT rows/bytes published
        # into this partition, accumulated at put time and MONOTONE —
        # they survive release/close so the coordinator's adaptive
        # re-planner reads stable numbers whenever it asks, and a
        # replayed task re-accumulates identical values (the spool
        # content is deterministic)
        self.stat_rows = 0
        self.stat_bytes = 0
        # measured post-codec wire bytes (ISSUE 17): blob-tier entries
        # count their actual serialized length; device-resident pages
        # never serialized, so they count their raw footprint (an
        # upper bound — freight costing must never under-count)
        self.stat_wire_bytes = 0

    def put(self, blob: bytes, to_disk: bool, rows: int = 0) -> None:
        from presto_tpu.exec.pagestore import PageStore

        if to_disk:
            if self._disk is None:
                self._disk = PageStore(tier="disk",
                                       spill_dir=self._spill_dir)
            store = self._disk
        else:
            store = self._host
        store.put_bytes(blob)
        self._entries.append((store, store.page_count - 1))
        self.stat_rows += int(rows)
        self.stat_bytes += len(blob)
        self.stat_wire_bytes += len(blob)

    def put_page(self, page, est_bytes: int, rows: int = 0) -> None:
        """Spool one partitioned Page WITHOUT serializing (the device-
        resident tier). est_bytes is the static page footprint — the
        resident-budget accounting the blob tier does by len(blob)."""
        self._entries.append(("page", page, est_bytes))
        self._page_bytes += est_bytes
        self.stat_rows += int(rows)
        self.stat_bytes += int(est_bytes)
        self.stat_wire_bytes += int(est_bytes)

    def blob(self, token: int) -> bytes:
        entry = self._entries[token]
        if entry[0] == "page":
            # lazy host materialization: deterministic serialization,
            # so a token re-fetch or a verified replay prefix reads
            # byte-identical wire data (no caching — re-fetches are
            # the rare retry path, and an uncached serialize keeps the
            # entry list free of cross-thread mutation)
            from presto_tpu.dist import spool as SPOOL

            return SPOOL.spool_blob(entry[1])
        store, i = entry
        return store.blob_at(i)

    @property
    def count(self) -> int:
        return len(self._entries)

    @property
    def bytes(self) -> int:
        return (self._host.bytes + self._page_bytes
                + (self._disk.bytes if self._disk else 0))

    def close(self) -> None:
        self._host.close()
        if self._disk is not None:
            self._disk.close()
        self._entries = []  # drops lazy Page refs -> frees HBM
        self._page_bytes = 0
        self.released = True


class _TaskSpool:
    """A task's partitioned output spool: P token-indexed partition
    buffers sharing one resident-byte budget (the spool_exchange_bytes
    session property) — blobs past it go to the disk tier."""

    def __init__(self, nparts: int, host_budget: int,
                 spill_dir: Optional[str] = None):
        self.parts = [_PartitionSpool(spill_dir)
                      for _ in range(max(nparts, 1))]
        self.host_budget = host_budget
        self.host_bytes = 0

    def put(self, p: int, blob: bytes, rows: int = 0) -> None:
        to_disk = (self.host_budget > 0
                   and self.host_bytes + len(blob) > self.host_budget)
        if not to_disk:
            self.host_bytes += len(blob)
        self.parts[p].put(blob, to_disk, rows=rows)

    def put_page(self, p: int, page, rows: int = 0) -> None:
        """Device-exchange tier: spool the partitioned Page itself.
        The spool_exchange_bytes budget bounds RESIDENT bytes across
        tiers — a page past it materializes eagerly (spool_blob) and
        rides the existing blob demotion to disk, so device-resident
        spools can never hold more HBM than the knob allows."""
        from presto_tpu.exec.executor import page_bytes

        est = page_bytes(page)
        if self.host_budget > 0 and self.host_bytes + est > \
                self.host_budget:
            from presto_tpu.dist import spool as SPOOL

            self.put(p, SPOOL.spool_blob(page), rows=rows)
            return
        self.host_bytes += est
        self.parts[p].put_page(page, est, rows=rows)

    @property
    def page_count(self) -> int:
        return sum(p.count for p in self.parts)

    @property
    def byte_count(self) -> int:
        return sum(p.bytes for p in self.parts)

    def part_stats(self) -> Tuple[List[int], List[int], List[int]]:
        """(rows, bytes, wire bytes) per partition — the stage-
        boundary stats the adaptive re-planner sums coordinator-side
        (ISSUE 15; wire bytes ISSUE 17). Exact and monotone:
        accumulated at publish time, stable across release and
        identical after a deterministic replay."""
        return ([p.stat_rows for p in self.parts],
                [p.stat_bytes for p in self.parts],
                [p.stat_wire_bytes for p in self.parts])

    def release(self, p: int) -> bool:
        if 0 <= p < len(self.parts):
            self.parts[p].close()
            return True
        return False

    def close(self) -> None:
        for p in self.parts:
            p.close()


# --------------------------------------------------------------------
# Same-process placement registry (ISSUE 13): uri -> TaskRuntime for
# every task runtime served from THIS process (in-process WorkerServer
# threads, the coordinator's embedded worker_tasks runtime). The
# mesh-local exchange fast path — dist/spool.iter_source_pages and the
# stage scheduler's root drain — looks placements up here and takes
# spooled Pages directly (no HTTP, no serde, no h2d re-stage for
# device-resident spools). Subprocess workers never appear: the
# registry is per-process by construction, so a remote placement
# always falls back to the metered HTTP + lazy-materialization path.
_runtimes_lock = make_lock("server.worker._runtimes_lock")
_LOCAL_RUNTIMES: Dict[str, "TaskRuntime"] = {}


def register_local_runtime(uri: str, rt: "TaskRuntime") -> None:
    with _runtimes_lock:
        _LOCAL_RUNTIMES[uri] = rt


def unregister_local_runtime(uri: str) -> None:
    with _runtimes_lock:
        _LOCAL_RUNTIMES.pop(uri, None)


def local_runtime(uri: str) -> Optional["TaskRuntime"]:
    with _runtimes_lock:
        return _LOCAL_RUNTIMES.get(uri)


class _Task:
    # lock discipline (tools/lint `locks` rule): lifecycle flags and
    # result buffers shared between the execution thread and the
    # fetch/status/cancel handlers — written under self.lock (the
    # writes live in TaskRuntime/route_* but the contract is the
    # task's; the runtime sanitizer enforces it per instance)
    _shared_attrs = ("pages", "spool", "done", "error", "cancelled",
                     "spans", "boost_retries", "skew_preempted")

    def __init__(self, task_id: str):
        self.task_id = task_id
        self.pages: List[bytes] = []
        self.spool: Optional[_TaskSpool] = None
        self.done = False
        self.error: Optional[str] = None
        self.cancelled = False
        # per-task executor outcomes shipped on the status plane
        # (ISSUE 15): overflow-ladder re-entries and pre-engaged skew
        # chunking, mirrored onto the coordinator's registry counters
        # so "first-run boosts driven to zero" is visible where the
        # adaptive re-planner's own counters live
        self.boost_retries = 0
        self.skew_preempted = 0
        self.lock = make_lock("server.worker._Task.lock")
        # lifecycle tracing (ISSUE 9): interval math on monotonic,
        # ONE wall anchor for cross-node correlation — the span
        # timing-source rule (obs/trace.py docstring)
        self.created_mono = time.monotonic()
        self.created_wall = time.time()
        # worker-side spans (queue/run/attempt), exported as offsets
        # from created_mono and shipped to the coordinator on the
        # status plane so it can assemble one cross-node timeline
        self.spans: Optional[List[Dict]] = None
        register_owner(self, lock_attrs=("lock",))

    # --------- unified read surface (legacy byte list OR spool tiers)
    def part_count(self, part: int) -> int:
        if self.spool is not None:
            if part >= len(self.spool.parts):
                return 0
            return self.spool.parts[part].count
        return len(self.pages) if part == 0 else 0

    def part_blob(self, part: int, token: int) -> bytes:
        if self.spool is not None:
            return self.spool.parts[part].blob(token)
        return self.pages[token]

    def part_released(self, part: int) -> bool:
        return (self.spool is not None
                and 0 <= part < len(self.spool.parts)
                and self.spool.parts[part].released)

    def total_pages(self) -> int:
        if self.spool is not None:
            return self.spool.page_count
        return len(self.pages)

    def free(self) -> None:
        self.pages.clear()
        if self.spool is not None:
            self.spool.close()


def find_partial_cut(plan: P.PhysicalNode) -> Optional[P.Aggregation]:
    """The topmost single-step aggregation — the PARTIAL/FINAL split
    point for the DCN boundary (reference: AddExchanges splitting
    AggregationNode into PARTIAL below / FINAL above the exchange)."""
    if isinstance(node := plan, P.Aggregation) and node.step == "single":
        return node
    for c in plan.children():
        hit = find_partial_cut(c)
        if hit is not None:
            return hit
    return None


def row_local_scan_count(node: P.PhysicalNode,
                         split_table: str) -> Optional[int]:
    """How many times ``split_table`` is scanned under ``node``, or
    None when the subtree is not ROW-LOCAL — i.e. when the multiset of
    its output rows is NOT the disjoint union of the outputs over a
    row-partition of split_table (all other tables replicated).

    Row-local shapes: Filter / Project / Exchange / TableScan / INNER
    hash joins. Inner joins distribute over a partition of any single
    table (each result row maps to exactly one row of it); outer/semi/
    anti/cross joins, aggregations, sorts, limits, windows, and
    MarkDistinct do not (a MarkDistinct would mark first-occurrence
    per worker and double-count values spanning workers)."""
    if isinstance(node, P.TableScan):
        return 1 if node.table == split_table else 0
    if isinstance(node, (P.Filter, P.Project, P.Exchange)):
        return row_local_scan_count(node.source, split_table)
    if isinstance(node, P.HashJoin):
        if node.join_type != "inner":
            return None
        left = row_local_scan_count(node.left, split_table)
        right = row_local_scan_count(node.right, split_table)
        if left is None or right is None:
            return None
        return left + right
    return None


def fanout_safe(cut: P.Aggregation, split_table: str) -> bool:
    """Whether the PARTIAL subtree distributes over a round-robin
    partition of split_table's rows: decomposable aggregates with no
    DISTINCT masks, and a row-local source with exactly ONE scan of
    the split table (see row_local_scan_count). Queries outside this
    shape use the union-cut fallback (find_union_cut) or run local."""
    if any(s.mask is not None for s in cut.aggregates):
        return False
    return row_local_scan_count(cut.source, split_table) == 1


def find_union_cut(plan: P.PhysicalNode,
                   split_table: str) -> Optional[P.PhysicalNode]:
    """The TOPMOST row-local subtree scanning split_table exactly once
    — the general distribution shape for plans with no decomposable
    aggregation cut (reference: a SOURCE_DISTRIBUTION leaf fragment
    under a GATHER exchange; SqlQueryScheduler runs the leaf stage on
    every worker and the coordinator consumes the union). Workers
    execute the subtree over their split share; the coordinator
    replaces it with a RemoteSource and runs everything above (sort /
    topN / window / non-decomposable aggregation) over the unioned
    pages. Returns None when no useful cut exists (a bare scan or a
    pure projection of one is not worth shipping: generation is
    cheaper than the wire — the cut must contain a join or filter)."""

    def has_work(n) -> bool:
        if isinstance(n, (P.HashJoin, P.Filter)):
            return True
        return any(has_work(c) for c in n.children())

    n = row_local_scan_count(plan, split_table)
    if n == 1 and has_work(plan):
        return plan
    for c in plan.children():
        hit = find_union_cut(c, split_table)
        if hit is not None:
            return hit
    return None


def hash_fanout_plan(cut: P.Aggregation, catalogs,
                     partition_threshold: int = 1 << 17):
    """Co-partitioning spec for a PARTITIONED JOIN fan-out below an
    aggregation cut; decomposability of the aggregates follows
    fanout_safe's rules (no DISTINCT masks). See hash_fanout_source."""
    if any(s.mask is not None for s in cut.aggregates):
        return None
    return hash_fanout_source(cut.source, catalogs,
                              partition_threshold)


def hash_fanout_source(root: P.PhysicalNode, catalogs,
                       partition_threshold: int = 1 << 17):
    """Co-partitioning spec for a PARTITIONED JOIN fan-out (the DCN
    hash-repartition exchange; reference: AddExchanges choosing
    REPARTITION and inserting hash exchanges on both join sides).

    Returns {table: partition_column} covering every BIG scanned table
    (row_count >= partition_threshold), or None when the shape does
    not co-partition. Valid shape under ``root``: Filter / Project /
    Exchange / TableScan / INNER hash joins; every join with big
    tables on BOTH sides must equi-join on single keys that are
    provably those tables' columns (exec/plan.scan_column_of), and
    each big table must receive exactly ONE partition column; small
    tables replicate (broadcast side)."""
    parts: dict = {}
    state = {"ok": True}

    def big_tables_under(n) -> set:
        out = set()

        def walk(x):
            if isinstance(x, P.TableScan):
                if catalogs[x.catalog].row_count(x.table) >= \
                        partition_threshold:
                    out.add(x.table)
                return
            for c in x.children():
                walk(c)

        walk(n)
        return out

    def assign(table: str, column: str):
        if parts.get(table, column) != column:
            state["ok"] = False  # conflicting partition keys
        parts[table] = column

    def walk(n):
        if not state["ok"]:
            return
        if isinstance(n, (P.Filter, P.Project, P.Exchange,
                          P.TableScan)):
            for c in n.children():
                walk(c)
            return
        if isinstance(n, P.HashJoin):
            if n.join_type != "inner":
                state["ok"] = False
                return
            left_big = big_tables_under(n.left)
            right_big = big_tables_under(n.right)
            if left_big and right_big:
                # partitioned join: both sides keyed by their own
                # table columns, co-partitioned on this equi-key
                if len(n.left_keys) < 1:
                    state["ok"] = False
                    return
                lsrc = P.scan_column_of(n.left, n.left_keys[0])
                rsrc = P.scan_column_of(n.right, n.right_keys[0])
                if lsrc is None or rsrc is None:
                    state["ok"] = False
                    return
                # dictionary codes are table-local (same rule as
                # executor._keys_partitionable): equal string values
                # would hash to different workers on each side —
                # refuse string/dictionary-typed partition keys
                from presto_tpu import types as T

                for cat, table, col in (lsrc, rsrc):
                    t = catalogs[cat].table_schema(
                        table).column_type(col)
                    if T.is_string(t) or t.is_dictionary_encoded:
                        state["ok"] = False
                        return
                # the key must constrain EVERY big table on its side —
                # a second big table not keyed by this join cannot be
                # co-partitioned
                if left_big != {lsrc[1]} or right_big != {rsrc[1]}:
                    state["ok"] = False
                    return
                assign(f"{lsrc[0]}.{lsrc[1]}", lsrc[2])
                assign(f"{rsrc[0]}.{rsrc[1]}", rsrc[2])
            walk(n.left)
            walk(n.right)
            return
        state["ok"] = False

    walk(root)
    if not state["ok"] or len(parts) < 2:
        return None
    return parts


def largest_table(node: P.PhysicalNode, catalogs) -> Optional[str]:
    """The fact table to split across workers: the scanned table with
    the most rows under this subtree (SOURCE_DISTRIBUTION pick)."""
    tables = []

    def scans(n):
        if isinstance(n, P.TableScan):
            tables.append((n.catalog, n.table))
        for ch in n.children():
            scans(ch)

    scans(node)
    if not tables:
        return None
    return max(
        tables, key=lambda ct: catalogs[ct[0]].row_count(ct[1])
    )[1]


# ---------------------------------------------------------------------
# Task-plane routing, shared between the worker's own HTTP server and
# the coordinator server (http_server.py delegates /v1/task* and
# /v1/fault here when constructed with a task runtime). A response is
# (status, headers_list, content_type, body_bytes); None means "not a
# task-plane path".

_JSON_CT = "application/json"
_PAGES_CT = "application/x-presto-pages"


def _jresp(obj, status=200, headers=()):
    return (status, list(headers), _JSON_CT, json.dumps(obj).encode())


def write_task_response(handler, resp) -> None:
    """Render a (status, headers, content_type, body) route result on
    a BaseHTTPRequestHandler — ONE renderer for both the worker's own
    handler and the coordinator's delegating handler, so the task
    plane cannot drift between the two servers."""
    status, headers, ctype, body = resp
    handler.send_response(status)
    handler.send_header("Content-Type", ctype)
    if status != 204:
        handler.send_header("Content-Length", str(len(body)))
    for k, v in headers:
        handler.send_header(k, v)
    handler.end_headers()
    if status != 204 and body:
        handler.wfile.write(body)


def route_task_post(app, path: str, body: bytes):
    if path.startswith("/v1/fault"):
        # runtime fault reconfiguration (chaos harness): the posted
        # overlay replaces the previous one; {} clears every RUNTIME
        # fault and restores env-ruled mode
        app.set_fault_config({
            k: int(v) for k, v in json.loads(body or b"{}").items()
        })
        return _jresp({"ok": True, "fault": app.fault_config})
    if path.startswith("/v1/cache/task"):
        # fleet cache probe (ISSUE 19, dist/cacheprobe.py): serve one
        # fragment key from THIS process's result cache by parking
        # the cached host pages in a pre-finished task spool — the
        # consumer then fetches them over the ordinary pooled
        # spool-fetch plane, indistinguishable from an executed task
        req = json.loads(body)
        return _jresp(app.serve_cached_fragment(
            str(req.get("taskId") or ""), str(req.get("key") or "")))
    if not path.startswith("/v1/task"):
        return None
    if app.maybe_inject_submit_fault():
        return _jresp({"error": "injected submit fault"}, 500)
    req = json.loads(body)
    task = app.create_task(req)
    return _jresp({"taskId": task.task_id, "state": "RUNNING"})


def route_task_get(app, path: str, query: str):
    from urllib.parse import parse_qs

    parts = [p for p in path.split("/") if p]
    # /v1/task/{id}/results/{token}[?part=p][&max=bytes]
    if len(parts) == 5 and parts[:2] == ["v1", "task"] \
            and parts[3] == "results":
        task = app.get_task(parts[2])
        if task is None:
            return _jresp({"error": "no such task"}, 404)
        token = int(parts[4])
        qs = parse_qs(query or "")
        part = int(qs.get("part", ["0"])[0])
        # ?max engages the streaming/ranged response (ISSUE 16): up
        # to `max` bytes of CONSECUTIVE page frames ship in one
        # framed body (dist/spool.pack_frames) so the consumer drains
        # a partition page-at-a-time under a bounded in-flight-bytes
        # window. Absent ?max, the legacy single-blob shape is served
        # unchanged.
        max_bytes = int(qs.get("max", ["0"])[0])
        if app.maybe_inject_fault():
            return _jresp({"error": "injected fault"}, 500)
        # bounded long-poll until the page at `token` exists or the
        # task finishes (reference: HttpPageBufferClient long-poll).
        # Monotonic, not wall: an NTP step mid-poll must not stretch
        # or collapse the window (ISSUE 9 timing-source audit)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            entry = blob = None
            with task.lock:
                if task.error:
                    # X-Task-Error marks a DETERMINISTIC task failure
                    # (the fragment itself failed, not the transport):
                    # consumers surface the real message instead of
                    # spinning fetch retries against a dead task
                    return _jresp({"error": task.error}, 500,
                                  headers=(("X-Task-Error", "1"),))
                if task.part_released(part):
                    return _jresp(
                        {"error": f"spool partition {part} released "
                                  f"(already acked)"}, 410)
                if token < task.part_count(part):
                    if task.spool is not None:
                        # resolve under the lock, READ outside it: a
                        # disk-tier blob read must not serialize the
                        # other partitions' consumers and the status
                        # polls behind one file read
                        entry = (task.spool.parts[part]
                                 ._entries[token])
                    else:
                        blob = task.pages[token]
                elif task.done:
                    return (204, [("X-Done", "1")], _JSON_CT, b"")
            if entry is not None:
                try:
                    if entry[0] == "page":
                        # device-resident spool entry: lazy host
                        # materialization happens HERE, outside the
                        # task lock (a d2h + serialize under the lock
                        # would serialize every other consumer — the
                        # concheck blocking-under-lock rule)
                        from presto_tpu.dist import spool as SPOOL

                        blob = SPOOL.spool_blob(entry[1])
                    else:
                        store, i = entry
                        blob = store.blob_at(i)
                except (OSError, IndexError):
                    # raced a concurrent ack/release of this partition
                    return _jresp(
                        {"error": f"spool partition {part} released "
                                  f"(already acked)"}, 410)
            if blob is not None:
                # fault injection point: the flip lands INSIDE one
                # page body (framing stays intact), so the consumer's
                # decode — not the transport — catches it
                blob = app.maybe_corrupt_blob(blob)
                if max_bytes <= 0:
                    # legacy single-blob response shape
                    return (200, [("X-Next-Token", str(token + 1)),
                                  ("X-Done", "0")], _PAGES_CT, blob)
                from presto_tpu.dist import spool as SPOOL

                # streaming/ranged response: extend with CONSECUTIVE
                # ready frames until the byte window fills. Frames
                # stop once the total reaches max_bytes, so one
                # response carries at most window + one page — the
                # consumer's bounded in-flight-bytes contract. Extra
                # frames are best-effort: any race (ack, store close)
                # just ends the range and the next request sees the
                # canonical 410/204 answer.
                frames = [blob]
                total = 8 + len(blob)
                while total < max_bytes:
                    nxt = token + len(frames)
                    entry2 = blob2 = None
                    with task.lock:
                        if task.error or task.part_released(part):
                            break
                        if nxt >= task.part_count(part):
                            break
                        if task.spool is not None:
                            entry2 = (task.spool.parts[part]
                                      ._entries[nxt])
                        else:
                            blob2 = task.pages[nxt]
                    if entry2 is not None:
                        try:
                            if entry2[0] == "page":
                                blob2 = SPOOL.spool_blob(entry2[1])
                            else:
                                store, i = entry2
                                blob2 = store.blob_at(i)
                        except (OSError, IndexError):
                            break
                    if blob2 is None:
                        break
                    frames.append(blob2)
                    total += 8 + len(blob2)
                return (200,
                        [("X-Next-Token", str(token + len(frames))),
                         ("X-Done", "0"),
                         ("X-Frames", str(len(frames)))],
                        _PAGES_CT, SPOOL.pack_frames(frames))
            time.sleep(0.02)
        return (204, [("X-Done", "0")], _JSON_CT, b"")
    if len(parts) == 3 and parts[:2] == ["v1", "task"]:
        task = app.get_task(parts[2])
        if task is None:
            return _jresp({"error": "no such task"}, 404)
        with task.lock:
            spool = task.spool
            body = {
                "taskId": task.task_id,
                "state": ("FAILED" if task.error else
                          "FINISHED" if task.done else "RUNNING"),
                "pages": task.total_pages(),
                "spooledPages": spool.page_count if spool else 0,
                "spooledBytes": spool.byte_count if spool else 0,
                "partitions": len(spool.parts) if spool else 1,
                "error": task.error,
                # spool-stats plane (ISSUE 15): exact per-partition
                # row/byte counts + executor outcomes, summed
                # coordinator-side at the stage boundary — the input
                # the adaptive re-planner re-optimizes from
                "boostRetries": task.boost_retries,
                "skewPreempted": task.skew_preempted,
            }
            if spool is not None:
                rows, nbytes, wire = spool.part_stats()
                body["spoolRows"] = rows
                body["spoolBytes"] = nbytes
                body["spoolWireBytes"] = wire
            if task.spans is not None:
                # worker-side spans for the coordinator's cross-node
                # timeline: offsets from this task's creation, plus
                # the worker's wall anchor for correlation only
                body["spans"] = task.spans
                body["wallAnchor"] = task.created_wall
            return _jresp(body)
    return None


def route_task_delete(app, path: str):
    parts = [p for p in path.split("/") if p]
    # /v1/task/{id}/spool/{part}: ack (release) one consumed spool
    # partition — partition-granular buffer release so long queries
    # can return exchange memory before the whole task expires
    if len(parts) == 5 and parts[:2] == ["v1", "task"] \
            and parts[3] == "spool":
        task = app.get_task(parts[2])
        if task is None:
            return _jresp({"error": "no such task"}, 404)
        with task.lock:
            ok = (task.spool is not None
                  and task.spool.release(int(parts[4])))
        if ok:
            return _jresp({"taskId": task.task_id,
                           "partition": int(parts[4]),
                           "state": "RELEASED"})
        return _jresp({"error": "no such spool partition"}, 404)
    if len(parts) == 3 and parts[:2] == ["v1", "task"]:
        task = app.pop_task(parts[2])
        if task is not None:
            with task.lock:
                # under the task lock like every other lifecycle-flag
                # write (the execution thread polls it between pages)
                task.cancelled = True
                task.free()  # page buffers + spool tiers
            return _jresp({"taskId": task.task_id,
                           "state": "CANCELED"})
    return None


class _WorkerHandler(BaseHTTPRequestHandler):
    server_version = "presto-tpu-worker/0.3"
    # HTTP/1.1 so the shuffle plane's pooled clients
    # (dist/connpool.py) get keep-alive for real; every response path
    # sends Content-Length (write_task_response; 204s ship no body).
    # The socket timeout bounds how long an idle keep-alive handler
    # thread lingers after its client forgets it.
    protocol_version = "HTTP/1.1"
    timeout = 120

    def log_message(self, fmt, *args):  # noqa: A003
        pass

    @property
    def app(self) -> "WorkerServer":
        return self.server.app  # type: ignore[attr-defined]

    def _write(self, resp) -> None:
        write_task_response(self, resp)

    def do_POST(self):
        n = int(self.headers.get("Content-Length", "0"))
        body = self.rfile.read(n) or b"{}"
        resp = route_task_post(self.app, self.path, body)
        self._write(resp if resp is not None
                    else _jresp({"error": "not found"}, 404))

    def do_GET(self):
        from urllib.parse import urlsplit

        split = urlsplit(self.path)
        if split.path.startswith("/v1/info"):
            info = {
                "nodeId": self.app.node_id,
                "state": "ACTIVE",
                "uptime_s": round(
                    time.monotonic() - self.app.started_mono, 1),
                "tasks": self.app.task_count(),
            }
            if SAN.is_armed():
                # sanitized-mode surface: tools/chaos.py --sanitize
                # polls each worker's violation count at the end of a
                # run (the worker process has no other reporting plane)
                info["sanitizerViolations"] = SAN.violation_count()
            # fleet-cache advertisement (ISSUE 19): a bloom summary of
            # this process's cached fragment keys rides every
            # heartbeat poll, so the coordinator's RemoteCacheIndex
            # stays fresh without a dedicated plane; absent when the
            # store doesn't exist or holds nothing (probe-free)
            from presto_tpu.cache import shared_cache_if_exists

            rc = shared_cache_if_exists()
            if rc is not None:
                keys = rc.pages_keys()
                if keys:
                    from presto_tpu.dist.cacheprobe import bloom_summary

                    info["cacheSummary"] = bloom_summary(keys)
            self._write(_jresp(info))
            return
        resp = route_task_get(self.app, split.path, split.query)
        self._write(resp if resp is not None
                    else _jresp({"error": "not found"}, 404))

    def do_DELETE(self):
        resp = route_task_delete(self.app, self.path)
        self._write(resp if resp is not None
                    else _jresp({"error": "not found"}, 404))


class TaskRuntime:
    """A process's task runtime (SqlTaskManager analog): task registry,
    fragment execution, spooled output buffers, fault injection — no
    HTTP server of its own. WorkerServer wraps it with one; the
    coordinator server (http_server.py) embeds one directly so a
    single process can serve both roles."""

    # lock discipline (tools/lint `locks` rule): the task registry is
    # mutated by HTTP handler threads (create/cancel) while status/
    # fetch handlers and expiry sweeps read it — guarded by
    # _tasks_lock; the fault overlay + its call counters by _fault_lock
    _shared_attrs = ("tasks", "fault_config", "_results_calls",
                     "_submit_calls", "_corrupt_calls")

    def __init__(self, catalogs, *, node_id: str = "w0",
                 default_catalog: Optional[str] = None,
                 page_rows: int = 1 << 16):
        self.catalogs = catalogs
        self.node_id = node_id
        self.default_catalog = default_catalog
        self.page_rows = page_rows
        self.tasks: Dict[str, _Task] = {}
        self._tasks_lock = make_lock(
            "server.worker.TaskRuntime._tasks_lock")
        self.started = time.time()
        # uptime arithmetic runs on monotonic (the wall `started` is
        # display/correlation only — timing-source audit, ISSUE 9)
        self.started_mono = time.monotonic()
        self._fault_lock = make_lock(
            "server.worker.TaskRuntime._fault_lock")
        self._results_calls = 0
        self._submit_calls = 0
        self._corrupt_calls = 0
        # runtime-settable fault injection (POST /v1/fault): posted
        # keys OVERRIDE the environment (an explicit 0 disables an
        # env-seeded fault); absent keys fall back to the environment,
        # so `{}` restores env-ruled mode — the overlay is never
        # one-way
        self.fault_config: Dict[str, int] = {}
        register_owner(self, lock_attrs=("_tasks_lock", "_fault_lock"))

    # ------------------------------------------------- task registry
    # The locked read/write surface: handler threads, task threads,
    # and expiry sweeps all go through these (the bare dict used to be
    # mutated from ThreadingHTTPServer handler threads while
    # create_task's expiry sweep iterated it — the unlocked-shared-
    # write shape this PR's concurrency pass exists to catch).

    def get_task(self, task_id: str) -> Optional[_Task]:
        with self._tasks_lock:
            return self.tasks.get(task_id)

    def pop_task(self, task_id: str) -> Optional[_Task]:
        with self._tasks_lock:
            return self.tasks.pop(task_id, None)

    def register_finished_task(self, task_id: str,
                               spool: "_TaskSpool") -> None:
        """Register an already-FINISHED task whose output is a
        pre-built spool — the ICI exchange plane's landing surface
        (ISSUE 18): the coordinator runs the all_to_all partitioning
        itself after the stage barrier and parks the per-partition
        device pages here, so consumers read them through the ONE
        spool data plane (mesh-local fast path or HTTP, token-indexed
        re-fetch, ack/release, task expiry) with no new protocol."""
        t = _Task(task_id)
        with t.lock:
            t.spool = spool
            t.done = True
        with self._tasks_lock:
            self.tasks[task_id] = t

    def serve_cached_fragment(self, task_id: str, key: str) -> Dict:
        """Fleet cache probe target (ISSUE 19): if this process's
        result cache holds ``key``, park its host pages in a
        pre-finished single-partition task spool (the
        register_finished_task landing surface) and report the hit —
        the prober then reads the pages over the ordinary pooled
        spool-fetch plane. A miss is one cheap dict probe."""
        from presto_tpu.cache import shared_cache_if_exists

        rc = shared_cache_if_exists()
        if rc is None or not task_id or not key:
            return {"hit": False}
        pages = rc.get_pages(key)
        if pages is None:
            return {"hit": False}
        spool = _TaskSpool(1, 0)
        for page in pages:
            spool.put_page(
                0, page,
                rows=int(XF.np_host(
                    page.valid, label="cache-remote-serve").sum()))
        self.register_finished_task(task_id, spool)
        rc.count_remote()
        return {"hit": True, "taskId": task_id,
                "pages": len(pages)}

    def task_count(self) -> int:
        with self._tasks_lock:
            return len(self.tasks)

    # -------------------------------------------------- fault injection
    def set_fault_config(self, cfg: Dict[str, int]) -> None:
        """Install a runtime fault config and RESET the call counters —
        'kill after n fetches' / 'drop every nth' count from the posted
        schedule, not from process-lifetime totals accumulated across
        earlier chaos iterations."""
        with self._fault_lock:
            self.fault_config = cfg
            self._results_calls = 0
            self._submit_calls = 0
            self._corrupt_calls = 0

    def _fault(self, name: str) -> int:
        if name in self.fault_config:
            return int(self.fault_config[name])
        return int(os.environ.get(name, "0") or 0)

    def maybe_inject_fault(self) -> bool:
        """SURVEY §6.3: faults inject at the host page proxy (delay /
        drop / kill); returns True when this fetch should fail with
        HTTP 500. Token-indexed re-fetch makes drops recoverable; a
        KILL is the real thing — the process hard-exits, recoverable
        only by the coordinator's task-retry re-dispatch."""
        delay = self._fault("FAULT_DELAY_MS")
        if delay:
            time.sleep(delay / 1000.0)
        with self._fault_lock:
            self._results_calls += 1
            calls = self._results_calls
        kill_after = self._fault("FAULT_KILL_AFTER_FETCHES")
        if kill_after and calls > kill_after:
            # worker death mid-query: bypass every finally/atexit, like
            # a real OOM-kill or host loss
            os._exit(137)
        drop = self._fault("FAULT_DROP_EVERY")
        if drop and calls % drop == 0:
            return True
        return False

    def maybe_corrupt_blob(self, blob: bytes) -> bytes:
        """FAULT_SPOOL_CORRUPT_EVERY=n: bit-flip one byte of every nth
        served results body (ISSUE 20 satellite) — proves the PR-16
        PageWireError loud-fail contract END TO END: the consumer's
        decode rejects the frame BEFORE its token advances, retries
        the same token boundedly, then climbs the replay ladder to a
        surviving replica or fails the query cleanly. Never garbage
        rows."""
        every = self._fault("FAULT_SPOOL_CORRUPT_EVERY")
        if not every or not blob:
            return blob
        with self._fault_lock:
            self._corrupt_calls += 1
            if self._corrupt_calls % every:
                return blob
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 0x01
        return bytes(flipped)

    def maybe_inject_submit_fault(self) -> bool:
        """HTTP 500 on every nth /v1/task submit — exercises the
        coordinator's submit-retry-to-a-different-worker path."""
        drop = self._fault("FAULT_SUBMIT_DROP_EVERY")
        if drop:
            with self._fault_lock:
                self._submit_calls += 1
                if self._submit_calls % drop == 0:
                    return True
        return False

    # ------------------------------------------------------------ tasks
    MAX_RETAINED_TASKS = 32
    # spooled tasks expire far later: their partitions are REPLAY
    # inputs for downstream stage-DAG tasks (the scheduler releases
    # them explicitly via DELETE/ack at query end) — evicting one
    # mid-query would turn a healthy worker into a [source-lost] node
    MAX_RETAINED_SPOOLED = 256

    def create_task(self, req: Dict) -> _Task:
        # expire oldest finished tasks (reference: SqlTaskManager task
        # expiry) so a long-lived worker's page buffers are bounded.
        # Registry mutation happens under _tasks_lock (handler threads
        # create concurrently); the evictees' buffer frees happen
        # OUTSIDE it so spool-file cleanup never stalls task lookups.
        doomed: List[_Task] = []
        with self._tasks_lock:
            for pool, cap in (
                ([tid for tid, t in self.tasks.items()
                  if t.done and t.spool is None],
                 self.MAX_RETAINED_TASKS),
                ([tid for tid, t in self.tasks.items()
                  if t.done and t.spool is not None],
                 self.MAX_RETAINED_SPOOLED),
            ):
                while len(pool) > cap:
                    old = self.tasks.pop(pool.pop(0), None)
                    if old is not None:
                        doomed.append(old)
            task = _Task(req.get("taskId") or f"t{len(self.tasks)}")
            self.tasks[task.task_id] = task
        for old in doomed:
            with old.lock:
                old.free()
        t = threading.Thread(target=self._run_task, args=(task, req),
                             daemon=True)
        t.start()
        return task

    def _run_task(self, task: _Task, req: Dict) -> None:
        # worker-side lifecycle tracing (ISSUE 9): when the coordinator
        # traces the query, the payload carries trace=true and this
        # task records queue/run (+ the executor's attempt) spans,
        # anchored at task creation, shipped back on the status plane
        wtr = None
        if req.get("trace"):
            from presto_tpu import obs as OBS

            wtr = OBS.QueryTrace(task.task_id,
                                 anchor_mono=task.created_mono,
                                 anchor_wall=task.created_wall)
            wtr.complete("queue", task.task_id, 0.0, wtr.now())
        run_t0 = wtr.now() if wtr is not None else 0.0
        try:
            # FAULT_TASK_EXEC_DELAY_MS: stall task EXECUTION (not the
            # fetch path) — makes this worker a deterministic
            # straggler so the scheduler's speculation policy can be
            # exercised without wall-clock races
            exec_delay = self._fault("FAULT_TASK_EXEC_DELAY_MS")
            if exec_delay:
                time.sleep(exec_delay / 1000.0)
            from presto_tpu.connectors.split_filter import (
                HashSplitConnector,
            )
            from presto_tpu.runner import LocalRunner

            index, count = int(req["splitIndex"]), int(req["splitCount"])
            if req.get("splitMode") == "hash":
                # hash-repartition exchange: co-partitioned scans
                # (see HashSplitConnector); the spec is keyed
                # "catalog.table" so a same-named table in another
                # catalog replicates untouched
                part_cols = req["partitionColumns"]
                catalogs = {
                    name: HashSplitConnector(
                        conn,
                        {t.split(".", 1)[1]: c
                         for t, c in part_cols.items()
                         if t.split(".", 1)[0] == name},
                        index, count,
                    )
                    for name, conn in self.catalogs.items()
                }
            elif req.get("splitTable"):
                split_table = req["splitTable"]
                catalogs = {
                    name: SplitFilterConnector(conn, split_table,
                                               index, count)
                    for name, conn in self.catalogs.items()
                }
            elif req.get("sources"):
                # non-leaf stage-DAG fragment: no scans to split —
                # inputs arrive through the spooled-exchange sources
                catalogs = dict(self.catalogs)
            else:
                # a leaf payload with neither a split assignment nor
                # sources must fail LOUDLY: executing it over unsplit
                # catalogs would have every worker scan the full table
                # and the coordinator concatenate N identical copies
                raise ValueError(
                    "task payload carries neither a split assignment "
                    "(splitTable/splitMode) nor spooled-exchange "
                    "sources — refusing to run the fragment unsplit"
                )
            session = Session(catalog=self.default_catalog or
                              next(iter(catalogs)))
            for k, v in (req.get("session") or {}).items():
                session.set(k, v)
            runner = LocalRunner(
                catalogs, page_rows=self.page_rows,
                default_catalog=session.catalog, session=session,
            )
            if req.get("fragment") is not None:
                # plan SHIPPING (reference: TaskUpdateRequest carrying a
                # serialized PlanFragment): execute exactly the subtree
                # the coordinator planned — no worker-side re-planning
                from presto_tpu.dist import plan_serde

                partial = plan_serde.loads(req["fragment"])
            else:
                # legacy SQL replay (pre-round-5 protocol, kept for
                # mixed-version peers): re-plan and take the same cut
                plan = runner.plan(req["sql"])
                cut = find_partial_cut(plan)
                if cut is None:
                    raise ValueError("no aggregation cut in fragment")
                partial = dataclasses.replace(cut, step="partial")
            ex = runner.executor
            runner.apply_session()
            if wtr is not None:
                # the fragment executor records its attempt spans into
                # the task trace too (overflow-ladder visibility ships
                # to the coordinator with the queue/run phases)
                from presto_tpu import obs as OBS

                OBS.attach(ex, wtr)
            sources = req.get("sources") or {}
            nparts = int(req.get("outputPartitions") or 0)
            out_keys = tuple(req.get("outputKeys") or ())
            spooled = bool(sources) or nparts > 0
            if sources:
                # stage-DAG ingest: RemoteSource suppliers fetching
                # this task's input partitions from upstream tasks'
                # spools (worker-to-worker exchange; dist/spool.py).
                # A persistently unreachable source fails the task
                # with a [source-lost ...] marker the scheduler uses
                # to replay the upstream task instead of just this one
                from presto_tpu.dist import spool as SPOOL

                backoff = (
                    int(session.get("retry_backoff_ms")) / 1000.0
                )
                for key, spec in sources.items():
                    ex.remote_sources[key] = (
                        lambda spec=spec: SPOOL.iter_source_pages(
                            spec, retries=3, backoff_s=backoff,
                            deadline=ex.query_deadline,
                            # mesh-local fast path: a same-process
                            # producer's spool serves Pages directly
                            on_local=ex.count_mesh_local,
                        )
                    )

            # Worker-side overflow discipline: the executor's shared
            # query-scope retry ladder (Executor.stream_fragment) —
            # pages buffer locally and publish only after the
            # fragment's OR-reduced overflow flags clear, so a
            # truncated page set can NEVER reach the coordinator as a
            # silent result. On overflow the fragment re-runs with 4x
            # capacities (the coordinator's long-poll tolerates the
            # delay); persistent overflow fails the task loudly via
            # task.error.
            if spooled:
                from presto_tpu.dist import spool as SPOOL

                # spooled-exchange emit: partition each host page by
                # hash(outputKeys) % P (P=1 collapses to a single
                # gather/broadcast partition), serialize per
                # partition, and stream STRAIGHT into the tiered
                # spool — blobs past the resident budget go to the
                # disk tier DURING execution, so spool_exchange_bytes
                # bounds peak worker memory for large exchanges. The
                # spool stays unpublished (task.spool None ⇒
                # consumers long-poll) until the attempt completes
                # overflow-free, and on_attempt resets it so a
                # boosted retry never double-spools.
                state = {"spool": None}

                def on_attempt() -> None:
                    if state["spool"] is not None:
                        state["spool"].close()
                    state["spool"] = _TaskSpool(
                        max(nparts, 1),
                        int(session.get("spool_exchange_bytes")),
                        spill_dir=session.get("spill_path") or None,
                    )

                dev_exchange = ex._device_exchange_on()
                mesh_raw = bool(req.get("meshExchange"))

                def emit(page) -> int:
                    if mesh_raw:
                        # ICI exchange plane (ISSUE 18): spool the RAW
                        # page to partition 0 untouched — partitioning
                        # happens in the coordinator's post-barrier
                        # all_to_all program, and the per-partition
                        # stats plane moves there with it (no
                        # spool-stats d2h pull, no hashing, no P-way
                        # compaction on this side of the edge)
                        state["spool"].put_page(0, page, rows=0)
                        return 1
                    if dev_exchange:
                        # device tier (ISSUE 13): partition + compact
                        # ON DEVICE (dist/spool.device_partition_pages
                        # — one jitted program, skew joins the boosted
                        # ladder) and spool the partition Pages
                        # themselves; host bytes materialize lazily
                        # only for HTTP (remote/replay) fetches. The
                        # d2h-at-emit term deletes here.
                        # with_counts: the same program also emits the
                        # per-partition row counts (spool-stats plane)
                        pp, counts = SPOOL.device_partition_pages(
                            ex, page, out_keys, max(nparts, 1),
                            with_counts=True)
                        for p, part_page in pp:
                            state["spool"].put_page(
                                p, part_page, rows=int(counts[p]))
                        return len(pp)
                    host = XF.to_host(page, label="task-emit")
                    n = 0
                    for p, part_page in SPOOL.partition_host_page(
                            host, out_keys, max(nparts, 1)):
                        # host pages: the validity mask is already
                        # host numpy — the exact-count read is free
                        rows = int(XF.np_host(part_page.valid).sum())
                        state["spool"].put(
                            p, serde.serialize_page(part_page),
                            rows=rows)
                        n += 1
                    return n

                ex.skew_preengaged = bool(req.get("skewHint"))
                ex.stream_fragment(
                    partial, emit, cancelled=lambda: task.cancelled,
                    on_attempt=on_attempt,
                )
                if wtr is not None:
                    wtr.complete("run", task.task_id, run_t0,
                                 wtr.now(),
                                 spooled=state["spool"].page_count)
                with task.lock:
                    if wtr is not None:
                        task.spans = wtr.export()
                    task.spool = state["spool"]
                    task.boost_retries = ex.capacity_boost_retries
                    task.skew_preempted = ex.skew_preempted
                    task.done = True
            else:
                def emit(page) -> bytes:
                    return serde.serialize_page(
                        XF.to_host(page, label="task-emit"))

                blobs: List = ex.stream_fragment(
                    partial, emit, cancelled=lambda: task.cancelled
                )
                if wtr is not None:
                    wtr.complete("run", task.task_id, run_t0,
                                 wtr.now(), pages=len(blobs))
                with task.lock:
                    if wtr is not None:
                        task.spans = wtr.export()
                    task.pages.extend(blobs)
                    task.boost_retries = ex.capacity_boost_retries
                    task.done = True
        except Exception as e:  # noqa: BLE001 - task failures surface
            # to the coordinator via the X-Task-Error results header
            # (real error text, no fetch-retry spinning), never as a
            # hung task
            if wtr is not None:
                wtr.complete("run", task.task_id, run_t0, wtr.now(),
                             error=repr(e)[:200])
            with task.lock:
                if wtr is not None:
                    task.spans = wtr.export()
                task.error = repr(e)[:400]
                task.done = True


class WorkerServer(TaskRuntime):
    """One worker process's task runtime behind its own HTTP server."""

    def __init__(self, catalogs, *, port: int = 0, node_id: str = "w0",
                 default_catalog: Optional[str] = None,
                 page_rows: int = 1 << 16):
        super().__init__(catalogs, node_id=node_id,
                         default_catalog=default_catalog,
                         page_rows=page_rows)
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port),
                                          _WorkerHandler)
        self._httpd.app = self  # type: ignore[attr-defined]
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # -------------------------------------------------------- lifecycle
    def start(self) -> int:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        # same-process placement registry: consumers in THIS process
        # take spooled Pages directly (mesh-local exchange fast path)
        register_local_runtime(f"http://127.0.0.1:{self.port}", self)
        return self.port

    def stop(self) -> None:
        # unregister FIRST: a stopped worker must look remote-and-dead
        # to local consumers (the forced-fallback replay path), never
        # serve stale spools through the fast path
        unregister_local_runtime(f"http://127.0.0.1:{self.port}")
        self._httpd.shutdown()


def main() -> int:  # pragma: no cover - subprocess entry
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--suite", default="tpch")
    parser.add_argument("--scale", type=float, default=0.01)
    parser.add_argument("--node-id", default="w0")
    parser.add_argument("--page-rows", type=int, default=1 << 16)
    args = parser.parse_args()

    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.connectors.tpch import TpchConnector

    cls = TpchConnector if args.suite == "tpch" else TpcdsConnector
    srv = WorkerServer(
        {args.suite: cls(scale=args.scale)}, port=args.port,
        node_id=args.node_id, default_catalog=args.suite,
        page_rows=args.page_rows,
    )
    port = srv.start()
    print(json.dumps({"port": port, "nodeId": args.node_id}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
