"""Coordinator HTTP service speaking the Presto client protocol.

Reference: presto-main server/protocol/StatementResource.java (the
/v1/statement paged REST protocol: POST the SQL, follow nextUri until it
disappears, token-addressed result pages, DELETE to cancel) plus
server/PrestoServer bootstrap. Sessions are client-carried exactly like
the reference: X-Presto-Session request headers hold property overrides,
SET SESSION responds with X-Presto-Set-Session and the client echoes it
back on later requests — the server itself stays stateless per query.

The engine is the in-process LocalRunner (single- or mesh-distributed);
queries execute on a worker thread under a global lock (one query on the
device at a time) while the protocol surface stays responsive.
"""

from __future__ import annotations

import json
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib.parse import urlparse

from presto_tpu import types as T
from presto_tpu.obs.sanitizer import (
    make_condition,
    make_lock,
    register_owner,
)
from presto_tpu.session import SYSTEM_SESSION_PROPERTIES, Session

_PAGE_ROWS = 4096  # rows per protocol fetch (client paging granularity)
# tailing cursors retain only this many recent token spans' rows —
# the retry horizon; a never-finishing cursor must not hold every row
# it ever emitted (clients only ever re-fetch their latest token)
_TAIL_RETAIN_SPANS = 8


class _Query:
    """Reference: server/protocol/Query.java — one statement's life."""

    def __init__(self, qid: str, sql: str, session: Session):
        self.id = qid
        self.sql = sql
        self.session = session
        self.state = "QUEUED"
        self.columns: Optional[List[Dict]] = None
        self.rows: List[tuple] = []
        self.error: Optional[Dict] = None
        self.update_type: Optional[str] = None
        self.set_session: Dict[str, str] = {}
        # ONE wall anchor (display/correlation); every elapsed-time
        # computation runs on monotonic so an NTP step mid-query can
        # neither stretch nor collapse it (ISSUE 9 timing-source rule)
        self.created = time.time()
        self.created_mono = time.monotonic()
        self.finished_at: Optional[float] = None
        self.finished_mono: Optional[float] = None
        self.cancelled = False
        self.done = threading.Event()
        # lifecycle trace (obs.QueryTrace): the query owns it from
        # submission (anchored at created_mono) to _finish_clock; the
        # runner and its executor record into it while it runs
        self.trace = None
        self.queue_span = None
        # microseconds waited at each admission gate (the queue
        # span's attrs)
        self.gate_wait_us: Dict[str, int] = {}
        # tailing cursor (ISSUE 14): non-None turns this query into a
        # never-finishing stream cursor served by _tail_results
        self.tail: Optional["TailCursor"] = None
        # durable journal handle (ISSUE 20): non-None means this
        # query's lifecycle + protocol-token advances are journaled
        # for crash re-attach (dist/checkpoint.QueryCheckpoint)
        self.checkpoint = None

    def _finish_clock(self) -> None:
        if self.finished_at is None:
            self.finished_at = time.time()
            self.finished_mono = time.monotonic()

    def waited(self, gate: str, since_mono: float) -> None:
        """Record the time since ``since_mono`` as spent at ``gate``."""
        self.gate_wait_us[gate] = self.gate_wait_us.get(gate, 0) + int(
            (time.monotonic() - since_mono) * 1e6)

    def info(self) -> Dict:
        end_mono = (self.finished_mono if self.finished_mono
                    is not None else time.monotonic())
        return {
            "queryId": self.id,
            "state": self.state,
            "query": self.sql,
            "createTime": self.created,
            "elapsedTimeMillis": int(
                (end_mono - self.created_mono) * 1000
            ),
            "error": self.error,
            "rowCount": len(self.rows),
        }


class TailCursor:
    """One tailing /v1/statement cursor over an append-only stream
    (ISSUE 14): the statement's nextUri never terminates — each poll
    long-polls the log (StreamConnector.wait_for_offset) and emits
    ONLY rows derived from new offsets. Three poll strategies, chosen
    once at creation from the planned statement:

      view       the statement IS a registered materialized view
                 (shape-fingerprint match, streaming/ivm.py): polls
                 ride the incremental refresh — O(new rows) fold —
                 and emit the multiset delta of the refreshed result
                 vs the previously emitted snapshot (changed/new
                 aggregate rows, the live-dashboard diff);
      delta      a pure per-row pipeline (Output → Filter/Project* →
                 stream scan): polls execute the plan over the pinned
                 [last, head) window only — exactly the new rows, no
                 recompute, no diff;
      recompute  anything else over a stream: polls re-execute the
                 statement and emit the multiset delta — degraded
                 (O(full) per poll) but never wrong, the same
                 loud-fallback stance as non-IVM-safe views.

    Concurrency: protocol GETs may race on one cursor. State mutates
    only under ``_cv``; the poll's query execution runs UNLOCKED
    behind the ``_polling`` flag (concurrent pollers wait, then read
    the freshly appended span) so no blocking work ever happens under
    an engine lock. Token paging is span-addressed: token t re-serves
    its recorded row span verbatim (retry-safe), the first fresh
    token takes everything new."""

    # lock discipline (tools/lint `locks` rule)
    _shared_attrs = ("columns", "types", "rows", "error", "closed",
                     "_polling", "_spans", "last_rows", "last_offset",
                     "_base", "_span_base", "resource_group")

    def __init__(self, runner, plan, streams, sink):
        from presto_tpu.streaming import ivm as IVM

        self.runner = runner
        self.plan = plan
        # every append-only table the statement scans: polls wake on
        # ANY of them advancing (view/delta modes have exactly one by
        # construction; recompute mode may join several streams)
        self.streams = list(streams)
        self.catalog, self.table = self.streams[0]
        self.conn = runner.catalogs[self.catalog]
        self.sink = sink  # bootstrap executor: registry counters
        self.poll_ms = int(runner.session.get("stream_poll_ms"))
        reg = IVM.shared_registry_if_exists()
        self.view = reg.match(plan) if reg is not None else None
        self.window = None
        self.executor = None
        if self.view is not None:
            self.mode = "view"
        elif self._delta_shape(plan):
            self.mode = "delta"
            self.executor, self.window = IVM.windowed_executor(
                runner.catalogs, self.catalog, self.table,
                like=runner.executor,
            )
        else:
            self.mode = "recompute"
        self.columns: Optional[List[Dict]] = None
        self.types: List[str] = []
        # emitted rows, trimmed to the retry horizon: _base is the
        # ABSOLUTE index of rows[0] — a never-finishing cursor must
        # not retain every row it ever emitted (spans older than
        # _TAIL_RETAIN_SPANS tokens are beyond any client retry)
        self.rows: List[tuple] = []
        self._base = 0
        # recent token spans only (ABSOLUTE (lo, hi) row indices);
        # _span_base counts the spans trimmed off the front — an idle
        # cursor heartbeats one span per poll forever, so the span
        # list is bounded exactly like the rows it addresses
        self._spans: List[tuple] = []
        self._span_base = 0
        self.last_rows: List[tuple] = []  # last full result (diff)
        # SUM of offsets across all scanned streams (single-stream
        # cursors: just that table's offset)
        self.last_offset = 0
        self.error: Optional[Dict] = None
        self.closed = False
        self._polling = False
        # resource-group admission slot (start_tail admits a tailing
        # statement through the same queue gate as submit(); close
        # releases it) — the manager reference rides along so close
        # can release without reaching back into the server
        self.resource_group = None
        self._rg_manager = None
        self._cv = make_condition(
            "server.http_server.TailCursor._cv")
        register_owner(self, lock_attrs=("_cv",))

    def _delta_shape(self, plan) -> bool:
        """True for Output → (Filter|Project)* → TableScan of THE
        stream table — the shape whose delta-window execution equals
        the delta of its results."""
        from presto_tpu.exec import plan as P

        node = plan
        if not isinstance(node, P.Output):
            return False
        node = node.source
        while isinstance(node, (P.Filter, P.Project)):
            node = node.source
        return (isinstance(node, P.TableScan)
                and node.catalog == self.catalog
                and node.table == self.table)

    # ------------------------------------------------------- polling
    def _offsets_total(self) -> int:
        return sum(self.runner.catalogs[c].offset(t)
                   for c, t in self.streams)

    def _wait_any(self, timeout_s: float) -> int:
        """Long-poll until ANY scanned stream advances past the last
        observed offsets (or the timeout lapses); returns the summed
        offset. Single-stream cursors ride the connector's condition;
        multi-stream recompute cursors poll in slices (appends to
        EITHER side of a stream join must produce rows)."""
        base = self.last_offset
        if len(self.streams) == 1:
            c, t = self.streams[0]
            self.runner.catalogs[c].wait_for_offset(t, base, timeout_s)
            return self._offsets_total()
        deadline = time.monotonic() + max(timeout_s, 0.0)
        while True:
            total = self._offsets_total()
            remaining = deadline - time.monotonic()
            if total > base or remaining <= 0:
                return total
            time.sleep(min(0.05, remaining))

    def poll(self, timeout_s: float) -> None:
        """Advance the cursor: wait for new offsets (up to
        ``timeout_s``), compute the delta-derived rows, append them.
        Serialized by the ``_polling`` flag; a failure closes the
        cursor with an error body (the protocol's FAILED contract —
        never a dropped connection)."""
        with self._cv:
            while self._polling and not self.closed:
                self._cv.wait(0.05)
            if self.closed:
                return
            self._polling = True
        new_rows: List[tuple] = []
        full: Optional[List[tuple]] = None
        err = None
        cols = None
        types = None
        offset = None
        try:
            new_rows, full, cols, types, offset = self._compute(
                timeout_s)
        except Exception as e:  # noqa: BLE001 - the protocol surfaces
            # every tail failure as an error body on the cursor
            err = {"message": str(e)[:2000],
                   "errorName": type(e).__name__}
        with self._cv:
            self._polling = False
            if err is not None:
                self.error = err
                self.closed = True
            else:
                if cols is not None and self.columns is None:
                    self.columns = cols
                    self.types = types or []
                if full is not None:
                    self.last_rows = full
                if offset is not None:
                    self.last_offset = offset
                self.rows.extend(new_rows)
            self._cv.notify_all()

    def _compute(self, timeout_s: float):
        """(delta rows, full result or None, columns or None, types,
        new offset). Runs UNLOCKED — see class docstring."""
        from presto_tpu.streaming import ivm as IVM

        initial = self.columns is None
        if initial:
            hi = self._offsets_total()
        else:
            hi = self._wait_any(timeout_s)
        self.sink.count_cursor_poll()
        if not initial and hi <= self.last_offset:
            return [], None, None, None, None  # quiet poll
        if not initial:
            # the log moved under a tailing cursor: one observed batch
            self.sink.count_stream_append()
        if self.mode == "view":
            names, rows, types = IVM.refresh(
                self.view, session=self.runner.session,
                sink=self.sink)
            delta = rows if initial else _multiset_delta(
                rows, self.last_rows)
            cols = [{"name": n, "type": t}
                    for n, t in zip(names, types)]
            return delta, list(rows), cols, types, hi
        if self.mode == "delta":
            ex = self.executor
            self.window.set_range(
                0 if initial else self.last_offset, hi)
            names, rows = ex.execute(self.plan)
            types = [str(t) for t in ex.output_types(self.plan)]
            cols = [{"name": n, "type": t}
                    for n, t in zip(names or [], types)]
            return rows, None, cols, types, hi
        # recompute: full statement re-execution + multiset diff —
        # degraded loudly (every poll is a real run), never wrong
        ex = self.runner.executor
        names, rows = ex.execute(self.plan)
        types = [str(t) for t in ex.output_types(self.plan)]
        cols = [{"name": n, "type": t}
                for n, t in zip(names or [], types)]
        delta = rows if initial else _multiset_delta(
            rows, self.last_rows)
        return delta, list(rows), cols, types, hi

    # ------------------------------------------------- token paging
    def take_span(self, token: int):
        """JSON rows for ``token``: a RECENT token re-serves its exact
        recorded span (retry-safe); the next fresh token takes every
        row emitted since the previous span. None for tokens further
        ahead or already trimmed past the retry horizon (protocol
        clients only ever retry their latest token)."""
        with self._cv:
            idx = token - self._span_base
            if idx < 0:
                return None  # trimmed: beyond the retry horizon
            if idx < len(self._spans):
                lo, hi = self._spans[idx]
            elif idx == len(self._spans):
                lo = self._spans[-1][1] if self._spans else self._base
                hi = self._base + len(self.rows)
                self._spans.append((lo, hi))
                # bound the never-finishing cursor's memory: spans AND
                # the rows they address drop past the retry horizon
                # (spans keep ABSOLUTE indices; _base/_span_base track
                # what rows[0]/_spans[0] correspond to)
                if len(self._spans) > _TAIL_RETAIN_SPANS:
                    drop = len(self._spans) - _TAIL_RETAIN_SPANS
                    floor = self._spans[drop][0]
                    del self._spans[:drop]
                    self._span_base += drop
                    if floor > self._base:
                        del self.rows[:floor - self._base]
                        self._base = floor
            else:
                return None
            types = self.types
            return [_json_row(r, types)
                    for r in self.rows[lo - self._base:hi - self._base]]

    def spans_served(self) -> int:
        with self._cv:
            return self._span_base + len(self._spans)

    def close(self) -> None:
        """Stop the cursor and RELEASE its heavy engine state (the
        dedicated runner/executor, delta window, diff snapshot) and
        its resource-group admission slot — the _Query record stays
        in the manager registry like any finished query, but a closed
        cursor must not pin an Executor. The already-emitted row tail
        stays servable for the final page. Waits out an in-flight
        poll (bounded by the poll timeout) so the engine refs are
        never nulled under a running query."""
        with self._cv:
            self.closed = True
            self._cv.notify_all()
            while self._polling:
                self._cv.wait(0.05)
            self.last_rows = []
            group, self.resource_group = self.resource_group, None
        if group is not None and self._rg_manager is not None:
            self._rg_manager.cancel_queued(group)
        self.runner = None
        self.executor = None
        self.window = None
        self.view = None


def _multiset_delta(new_rows, old_rows):
    """Rows of ``new_rows`` not covered by ``old_rows`` as a multiset
    (repr-keyed: rows may carry unhashable nested values) — the
    changed/new rows a dashboard diff emits per refresh."""
    import collections

    old = collections.Counter(map(repr, old_rows))
    out = []
    for r in new_rows:
        k = repr(r)
        if old[k] > 0:
            old[k] -= 1
        else:
            out.append(r)
    return out


class MemoryArbiter:
    """Admission by estimated HBM footprint (reference:
    memory/ClusterMemoryManager + query.max-memory): queries reserve
    their estimate and block until it fits the budget. A query larger
    than the whole budget is admitted only when it would run alone —
    progress is guaranteed, concurrency degrades to serial exactly
    when memory demands it (the reference's reserved-pool promotion)."""

    # lock discipline (tools/lint `locks` rule): the reservation
    # tallies every query's admission thread contends on
    _shared_attrs = ("used", "active")

    def __init__(self, total_bytes: int):
        self.total = int(total_bytes)
        self.used = 0
        self.active = 0
        self._cv = make_condition(
            "server.http_server.MemoryArbiter._cv")
        register_owner(self, lock_attrs=("_cv",))

    def acquire(self, est: int, should_abort=None) -> bool:
        with self._cv:
            while True:
                if should_abort is not None and should_abort():
                    return False
                if self.used + est <= self.total or self.active == 0:
                    self.used += est
                    self.active += 1
                    return True
                self._cv.wait(timeout=0.1)

    def release(self, est: int) -> None:
        with self._cv:
            self.used -= est
            self.active -= 1
            self._cv.notify_all()


def _xfer_totals():
    """Process-total transfer tallies (exec/xfer.py choke points)
    under the registry counter names — per-query executors come and
    go on the concurrent path; the copy-tax truth /metrics reports is
    the process accumulation."""
    from presto_tpu.exec import xfer as XFER

    return XFER.process_totals()


def _wire_totals():
    """Process-total exchange wire tallies (dist/serde.py codecs +
    dist/connpool.py reuse) under the registry counter names — same
    rationale as _xfer_totals: worker task executors never surface
    on the scrape path, the process accumulation is the fleet truth
    for wire efficiency."""
    from presto_tpu.dist import connpool as CONNPOOL
    from presto_tpu.dist import serde as SERDE

    out = SERDE.wire_totals()
    out.update(CONNPOOL.pool_totals())
    return out


def _result_cache_totals():
    """Process-total result-cache tallies under the registry counter
    names (zeros when no session ever created the shared store —
    scraping metrics must never allocate a cache)."""
    from presto_tpu.cache import shared_cache_if_exists

    rc = shared_cache_if_exists()
    if rc is None:
        return {
            "result_cache_hits": 0,
            "result_cache_misses": 0,
            "result_cache_evictions": 0,
            "result_cache_invalidations": 0,
            "cache_warm_loads": 0,
            "cache_remote_hits": 0,
            "cache_subsumed_hits": 0,
            "cache_manifest_drops": 0,
        }
    return rc.counters()


class QueryManager:
    """Reference: execution/SqlQueryManager.java — registry + lifecycle
    (QUEUED -> RUNNING -> FINISHED/FAILED/CANCELED)."""

    # lock discipline (tools/lint `locks` rule): attributes touched
    # from both HTTP handler threads and query-execution threads —
    # written ONLY under self._lock outside __init__
    _shared_attrs = ("_queries", "_seq", "completed_by_state",
                     "rows_returned_total", "query_wall_ms_total",
                     "cache_admission_bypasses",
                     "exec_counter_totals",
                     "queued_now", "peak_queued", "journal")

    # launch/batch counters accumulated across the concurrent path's
    # per-query executors at completion (ISSUE 17): those executors
    # are discarded per query, so the PROCESS aggregate — the number
    # /metrics reports — lives here and
    # overlays the registry snapshot on /metrics + system.metrics
    # (the _result_cache_totals rationale applied to dispatch)
    _EXEC_TOTAL_SUMS = (
        "program_launches", "splits_scanned", "cross_query_batches",
        "cross_query_batched_queries", "batch_gather_wait_ms",
        "device_launches", "exchange_launches", "mesh_fused_rounds",
        "mesh_batched_rounds", "row_counts_launched", "row_counts_eager",
        "dispatch_wall_us", "device_wait_us",
        "resident_splits_scanned", "resident_bytes_scanned",
        "join_builds", "join_build_rows", "join_build_bytes",
        "join_build_wall_us", "join_probes_at_build",
        "plan_constants_folded",
    )
    _EXEC_TOTAL_MAX = ("queries_per_launch",)

    def __init__(self, runner_factory, listeners=(),
                 resource_groups=None, memory_arbiter=None,
                 listener_error_counter=None, journal=None,
                 counter_executor=None, dcn=None, session_seed=None):
        from presto_tpu.obs.histo import Histogram

        self._runner_factory = runner_factory
        # seeds a submitted statement's session with the deployment's
        # defaults (tracing among them) before its trace is made; the
        # runner factory seeds the same way, idempotently
        self._session_seed = session_seed
        # durable coordinator journal (ISSUE 20): server-configured
        # (checkpoint.dir etc key) or lazily bound from the
        # checkpoint_dir session property at first enabled submit
        self.journal = journal
        self._counter_ex = counter_executor
        # the DCN dispatch plane whose scheduler barriers the per-query
        # checkpoint handle is attached to for stage-boundary journaling
        self._dcn = dcn
        self._queries: Dict[str, _Query] = {}
        self._seq = 0
        self._lock = make_lock(
            "server.http_server.QueryManager._lock")
        # serial fallback when no arbiter is configured
        self._exec_lock = make_lock(
            "server.http_server.QueryManager._exec_lock")
        self.memory = memory_arbiter
        self.listeners = list(listeners)
        # swallowed-listener-exception sink (events.dispatch on_error
        # -> the executor's listener_errors registry counter)
        self._listener_error = listener_error_counter
        # admission control (reference: resourceGroups/*; None = admit
        # everything, the pre-RG behavior)
        self.resource_groups = resource_groups
        # /metrics counters (reference: airlift stats -> JMX; ours is a
        # Prometheus text endpoint, SURVEY §6.5 build mapping)
        self.completed_by_state: Dict[str, int] = {}
        self.rows_returned_total = 0
        self.query_wall_ms_total = 0
        # cache-aware admission (ISSUE 17): statements served whole
        # from the result cache without ever taking a resource-group
        # concurrency slot or an arbiter reservation
        self.cache_admission_bypasses = 0
        # process launch/batch aggregate (see _EXEC_TOTAL_SUMS)
        self.exec_counter_totals: Dict[str, int] = {}
        # admission queue depth (ISSUE 17): queries currently waiting
        # for admission (resource-group slot / memory reservation /
        # the serial exec lock) and the lifetime peak (/metrics):
        # cache replays must never inflate this line
        self.queued_now = 0
        self.peak_queued = 0
        # latency histograms (obs/histo.py): bucketed query wall and
        # per-stage wall for p50/p95/p99 — internally locked, written
        # via observe() from completion paths, scraped by /metrics
        # (the surface ROADMAP item 1's load benchmark reads)
        self.latency_histo = Histogram()
        self.stage_histo = Histogram()
        register_owner(self)

    def _journal_for(self, session: Session):
        """The journal this query's barriers record to: the server-
        configured one (checkpoint.dir etc key / constructor kwarg),
        or one bound lazily from the checkpoint_dir session property.
        None = journaling off (checkpoint_enabled false, or no
        directory anywhere)."""
        if not bool(session.get("checkpoint_enabled")):
            return None
        if self.journal is not None:
            return self.journal
        d = session.get("checkpoint_dir")
        if not d:
            return None
        from presto_tpu.dist.checkpoint import CheckpointJournal

        j = CheckpointJournal(d, counter_ex=self._counter_ex)
        with self._lock:
            if self.journal is None:
                self.journal = j
        return self.journal

    def submit(self, sql: str, session: Session) -> _Query:
        from presto_tpu import events as E
        from presto_tpu import obs as OBS

        group = None
        if self.resource_groups is not None:
            # raises QueryQueueFullError before the query exists
            # (reference: admission happens ahead of planning)
            group = self.resource_groups.admit(session.user)
        with self._lock:
            self._seq += 1
            qid = time.strftime("%Y%m%d_%H%M%S") + \
                f"_{self._seq:05d}_{uuid.uuid4().hex[:5]}"
            q = _Query(qid, sql, session)
            q.resource_group = group
            self._queries[qid] = q
        if self._session_seed is not None:
            self._session_seed(session)
        q.trace = OBS.maybe_trace(
            session, query_id=qid, sql=sql,
            anchor_mono=q.created_mono, anchor_wall=q.created)
        j = self._journal_for(session)
        if j is not None:
            # admission barrier (ISSUE 20): statement + session +
            # group land durably before the execution thread exists
            q.checkpoint = j.admit(
                qid, sql, _session_snapshot(session),
                str(group.paths[-1]) if group is not None else None,
            )
        E.dispatch(self.listeners, "query_created", E.QueryCreatedEvent(
            query_id=q.id, sql=sql, user=session.user,
            create_time=q.created,
        ), on_error=self._listener_error)
        threading.Thread(
            target=self._run, args=(q,), daemon=True
        ).start()
        return q

    def get(self, qid: str) -> Optional[_Query]:
        return self._queries.get(qid)

    def register_tail(self, sql: str, session: Session,
                      cursor: TailCursor) -> _Query:
        """Register a tailing cursor as a RUNNING query: it appears
        in /v1/query and system.runtime_queries like any statement,
        but no execution thread is spawned — polls ride the protocol
        GET handlers (TailCursor.poll serializes them)."""
        with self._lock:
            self._seq += 1
            qid = time.strftime("%Y%m%d_%H%M%S") + \
                f"_{self._seq:05d}_{uuid.uuid4().hex[:5]}"
            q = _Query(qid, sql, session)
            q.tail = cursor
            q.state = "RUNNING"
            self._queries[qid] = q
        return q

    def cancel(self, qid: str) -> bool:
        q = self._queries.get(qid)
        if q is None:
            return False
        q.cancelled = True
        if not q.done.is_set():
            q.state = "CANCELED"
            q._finish_clock()
            q.done.set()
        return True

    def query_info(self, qid: str) -> Optional[Dict]:
        """The QueryInfo/StageInfo/TaskInfo tree for one query
        (reference: /v1/query/{id}). Served LIVE: a RUNNING query's
        tree comes straight off its runner's active trace, so a
        mid-query poll sees the stages/tasks recorded so far."""
        q = self._queries.get(qid)
        if q is None:
            return None
        info = q.info()
        tr = q.trace
        if tr is not None:
            tree = tr.to_info()
            info["stages"] = tree["stages"]
            info["spanCount"] = tree["spanCount"]
            # the top-level spans, microseconds from submission: they
            # tile elapsedTimeMillis (queue, parse, plan, execute,
            # encode); stages[*] count from the instant the runner
            # begins to plan, as they always have
            info["phases"] = tr.phases()
            info["anchorMonotonicS"] = q.created_mono
        else:
            info["stages"] = []
            info["spanCount"] = 0
        return info

    def _run(self, q: _Query) -> None:
        group = getattr(q, "resource_group", None)
        runner = None
        if q.trace is not None:
            # begins at submission; this thread, started there, holds
            # its annotation
            q.queue_span = q.trace.phase("queue", at=0.0)
        if self.memory is not None and not q.cancelled:
            # cache-aware admission (ISSUE 17): a statement the
            # result cache would serve whole costs near nothing —
            # parking it in the resource-group line or reserving HBM
            # for it would spend real slots on zero-cost work and
            # queue REAL queries behind replays. The probe is pure
            # host work (parse + plan + tally-free key peek); on a
            # hit the query executes immediately, outside every
            # admission gate. Advisory: a racing eviction between
            # probe and serve just runs the query for real, admitted
            # only by the arbiter-level backstop it skipped — an
            # accepted, bounded misestimate (est is small anyway).
            runner = self._runner_factory(q.session)
            if runner.statement_cache_probe(q.sql):
                if group is not None:
                    self.resource_groups.cancel_queued(group)
                with self._lock:
                    self.cache_admission_bypasses += 1
                self._execute(q, runner)
                return
        self._queue_enter(q)
        if group is not None:
            if q.cancelled:
                self.resource_groups.cancel_queued(group)
                self._record_completion(q)
                return
            t0 = time.monotonic()
            admitted = self.resource_groups.acquire(
                group, should_abort=lambda: q.cancelled)
            q.waited("resource_group", t0)
            if not admitted:
                # canceled while queued: acquire released the queue slot
                self._record_completion(q)
                return
        try:
            self._run_admitted(q, runner)
        finally:
            if group is not None:
                self.resource_groups.release(group)

    def _queue_enter(self, q: _Query) -> None:
        """Mark q as waiting for admission. Paired with _queue_exit
        (first of: execution start, completion record) via a consumed-
        once flag, so abort paths and the execute path can both exit
        without double counting."""
        q.in_admission = True
        with self._lock:
            self.queued_now += 1
            self.peak_queued = max(self.peak_queued, self.queued_now)

    def _queue_exit(self, q: _Query) -> None:
        if getattr(q, "in_admission", False):
            q.in_admission = False
            with self._lock:
                self.queued_now -= 1

    # NB: not named `*_locked` — that suffix is the machine-checked
    # caller-holds-the-lock convention (tools/concheck.py); this
    # method ACQUIRES the execution lock/arbiter itself
    def _run_admitted(self, q: _Query, runner=None) -> None:
        if self.memory is None:
            t0 = time.monotonic()
            with self._exec_lock:
                q.waited("execution_lock", t0)
                self._execute(q)
            return
        # concurrent path: admission by estimated footprint replaces
        # the global device lock; each query runs on
        # its own runner/executor (shared jit cache), so small queries
        # interleave while the arbiter keeps the sum under budget
        if runner is None:
            runner = self._runner_factory(q.session)
        est = runner.estimate_memory(q.sql)
        group = getattr(q, "resource_group", None)
        if group is not None and self.resource_groups is not None:
            # per-group HBM shares (ISSUE 17): the group policy's
            # memory_share resolves into THIS query's governed
            # device budget (exec/membudget.py) — N concurrent
            # queries split the device by policy instead of
            # colliding into the OOM ladder. An explicit session
            # device_memory_budget always wins.
            share = self.resource_groups.memory_share_for(group)
            if share > 0 and not q.session.is_set(
                    "device_memory_budget"):
                from presto_tpu.exec import membudget as MB

                q.session.set(
                    "device_memory_budget",
                    MB.group_share_bytes(share),
                )
        if group is not None and self.resource_groups is not None:
            # per-group memory quotas gate before the global arbiter
            # (reference: soft_memory_limit per resource group)
            t0 = time.monotonic()
            reserved = self.resource_groups.reserve_memory(
                group, est, should_abort=lambda: q.cancelled)
            q.waited("resource_group", t0)
            if not reserved:
                self._record_completion(q)
                return
        try:
            t0 = time.monotonic()
            admitted = self.memory.acquire(
                est, should_abort=lambda: q.cancelled)
            q.waited("footprint_arbiter", t0)
            if not admitted:
                self._record_completion(q)
                return
            try:
                self._execute(q, runner)
            finally:
                self.memory.release(est)
        finally:
            if group is not None and self.resource_groups is not None:
                self.resource_groups.release_memory(group, est)

    def _execute(self, q: _Query, runner=None) -> None:
            self._queue_exit(q)
            tr = q.trace
            if tr is not None:
                # admitted: the queue phase ends here with the gates'
                # waits, and parse opens at the same instant
                waits = q.gate_wait_us
                tr.end(q.queue_span,
                       gate=max(waits, key=waits.get) if waits else None,
                       **{f"{g}_us": us for g, us in waits.items()})
                tr.phase("parse")
            ckpt = q.checkpoint
            if q.cancelled:
                # canceled while queued: still record completion so event
                # listeners and /metrics see every created query finish
                self._record_completion(q)
                if ckpt is not None:
                    ckpt.delivered()  # nothing left to recover
                return
            q.state = "RUNNING"
            if ckpt is not None:
                ckpt.running()
                # stage-boundary barriers ride the DCN scheduler
                # (dist/scheduler._checkpoint_stage reads this handle);
                # serial path only, so one query owns it at a time
                if self._dcn is not None:
                    self._dcn.checkpoint_handle = ckpt
            try:
                if runner is None:
                    runner = self._runner_factory(q.session)
                result = runner.execute(q.sql, trace=tr)
                if tr is not None:
                    tr.phase("encode")
                types = result.column_types or [
                    "unknown" for _ in result.column_names
                ]
                q.columns = [
                    {"name": n, "type": t}
                    for n, t in zip(result.column_names, types)
                ]
                q.rows = [_json_row(r, types) for r in result.rows]
                q.update_type = result.update_type
                if result.update_type == "SET SESSION":
                    # surface the new value so clients echo it back
                    # (X-Presto-Set-Session round trip)
                    from presto_tpu.sql.parser import parse
                    from presto_tpu.sql import ast_nodes as N

                    stmt = parse(q.sql)
                    if isinstance(stmt, N.SetSession):
                        q.set_session[stmt.name] = str(stmt.value)
                if not q.cancelled:
                    q.state = "FINISHED"
                    if ckpt is not None:
                        # results exist but the client hasn't drained
                        # them: the record survives (with columns +
                        # row count) until the stream completes, so a
                        # restart mid-delivery can regenerate + verify
                        ckpt.finished(q.columns or [], len(q.rows))
            except Exception as e:  # noqa: BLE001 - the protocol
                # surfaces EVERY query failure as a FAILED state with
                # an error body (reference: QueryResults.error), never
                # as a dropped HTTP connection
                if not q.cancelled:
                    q.error = {
                        "message": str(e)[:2000],
                        "errorName": type(e).__name__,
                    }
                    q.state = "FAILED"
                    if ckpt is not None:
                        ckpt.failed(str(e), type(e).__name__)
            finally:
                if ckpt is not None and self._dcn is not None:
                    self._dcn.checkpoint_handle = None
                q._finish_clock()
                q.done.set()
                self._record_completion(q)
                self._accumulate_exec_totals(runner)

    def _accumulate_exec_totals(self, runner) -> None:
        """Fold one finished query's launch/batch counters into the
        process aggregate (concurrent path only — the serial path's
        bootstrap executor already IS the process surface, and adding
        it here would double-count). Per-attempt gauges carry the
        final attempt's values, matching EXPLAIN ANALYZE."""
        if self.memory is None or runner is None:
            return
        ex = getattr(runner, "executor", None)
        if ex is None:
            return
        with self._lock:
            t = self.exec_counter_totals
            for name in self._EXEC_TOTAL_SUMS:
                t[name] = t.get(name, 0) + int(getattr(ex, name, 0))
            for name in self._EXEC_TOTAL_MAX:
                t[name] = max(
                    t.get(name, 0), int(getattr(ex, name, 0)))

    def _record_completion(self, q: _Query) -> None:
        from presto_tpu import events as E
        from presto_tpu import obs as OBS

        self._queue_exit(q)
        if q.trace is not None:
            # the root ends at the query's own finish clock, so the
            # phases tile elapsedTimeMillis
            OBS.close(q.trace, q.session.get("query_trace_dir"),
                      at_mono=q.finished_mono)
        wall_ms = q.info()["elapsedTimeMillis"]
        with self._lock:
            self.completed_by_state[q.state] = (
                self.completed_by_state.get(q.state, 0) + 1
            )
            self.rows_returned_total += len(q.rows)
            self.query_wall_ms_total += wall_ms
        # histogram observations (internally locked): query latency
        # always; per-stage wall when the query was traced
        self.latency_histo.observe(wall_ms / 1000.0)
        query_info = None
        if q.trace is not None:
            query_info = q.trace.to_info()
            for stage in query_info["stages"]:
                self.stage_histo.observe(stage["wallMs"] / 1000.0)
        E.dispatch(
            self.listeners, "query_completed", E.QueryCompletedEvent(
                query_id=q.id, sql=q.sql, user=q.session.user,
                state=q.state, create_time=q.created,
                end_time=q.finished_at or time.time(),
                wall_ms=wall_ms,
                row_count=len(q.rows),
                error_name=(q.error or {}).get("errorName"),
                error_message=(q.error or {}).get("message"),
                query_info=query_info,
            ),
            on_error=self._listener_error,
        )

    def metrics_text(self, uptime: float, executor=None) -> str:
        """Prometheus text exposition (reference role: JMX beans +
        presto-jmx; a /metrics scrape replaces the MBean server)."""
        lines = [
            "# TYPE presto_tpu_uptime_seconds gauge",
            f"presto_tpu_uptime_seconds {uptime:.3f}",
            "# TYPE presto_tpu_queries_total counter",
        ]
        with self._lock:
            for state, n in sorted(self.completed_by_state.items()):
                lines.append(
                    f'presto_tpu_queries_total{{state="{state}"}} {n}'
                )
            running = sum(
                1 for q in self._queries.values() if not q.done.is_set()
            )
            lines += [
                "# TYPE presto_tpu_queries_running gauge",
                f"presto_tpu_queries_running {running}",
                "# TYPE presto_tpu_rows_returned_total counter",
                f"presto_tpu_rows_returned_total "
                f"{self.rows_returned_total}",
                "# TYPE presto_tpu_query_wall_ms_total counter",
                f"presto_tpu_query_wall_ms_total "
                f"{self.query_wall_ms_total}",
            ]
        # latency histograms (obs/histo.py): bucketed for p50/p95/p99
        # — Prometheus-native histogram exposition, the surface the
        # concurrent-load benchmark (ROADMAP item 1) scrapes
        lines += self.latency_histo.prom_lines(
            "presto_tpu_query_latency_seconds")
        lines += self.stage_histo.prom_lines(
            "presto_tpu_stage_wall_seconds")
        if executor is not None:
            # device-memory governor (exec/membudget.py): resolved
            # budget plus the last attempt's peak
            lines += [
                "# TYPE presto_tpu_device_memory_budget_bytes gauge",
                f"presto_tpu_device_memory_budget_bytes "
                f"{executor._budget()}",
                "# TYPE presto_tpu_peak_device_bytes gauge",
                f"presto_tpu_peak_device_bytes "
                f"{executor.peak_memory_bytes}",
            ]
            # every declared execution counter (exec/counters.py): the
            # registry IS the exposition list, so a counter added to
            # the engine cannot silently miss the fleet surface (the
            # pre-registry wiring lost split_batch_fallbacks and the
            # spill counters). Lifetime counters keep their historical
            # _total suffix.
            from presto_tpu.exec import counters as CTRS

            snap = CTRS.snapshot(executor)
            # result-cache totals come from the PROCESS-shared store,
            # not the bootstrap executor: on the concurrent path each
            # query runs its own executor whose counters are
            # discarded, while the store the queries actually shared
            # keeps the fleet truth (the hit-rate surface /metrics
            # serves)
            snap.update(_result_cache_totals())
            # transfer counters overlay the same way (exec/xfer.py
            # process totals — the aggregate copy tax next to QPS/p99)
            xf = _xfer_totals()
            snap.update({k: int(v) for k, v in xf.items()
                         if k in CTRS.QUERY_COUNTERS})
            # exchange wire/codec + connection-reuse totals ride the
            # same process-shared overlay (dist/serde, dist/connpool)
            snap.update({k: int(v) for k, v in _wire_totals().items()
                         if k in CTRS.QUERY_COUNTERS})
            # launch/batch totals accumulate across the concurrent
            # path's discarded per-query executors (ISSUE 17): sums
            # ADD to the bootstrap executor's own counts (zero when
            # idle), the width gauge takes the max — the aggregate
            # launches-per-query truth
            with self._lock:
                for name in self._EXEC_TOTAL_SUMS:
                    snap[name] = snap.get(name, 0) + \
                        self.exec_counter_totals.get(name, 0)
                for name in self._EXEC_TOTAL_MAX:
                    snap[name] = max(
                        snap.get(name, 0),
                        self.exec_counter_totals.get(name, 0))
            for name, (kind, _help) in CTRS.QUERY_COUNTERS.items():
                suffix = "_total" if kind == "counter" else ""
                lines += [
                    f"# TYPE presto_tpu_{name}{suffix} {kind}",
                    f"presto_tpu_{name}{suffix} {snap[name]}",
                ]
            lines += [
                "# TYPE presto_tpu_transfer_wall_seconds gauge",
                f"presto_tpu_transfer_wall_seconds "
                f"{xf['transfer_wall_s']}",
            ]
        # program load, split (compilecache.py): process totals —
        # what every program's first call in this process cost, by
        # part; the registry's programs_compiled/program_cache_hits
        # above are the last statement's
        from presto_tpu import compilecache

        cc = compilecache.snapshot()
        for name in ("program_trace_wall_s", "program_lower_wall_s",
                     "program_retrieval_wall_s", "compile_wall_s"):
            lines += [f"# TYPE presto_tpu_{name} gauge",
                      f"presto_tpu_{name} {cc[name]:.6f}"]
        for name, key in (
            ("programs_traced", "programs_traced"),
            ("programs_lowered", "programs_lowered"),
            ("process_programs_compiled", "programs_compiled"),
            ("process_program_cache_hits", "program_cache_hits"),
        ):
            lines += [f"# TYPE presto_tpu_{name}_total counter",
                      f"presto_tpu_{name}_total {cc[key]}"]
        # cache-aware admission (ISSUE 17): replays that never took a
        # resource-group slot — next to the hit-rate, so a reader sees
        # that near-zero-cost hits stop occupying the queue
        with self._lock:
            bypasses = self.cache_admission_bypasses
            peak_q = self.peak_queued
        lines += [
            "# TYPE presto_tpu_admission_cache_bypasses_total counter",
            f"presto_tpu_admission_cache_bypasses_total {bypasses}",
            "# TYPE presto_tpu_peak_queued gauge",
            f"presto_tpu_peak_queued {peak_q}",
        ]
        return "\n".join(lines) + "\n"


_DECIMAL_RE = re.compile(r"decimal\((\d+),\s*(\d+)\)")


def _render_decimal(unscaled: int, scale: int) -> str:
    """Engine-internal unscaled int -> SQL decimal text (reference:
    server/protocol renders decimals scaled: 1529698.00, never the raw
    152969800)."""
    if scale == 0:
        return str(int(unscaled))
    u = int(unscaled)
    sign = "-" if u < 0 else ""
    u = abs(u)
    return f"{sign}{u // 10**scale}.{u % 10**scale:0{scale}d}"


def _json_row(row: tuple, types=None) -> list:
    out = []
    for j, v in enumerate(row):
        t = types[j] if types and j < len(types) else ""
        if v is None:
            out.append(None)
        elif isinstance(v, int) and not isinstance(v, bool) and t:
            m = _DECIMAL_RE.match(t)
            if m:
                out.append(_render_decimal(v, int(m.group(2))))
            elif t == "date":
                import datetime

                out.append(str(
                    datetime.date(1970, 1, 1)
                    + datetime.timedelta(days=v)
                ))
            elif t == "timestamp":
                import datetime

                out.append(
                    (datetime.datetime(1970, 1, 1)
                     + datetime.timedelta(microseconds=v)
                     ).isoformat(sep=" ")
                )
            else:
                out.append(v)
        elif isinstance(v, (bool, int, float, str)):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            if t.startswith("map("):
                # map values serialize as JSON objects (reference:
                # protocol renders MAP as {key: value})
                out.append({str(k): mv for k, mv in v})
            else:
                out.append(_json_value(v))
        else:
            out.append(str(v))
    return out


def _json_value(v):
    if isinstance(v, (tuple, list)):
        return [_json_value(x) for x in v]
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


def _session_snapshot(session: Session) -> Dict:
    """JSON-safe session state for the checkpoint journal: user/
    catalog/schema plus the EXPLICITLY set properties (typed values
    are already JSON-shaped) — enough to reconstruct an equivalent
    Session on a restarted coordinator."""
    return {
        "user": session.user,
        "catalog": session.catalog,
        "schema": session.schema,
        "values": {
            k: v for k, v in session._values.items()
            if v is None or isinstance(v, (bool, int, float, str))
        },
    }


class _DcnServerRunner:
    """The serial path's runner when a worker fleet is configured
    (ISSUE 20): plain queries dispatch through the DcnRunner (stage
    DAG / legacy cuts / local fallback), everything else — SET, DDL,
    SHOW, EXPLAIN, prepared statements — runs on the local engine
    directly. The DCN coordinator's final stage executes on the SAME
    bootstrap runner/executor, so sessions, traces and counters are
    one surface either way."""

    def __init__(self, dcn, local):
        self._dcn = dcn
        self._local = local

    @property
    def session(self):
        return self._local.session

    @property
    def executor(self):
        return self._local.executor

    def execute(self, sql: str, trace=None):
        from presto_tpu.runner import QueryResult
        from presto_tpu.sql import ast_nodes as N
        from presto_tpu.sql.parser import parse

        try:
            stmt = parse(sql)
        except Exception:  # noqa: BLE001 - not dispatchable: the
            stmt = None    # local path raises the proper error body
        if isinstance(stmt, N.Query):
            self._dcn.handed_trace = trace
            try:
                rows = self._dcn.execute(sql)
            finally:
                self._dcn.handed_trace = None
            return QueryResult(
                column_names=self._dcn.last_output_names or [],
                rows=rows,
            )
        return self._local.execute(sql, trace=trace)


class _Handler(BaseHTTPRequestHandler):
    server_version = "presto-tpu/0.2"
    protocol_version = "HTTP/1.1"

    # silence default stderr logging
    def log_message(self, fmt, *args):  # noqa: A003
        pass

    @property
    def app(self) -> "PrestoTpuServer":
        return self.server.app  # type: ignore[attr-defined]

    def _send_json(self, obj, status=200, headers=None):
        body = json.dumps(obj).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _session_from_headers(self) -> Session:
        props = {}
        user = self.headers.get("X-Presto-User", "presto")
        access = self.app._runner.access_control
        hdr = self.headers.get("X-Presto-Session", "")
        for part in hdr.split(","):
            part = part.strip()
            if part and "=" in part:
                k, v = part.split("=", 1)
                if k.strip() in SYSTEM_SESSION_PROPERTIES:
                    # header overrides pass the same choke point as
                    # SET SESSION statements (reference:
                    # checkCanSetSystemSessionProperty runs for header-
                    # carried properties too)
                    access.check_can_set_session(user, k.strip())
                    props[k.strip()] = v.strip()
        return Session(
            user=user,
            catalog=self.headers.get("X-Presto-Catalog"),
            schema=self.headers.get("X-Presto-Schema", "default"),
            properties=props,
        )

    def _maybe_task_plane(self, method: str) -> bool:
        """Serve /v1/task* and /v1/fault from the embedded task
        runtime (coordinator+worker single process). Returns True when
        the request was handled."""
        rt = self.app.task_runtime
        if rt is None:
            return False
        split = urlparse(self.path)
        if not (split.path.startswith("/v1/task")
                or split.path.startswith("/v1/fault")):
            return False
        from presto_tpu.server import worker as W

        if method == "POST":
            n = int(self.headers.get("Content-Length", "0"))
            resp = W.route_task_post(rt, split.path,
                                     self.rfile.read(n) or b"{}")
        elif method == "GET":
            resp = W.route_task_get(rt, split.path, split.query)
        else:
            resp = W.route_task_delete(rt, split.path)
        if resp is None:
            return False
        W.write_task_response(self, resp)
        return True

    def do_POST(self):
        path = urlparse(self.path).path
        if self._maybe_task_plane("POST"):
            return
        if path != "/v1/statement":
            self._send_json({"error": "not found"}, 404)
            return
        length = int(self.headers.get("Content-Length", 0))
        sql = self.rfile.read(length).decode()
        from presto_tpu.server.resource_groups import QueryQueueFullError

        from presto_tpu.security import AccessDeniedError

        try:
            session = self._session_from_headers()
            # tailing-cursor mode (ISSUE 14): the stream_tail_enabled
            # session property (set per request via X-Presto-Session —
            # the protocol's per-request flag — or via SET SESSION)
            # turns a query over an append-only stream table into a
            # never-finishing cursor; non-tailable statements fall
            # through to the normal submit path
            if bool(session.get("stream_tail_enabled")):
                q = self.app.start_tail(sql, session)
                if q is not None:
                    self._send_json(self._tail_results(q, 0))
                    return
            q = self.app.manager.submit(sql, session)
        except QueryQueueFullError as e:
            self._send_json({
                "error": {"message": str(e),
                          "errorName": "QUERY_QUEUE_FULL"},
                "stats": {"state": "FAILED"},
            }, 429)
            return
        except AccessDeniedError as e:
            self._send_json({
                "error": {"message": str(e),
                          "errorName": "PERMISSION_DENIED"},
                "stats": {"state": "FAILED"},
            }, 403)
            return
        # brief wait so fast statements (SET SESSION, DDL) answer in one
        # round trip with their headers (reference: ~100ms initial wait)
        q.done.wait(timeout=0.5)
        headers = {}
        for k, v in q.set_session.items():
            headers["X-Presto-Set-Session"] = f"{k}={v}"
        self._send_json(self._results(q, 0), headers=headers)

    def do_GET(self):
        path = urlparse(self.path).path
        if self._maybe_task_plane("GET"):
            return
        parts = [p for p in path.split("/") if p]
        if parts[:2] == ["v1", "statement"] and len(parts) == 4:
            q = self.app.manager.get(parts[2])
            if q is None:
                self._send_json({"error": "no such query"}, 404)
                return
            token = int(parts[3])
            if q.tail is not None:
                # tailing cursor: the poll IS the long-poll (it waits
                # on the append log, not on query completion)
                self._send_json(self._tail_results(q, token))
                return
            # long-poll up to ~1s for progress (reference client behavior)
            q.done.wait(timeout=1.0)
            headers = {}
            for k, v in q.set_session.items():
                headers["X-Presto-Set-Session"] = f"{k}={v}"
            self._send_json(self._results(q, token), headers=headers)
            return
        if parts == ["v1", "query"]:
            # reference: /v1/query lists every tracked query's
            # BasicQueryInfo (live + finished)
            mgr = self.app.manager
            with mgr._lock:
                qs = list(mgr._queries.values())
            self._send_json([
                q.info() for q in sorted(qs, key=lambda x: x.id)
            ])
            return
        if parts[:2] == ["v1", "query"] and len(parts) == 3:
            # the full QueryInfo/StageInfo/TaskInfo tree, served LIVE
            # mid-query from the active trace (obs/trace.to_info)
            info = self.app.manager.query_info(parts[2])
            if info is None:
                self._send_json({"error": "no such query"}, 404)
                return
            self._send_json(info)
            return
        if parts == ["v1", "info"] or parts == ["v1", "status"]:
            info = {
                "nodeId": "presto-tpu-coordinator",
                "coordinator": True,
                "uptime": time.time() - self.app.started,
                "backend": self.app.backend_name,
            }
            from presto_tpu.obs import sanitizer as SAN

            if SAN.is_armed():
                # sanitized chaos runs poll the coordinator subprocess
                # the same way they poll workers (worker.py /v1/info)
                info["sanitizerViolations"] = SAN.violation_count()
            self._send_json(info)
            return
        if parts == ["v1", "resourceGroup"]:
            rg = self.app.manager.resource_groups
            self._send_json(rg.snapshot() if rg else [])
            return
        if parts == ["v1", "node"]:
            # reference: /v1/node lists cluster members with health
            # (DiscoveryNodeManager + HeartbeatFailureDetector view)
            det = self.app.failure_detector
            self._send_json(det.snapshot() if det else [])
            return
        if parts == ["metrics"]:
            body = self.app.manager.metrics_text(
                time.time() - self.app.started,
                executor=self.app._runner.executor,
            ).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        self._send_json({"error": "not found"}, 404)

    def do_DELETE(self):
        if self._maybe_task_plane("DELETE"):
            return
        parts = [p for p in urlparse(self.path).path.split("/") if p]
        if parts[:2] == ["v1", "statement"] and len(parts) >= 3:
            q = self.app.manager.get(parts[2])
            ok = self.app.manager.cancel(parts[2])
            if q is not None and q.tail is not None:
                # stop tailing: wake blocked pollers, final GET then
                # serves the remaining rows with no nextUri
                q.tail.close()
            self._send_json({"cancelled": ok})
            return
        self._send_json({"error": "not found"}, 404)

    # --------------------------------------------------------- protocol
    def _tail_results(self, q: _Query, token: int) -> Dict:
        """Protocol page for a tailing cursor (ISSUE 14): a fresh
        token first POLLS (long-polling the append log up to
        stream_poll_ms), then serves the rows the poll derived from
        new offsets; known tokens re-serve their recorded span.
        nextUri persists until the cursor is cancelled/closed — empty
        pages with a fresh nextUri are the idle-tail heartbeat."""
        cur = q.tail
        base = f"http://{self.headers.get('Host', 'localhost')}"
        out: Dict = {
            "id": q.id,
            "infoUri": f"{base}/v1/query/{q.id}",
            "stats": {
                "state": q.state,
                "queued": False,
                "elapsedTimeMillis": q.info()["elapsedTimeMillis"],
                "tail": True,
            },
        }
        fresh = token >= cur.spans_served()
        if fresh and not q.cancelled and not cur.closed:
            cur.poll(cur.poll_ms / 1000.0)
        if cur.error is not None:
            q.error = cur.error
            q.state = "FAILED"
            q._finish_clock()
            q.done.set()
            out["stats"]["state"] = "FAILED"
            out["error"] = cur.error
            return out
        chunk = cur.take_span(token)
        if chunk is None:
            out["error"] = {
                "message": f"unknown result token {token}",
                "errorName": "INVALID_TOKEN",
            }
            return out
        if cur.columns is not None:
            out["columns"] = cur.columns
        if chunk:
            out["data"] = chunk
        done = (q.cancelled or cur.closed) and \
            token + 1 >= cur.spans_served()
        if done:
            out["stats"]["state"] = q.state
        else:
            out["nextUri"] = f"{base}/v1/statement/{q.id}/{token + 1}"
        return out

    def _results(self, q: _Query, token: int) -> Dict:
        base = f"http://{self.headers.get('Host', 'localhost')}"
        out: Dict = {
            "id": q.id,
            "infoUri": f"{base}/v1/query/{q.id}",
            "stats": {
                "state": q.state,
                "queued": q.state == "QUEUED",
                "elapsedTimeMillis": q.info()["elapsedTimeMillis"],
            },
        }
        if q.error is not None:
            out["error"] = q.error
            return out
        if not q.done.is_set():
            # still running: client polls the same token
            out["nextUri"] = f"{base}/v1/statement/{q.id}/{token}"
            return out
        if q.columns is not None:
            out["columns"] = q.columns
        if q.update_type:
            out["updateType"] = q.update_type
        lo = token * _PAGE_ROWS
        hi = lo + _PAGE_ROWS
        chunk = q.rows[lo:hi]
        if chunk:
            out["data"] = chunk
        ckpt = q.checkpoint
        if hi < len(q.rows):
            out["nextUri"] = f"{base}/v1/statement/{q.id}/{token + 1}"
            if ckpt is not None:
                # protocol-token barrier (ISSUE 20): this page is now
                # in the client's hands — a restarted coordinator must
                # resume the stream AT token+1 with this page's digest
                # verified against the regenerated rows
                from presto_tpu.dist.checkpoint import page_digest

                ckpt.note_client_token(token + 1, page_digest(chunk))
        elif ckpt is not None:
            # stream fully delivered: nothing left to recover
            ckpt.delivered()
        return out


class PrestoTpuServer:
    """Reference: server/PrestoServer.java + StatementResource wiring."""

    def __init__(
        self,
        catalogs,
        default_catalog: str = "tpch",
        port: int = 8080,
        mesh=None,
        page_rows: int = 1 << 18,
        event_listeners=(),
        peer_uris=(),
        plugins=(),
        resource_groups=None,
        memory_budget_bytes: Optional[int] = None,
        session_defaults=None,
        worker_tasks: bool = False,
        worker_uris=(),
        checkpoint_dir: str = "",
    ):
        from presto_tpu.runner import LocalRunner

        event_listeners = list(event_listeners)
        for p in plugins:
            event_listeners.extend(p.event_listeners())
        self.catalogs = catalogs
        self.port = port
        self.started = time.time()
        # peer health monitoring (reference: HeartbeatFailureDetector
        # over discovered nodes; ours watches configured peer slices)
        self.failure_detector = None
        if peer_uris:
            from presto_tpu.server.heartbeat import (
                HeartbeatFailureDetector,
            )

            self.failure_detector = HeartbeatFailureDetector(
                list(peer_uris)
            )
        # a JAX runtime that fails to initialise is an error at start,
        # not a server that answers /v1/info with an unknown backend
        import jax

        self.backend_name = jax.default_backend()

        # bootstrap runner installs plugins into catalogs/registries;
        # it also serves the serial (no-arbiter) path
        self._runner = LocalRunner(
            catalogs, default_catalog=default_catalog,
            page_rows=page_rows, mesh=mesh, plugins=plugins,
        )
        self.catalogs = self._runner.catalogs  # incl. plugin catalogs
        # compiled kernels shared across per-query executors (the
        # compiled-expression LRU is process-wide in the reference too)
        self._shared_jit_cache = self._runner.executor._jit_cache
        self._mesh = mesh
        self._page_rows = page_rows
        self._default_catalog = default_catalog

        # distributed dispatch plane (ISSUE 20): a configured worker
        # fleet makes this server a DCN coordinator — plain queries on
        # the serial path execute through DcnRunner (stage DAG, legacy
        # cuts, local fallback), with the coordinator-side final stage
        # running on THE bootstrap runner/executor so sessions, traces
        # and every dist counter surface on /metrics + system.metrics
        self._dcn = None
        if worker_uris:
            from presto_tpu.dist.dcn import DcnRunner

            self._dcn = DcnRunner(
                self.catalogs, list(worker_uris),
                default_catalog=default_catalog,
                page_rows=page_rows,
            )
            self._dcn.runner = self._runner
        # durable coordinator journal (ISSUE 20 tentpole): configured
        # via the checkpoint.dir etc key / this kwarg; a bare
        # checkpoint_dir SESSION property instead binds lazily in the
        # manager at first enabled submit
        self._journal = None
        if checkpoint_dir:
            from presto_tpu.dist.checkpoint import CheckpointJournal

            self._journal = CheckpointJournal(
                checkpoint_dir, counter_ex=self._runner.executor)

        memory_arbiter = None
        # cross-query launch batching (ISSUE 17): ONE shared batch
        # point for the concurrent path's per-query executors —
        # attachment is what "auto" resolves against, so the serial
        # path and raw Executors never batch
        self._launch_batcher = None
        if memory_budget_bytes:
            memory_arbiter = MemoryArbiter(memory_budget_bytes)
            from presto_tpu.server.launch_batcher import LaunchBatcher

            self._launch_batcher = LaunchBatcher()

        # fail-fast validation: a bad deployment default (unknown name,
        # rejected value) must abort startup, not fail every query.
        # Kept introspectable (tests/test_config_etc.py verifies the
        # etc-registry plumbing against it; SHOW-style tooling can too)
        self.session_defaults = dict(session_defaults or {})
        if session_defaults:
            Session(properties=session_defaults)

        def seed_session(session: Session):
            # deployment-tier session defaults (etc/config.properties,
            # see config.server_from_etc): seed properties the client
            # session did not explicitly set — an explicit
            # X-Presto-Session header or SET SESSION always wins.
            # Seeded values read as set() for this query's session (a
            # deployment default behaves like a header-supplied
            # property); they re-seed on every query, so there is no
            # cross-query unset() path back to the code default.
            for k, v in (session_defaults or {}).items():
                if not session.is_set(k):
                    session.set(k, v)
            # the server traces queries by default (ISSUE 9): the
            # /v1/query/{id} tree, system.runtime_tasks, and the
            # stage-wall histogram all read the lifecycle trace. An
            # explicit client/deployment off always wins.
            if not session.is_set("query_trace_enabled"):
                session.set("query_trace_enabled", True)

        def runner_factory(session: Session):
            # the manager seeds at submission (before it makes the
            # statement's trace); a factory called directly seeds too
            seed_session(session)
            if memory_arbiter is None:
                # serial path: one engine, re-sessioned per query;
                # with a worker fleet, plain queries route through the
                # DCN dispatch plane on that same engine
                self._runner.session = session
                if self._dcn is not None:
                    return _DcnServerRunner(self._dcn, self._runner)
                return self._runner
            # the concurrent server defaults the result cache ON
            # (ISSUE 17): the process-shared store is what collapses
            # repeated dashboard statements across per-query runners,
            # and cache-aware admission needs hits to exist to bypass
            # the queue. Raw Executor / serial-path / library defaults
            # stay off; an explicit client/deployment off wins.
            if not session.is_set("result_cache_enabled"):
                session.set("result_cache_enabled", True)
            # concurrent path: per-query runner/executor so query state
            # (overflow flags, capacity boosts, stream caches) never
            # crosses queries; compiled kernels and views are server-
            # wide (reference: views live in connector metadata); the
            # prepared registry is shared but keyed per user inside
            # LocalRunner, mirroring the reference's session scoping
            r = LocalRunner(
                self.catalogs, default_catalog=self._default_catalog,
                page_rows=self._page_rows, mesh=self._mesh,
                session=session,
            )
            r.executor._jit_cache = self._shared_jit_cache
            # every per-query executor shares THE batch point: a
            # compatible launch from any of them can lead or join a
            # gather group (runner.apply_session resolves the
            # session's cross_query_batching against this attachment)
            r.executor.launch_batcher = self._launch_batcher
            r.views = self._runner.views
            r.prepared = self._runner.prepared
            r.access_control = self._runner.access_control
            return r

        self.manager = QueryManager(
            runner_factory,
            listeners=event_listeners,
            resource_groups=resource_groups,
            memory_arbiter=memory_arbiter,
            # swallowed listener exceptions land on the bootstrap
            # executor's listener_errors registry counter
            listener_error_counter=(
                self._runner.executor.count_listener_error),
            journal=self._journal,
            counter_executor=self._runner.executor,
            dcn=self._dcn,
            session_seed=seed_session,
        )
        if self._launch_batcher is not None:
            # gather only when there is someone to gang with: a lone
            # client on the concurrent path must never pay the window
            mgr = self.manager

            def _running_queries() -> int:
                with mgr._lock:
                    return sum(1 for q in mgr._queries.values()
                               if q.state == "RUNNING")

            self._launch_batcher.concurrency_probe = _running_queries
        # coordinator+worker single process (reference: a node that is
        # both coordinator and worker): an embedded task runtime makes
        # this server a full DCN peer — it serves the /v1/task control
        # plane and the spooled-exchange fetch/ack data plane
        # (server/worker.route_task_*), so a DcnRunner or stage-DAG
        # scheduler can pool it like any worker
        self.task_runtime = None
        if worker_tasks:
            from presto_tpu.server.worker import TaskRuntime

            self.task_runtime = TaskRuntime(
                self.catalogs, node_id="coordinator-worker",
                default_catalog=default_catalog, page_rows=page_rows,
            )
        self._install_runtime_tables()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # crash re-attach (ISSUE 20): pick up every query a previous
        # coordinator process journaled but never delivered. Claimed
        # once per journal+process so a double-constructed server
        # can't run the pass twice.
        if self._journal is not None and self._journal.claim_reattach():
            self._reattach_pending()

    def _reattach_pending(self) -> None:
        """Register a _Query stub (under its ORIGINAL id — the
        client's persisted nextUri names it) for every journaled
        in-flight query and recover each on a daemon thread through
        dist.checkpoint.reattach_query: surviving producer spools
        resume, dead placements re-dispatch from persisted payloads,
        anything non-recoverable fails loudly with
        CoordinatorRestarted — never a hang."""
        pending = self._journal.pending()
        if not pending:
            return
        mgr = self.manager
        for qid in sorted(pending):
            rec = pending[qid]
            sess = rec.get("session") or {}
            try:
                session = Session(
                    user=sess.get("user", "presto"),
                    catalog=(sess.get("catalog")
                             or self._default_catalog),
                    schema=sess.get("schema", "default"),
                    properties=sess.get("values") or None,
                )
            except Exception:  # noqa: BLE001 - version skew on a
                # persisted property must not kill the whole pass:
                # recover the query under a default session instead
                session = Session(catalog=self._default_catalog)
            from presto_tpu.dist.checkpoint import QueryCheckpoint

            q = _Query(qid, rec.get("sql") or "", session)
            q.state = "RUNNING"
            q.checkpoint = QueryCheckpoint(self._journal, qid)
            with mgr._lock:
                mgr._queries[qid] = q
            threading.Thread(
                target=self._reattach_run, args=(q, rec), daemon=True
            ).start()

    def _reattach_run(self, q: _Query, rec: Dict) -> None:
        from presto_tpu.dist import checkpoint as CKPT

        ckpt = q.checkpoint
        try:
            if rec.get("state") == "failed":
                # the query had already failed: resurface the SAME
                # error body at the client's persisted nextUri
                q.error = rec.get("error") or {
                    "message": "query failed before the restart",
                    "errorName": "QueryFailed",
                }
                q.state = "FAILED"
                return
            # serialize against live queries: the recovery re-executes
            # on the shared serial engine
            with self.manager._exec_lock:
                self._runner.session = q.session
                res = CKPT.reattach_query(
                    rec, self._dcn, self._runner.executor)
            cols = rec.get("columns")
            if not cols:
                cols = [{"name": n, "type": "unknown"}
                        for n in res.column_names]
            types = [c["type"] for c in cols]
            rows = [_json_row(r, types) for r in res.rows]
            # verify every page the OLD process already handed the
            # client against the regenerated rows — the stream only
            # resumes when the delivered prefix is byte-identical
            page_sha = rec.get("page_sha") or {}
            for i in range(int(rec.get("token") or 0)):
                want = page_sha.get(str(i))
                got = CKPT.page_digest(
                    rows[i * _PAGE_ROWS:(i + 1) * _PAGE_ROWS])
                if want is not None and got != want:
                    raise CKPT.CoordinatorRestarted(
                        f"resumed result stream diverges at page {i}"
                        " (digest mismatch with the delivered prefix)"
                    )
            q.columns = cols
            q.rows = rows
            q.state = "FINISHED"
            if ckpt is not None:
                ckpt.finished(cols, len(rows))
        except Exception as e:  # noqa: BLE001 - the loud-fail leg of
            # the recovery contract: any non-recoverable state becomes
            # a FAILED query at the client's nextUri, never a hang
            q.error = {
                "message": str(e)[:2000],
                "errorName": ("CoordinatorRestarted"
                              if isinstance(e, CKPT.CoordinatorRestarted)
                              else type(e).__name__),
            }
            q.state = "FAILED"
            if ckpt is not None:
                ckpt.failed(str(e), q.error["errorName"])
        finally:
            q._finish_clock()
            q.done.set()
            self.manager._record_completion(q)

    def _install_runtime_tables(self) -> None:
        """system.runtime_queries / nodes / metrics over live server
        state (reference: system.runtime.* tables + the jmx connector's
        SQL-over-metrics)."""
        sys_conn = self.catalogs.get("system")
        if sys_conn is None or not hasattr(sys_conn, "register"):
            return
        V, B = T.VARCHAR, T.BIGINT
        mgr = self.manager

        def runtime_queries():
            out = []
            with mgr._lock:
                queries = list(mgr._queries.values())
            for q in queries:
                info = q.info()
                out.append((
                    q.id, q.state, q.session.user, q.sql,
                    info["elapsedTimeMillis"], len(q.rows),
                ))
            return sorted(out)

        def nodes():
            me = (f"http://127.0.0.1:{self.port}", "active", 1)
            peers = []
            fd = self.failure_detector
            if fd is not None:
                for info in fd.snapshot():
                    # one vocabulary with the coordinator row:
                    # active / failed
                    alive = info.get("state") == "ALIVE"
                    peers.append((
                        info.get("uri"),
                        "active" if alive else "failed",
                        0,
                    ))
            return [me] + sorted(peers)

        def metrics():
            with mgr._lock:
                out = [
                    ("rows_returned_total", mgr.rows_returned_total),
                    ("query_wall_ms_total", mgr.query_wall_ms_total),
                ]
                by_state = dict(mgr.completed_by_state)
            for state, n in sorted(by_state.items()):
                out.append((f"queries_completed_{state.lower()}", n))
            # device-memory governor (exec/membudget.py): the serial
            # runner's resolved budget and last-attempt peak — the
            # fleet-visible half of the peak_device_bytes contract
            ex = self._runner.executor
            out.append(("device_memory_budget_bytes", ex._budget()))
            out.append(("peak_device_bytes", ex.peak_memory_bytes))
            # every declared execution counter (exec/counters.py),
            # queryable with SQL like every other engine metric — the
            # same registry /metrics and EXPLAIN ANALYZE render, so
            # the three surfaces cannot drift
            from presto_tpu.exec import counters as CTRS

            snap = CTRS.snapshot(ex)
            # same process-shared overlay as /metrics (see
            # _result_cache_totals): one truth on both surfaces
            snap.update(_result_cache_totals())
            xf = _xfer_totals()
            snap.update({k: int(v) for k, v in xf.items()
                         if k in CTRS.QUERY_COUNTERS})
            snap.update({k: int(v) for k, v in _wire_totals().items()
                         if k in CTRS.QUERY_COUNTERS})
            # launch/batch totals: same overlay as /metrics (see
            # QueryManager.metrics_text) so the two surfaces agree
            with mgr._lock:
                for name in mgr._EXEC_TOTAL_SUMS:
                    snap[name] = snap.get(name, 0) + \
                        mgr.exec_counter_totals.get(name, 0)
                for name in mgr._EXEC_TOTAL_MAX:
                    snap[name] = max(
                        snap.get(name, 0),
                        mgr.exec_counter_totals.get(name, 0))
                bypasses = mgr.cache_admission_bypasses
                peak_q = mgr.peak_queued
            out.extend(sorted(snap.items()))
            # the float crossing wall rides as integer milliseconds
            # (system.metrics values are BIGINT)
            out.append(("transfer_wall_ms",
                        int(xf["transfer_wall_s"] * 1000)))
            out.append(("admission_cache_bypasses", bypasses))
            out.append(("peak_queued", peak_q))
            return out

        def runtime_tasks():
            # the task-level runtime table (reference:
            # system.runtime.tasks): one row per stage task from the
            # SAME QueryInfo tree /v1/query/{id} serves, so the two
            # surfaces cannot disagree
            with mgr._lock:
                qids = list(mgr._queries)
            out = []
            for qid in qids:
                info = mgr.query_info(qid)
                if not info:
                    continue
                for stage in info.get("stages", ()):
                    for t in stage["tasks"]:
                        out.append((
                            qid, str(stage["stageId"]), t["taskId"],
                            t["state"], t.get("uri") or "",
                            int(t["wallMs"]),
                            int(t.get("rows") or 0),
                            int(t.get("retries") or 0),
                        ))
            return sorted(out)

        sys_conn.register(
            "runtime_queries",
            [("query_id", V), ("state", V), ("user", V), ("query", V),
             ("elapsed_ms", B), ("result_rows", B)],
            runtime_queries,
        )
        sys_conn.register(
            "runtime_tasks",
            [("query_id", V), ("stage_id", V), ("task_id", V),
             ("state", V), ("uri", V), ("wall_ms", B), ("rows", B),
             ("retries", B)],
            runtime_tasks,
        )
        sys_conn.register(
            "nodes",
            [("uri", V), ("state", V), ("is_coordinator", B)], nodes,
        )
        sys_conn.register(
            "metrics", [("name", V), ("value", B)], metrics,
        )

    def start_tail(self, sql: str,
                   session: Session) -> Optional[_Query]:
        """Register a tailing cursor for ``sql`` when it is tailable
        (ISSUE 14): a plain query, local engine, scanning at least
        one append-only stream table. None otherwise — the statement
        then runs the normal protocol path (which also surfaces its
        parse/plan/access errors with the ordinary error body).
        Tailing statements pass the SAME resource-group queue gate as
        submitted ones (QueryQueueFullError surfaces as 429); the
        slot releases when the cursor closes."""
        rg = self.manager.resource_groups
        group = rg.admit(session.user) if rg is not None else None
        cursor = self.make_tail_cursor(sql, session)
        if cursor is None:
            if group is not None:
                rg.cancel_queued(group)
            return None
        with cursor._cv:
            cursor.resource_group = group
        cursor._rg_manager = rg
        return self.manager.register_tail(sql, session, cursor)

    def make_tail_cursor(self, sql: str,
                         session: Session) -> Optional[TailCursor]:
        if self._mesh is not None:
            return None  # tail cursors ride the local executor
        # cheap pre-check before ANY planning work: a deployment with
        # no append-only catalog can never tail — a session that left
        # stream_tail_enabled on must not pay a throwaway runner and
        # a second planning pass per ordinary statement
        if not any(getattr(c, "append_only", False)
                   for c in self.catalogs.values()):
            return None
        from presto_tpu.runner import LocalRunner
        from presto_tpu.sql import ast_nodes as N
        from presto_tpu.sql.parser import parse

        try:
            stmt = parse(sql)
        except Exception:  # noqa: BLE001 - not tailable; the normal
            return None    # path surfaces the parse error properly
        if not isinstance(stmt, N.Query):
            return None  # DDL/SET/EXPLAIN/... never tail
        # dedicated runner (the concurrent-path shape): cursor polls
        # run on protocol handler threads and must never race the
        # serial bootstrap runner's queries
        r = LocalRunner(
            self.catalogs, default_catalog=self._default_catalog,
            page_rows=self._page_rows, session=session,
        )
        r.executor._jit_cache = self._shared_jit_cache
        r.views = self._runner.views
        r.prepared = self._runner.prepared
        r.access_control = self._runner.access_control
        try:
            r.access_control.check_can_execute_query(
                session.user, sql)
            r.apply_session()
            plan = r._plan_statement_query(stmt)
        except Exception:  # noqa: BLE001 - not tailable; the normal
            return None    # path surfaces plan/access errors properly
        from presto_tpu.cache.rules import scan_tables

        streams = [
            (c, t) for c, t in sorted(scan_tables(plan))
            if getattr(r.catalogs.get(c), "append_only", False)
        ]
        if not streams:
            return None  # nothing appends: a plain finite statement
        return TailCursor(r, plan, streams,
                          sink=self._runner.executor)

    def start(self) -> int:
        self._httpd = ThreadingHTTPServer(("127.0.0.1", self.port), _Handler)
        self._httpd.app = self  # type: ignore[attr-defined]
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        if self.failure_detector:
            self.failure_detector.start()
        if self.task_runtime is not None:
            # coordinator+worker single process: register the embedded
            # runtime so same-process consumers and the stage-DAG root
            # drain take its spooled Pages directly (mesh-local
            # exchange fast path, server/worker registry)
            from presto_tpu.server.worker import register_local_runtime

            register_local_runtime(
                f"http://127.0.0.1:{self.port}", self.task_runtime)
        return self.port

    def stop(self) -> None:
        if self.task_runtime is not None:
            from presto_tpu.server.worker import (
                unregister_local_runtime,
            )

            unregister_local_runtime(f"http://127.0.0.1:{self.port}")
        if self.failure_detector:
            self.failure_detector.stop()
        if self._dcn is not None:
            self._dcn.close()
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()

    def serve_forever(self) -> None:  # pragma: no cover - CLI entry
        self.start()
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            self.stop()


def main() -> int:  # pragma: no cover - subprocess entry
    """Coordinator subprocess entry (the kill-coordinator chaos mode's
    victim): boots a PrestoTpuServer over a configured worker fleet
    with a durable checkpoint journal, prints its port as one JSON
    line, then serves until killed — the harness SIGKILLs this process
    mid-query and boots a successor on the same --checkpoint-dir."""
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--suite", default="tpch")
    parser.add_argument("--scale", type=float, default=0.01)
    parser.add_argument("--page-rows", type=int, default=1 << 16)
    parser.add_argument("--workers", default="",
                        help="comma-separated worker base uris")
    parser.add_argument("--checkpoint-dir", default="")
    args = parser.parse_args()

    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.connectors.tpch import TpchConnector

    cls = TpchConnector if args.suite == "tpch" else TpcdsConnector
    srv = PrestoTpuServer(
        {args.suite: cls(scale=args.scale)}, port=args.port,
        default_catalog=args.suite, page_rows=args.page_rows,
        worker_uris=[u for u in args.workers.split(",") if u],
        checkpoint_dir=args.checkpoint_dir,
    )
    port = srv.start()
    print(json.dumps({"port": port}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
