"""DCN coordinator: multi-process query execution over localhost (or
any network) workers.

Reference: the coordinator half of distributed execution —
server/remotetask/HttpRemoteTask.java (task create + status),
operator/ExchangeClient.java + HttpPageBufferClient.java (token-acked
page fetch with retries), metadata/DiscoveryNodeManager +
failureDetector/HeartbeatFailureDetector (peer liveness).

TPU-native shape (SURVEY §6.8): ICI-scale parallelism stays INSIDE a
worker process as compiled collectives; this layer is the DCN half —
processes exchange serialized pages over HTTP exactly where the
reference does, at one of two fragment boundaries:

    PARTIAL/FINAL aggregation cut (tiny state pages; preferred):
      worker w: scan(splits w::K of fact table) -> ... -> partial agg
      coordinator: RemoteSource(all workers) -> final agg -> rest
    UNION cut (general row-local subtree; multi-join pipelines with
    no decomposable aggregation):
      worker w: row-local subtree over split share -> result pages
      coordinator: RemoteSource union -> sort/topN/window/agg -> rest

Either way the task body carries the coordinator's SERIALIZED physical
fragment (dist/plan_serde.py — the reference's TaskUpdateRequest
PlanFragment); workers execute exactly that tree, never re-planning.
Scans split round-robin or hash-co-partitioned on join keys (both big
join sides 1/N per worker; hash_fanout_source).

Failure model: FAULT-TOLERANT task retry (reference: Project
Tardigrade's task-level retry, "A Decade of SQL Analytics at Meta"
VLDB 2023), made cheap by deterministic generation — a dead worker's
fragment re-dispatches to a surviving ALIVE worker carrying the SAME
split assignment, the survivor re-generates that split share at the
scan (gen_at/key_inverse SPI + connectors/split_filter.py), and pages
the coordinator already consumed dedupe by fetch token — the new
placement's regenerated prefix is VERIFIED byte-identical (rolling
sha256) before the fetch resumes at the consumed token, so delivery
stays effectively exactly-once and a non-deterministic sequence fails
loudly instead of silently — no spooled shuffle tier required.
Governed by session properties: `task_retry_attempts` re-dispatches per
task (0 pins the classic fail-query-cleanly model), `retry_backoff_ms`
seeds the exponential-backoff-with-jitter ladder between retries, and
`query_max_run_time` is a hard wall-clock deadline enforced in the
fetch loop and at executor page boundaries (QueryDeadlineExceeded).
The heartbeat detector is consulted BEFORE task submit so FAILED nodes
are never picked, and every recovery action is observable: the
coordinator executor's `task_retries` / `workers_excluded` counters
(EXPLAIN ANALYZE, /metrics, system.metrics) and `TaskRetryEvent` on
the EventListener SPI.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import random
import time
import urllib.error
import uuid
from typing import Dict, List, Optional

from presto_tpu.dist import connpool as CONNPOOL
from presto_tpu.dist import plan_serde, serde
from presto_tpu.dist import spool as SPOOL
from presto_tpu.exec import faults as FAULTS
from presto_tpu.exec import plan as P
from presto_tpu.exec.executor import QueryDeadlineExceeded
from presto_tpu.server.heartbeat import HeartbeatFailureDetector
from presto_tpu.server.worker import (
    fanout_safe,
    find_partial_cut,
    find_union_cut,
    hash_fanout_plan,
    hash_fanout_source,
    largest_table,
)


class DcnQueryFailed(RuntimeError):
    """Query-level failure: task retries exhausted / no survivors (or,
    with task_retry_attempts=0, the classic fail-query-and-let-the-
    client-retry model with no task-level recovery)."""


class _TaskLost(RuntimeError):
    """Internal: one task placement is gone (submit failure, exhausted
    fetch retries, or — with task_error=True — a deterministic
    worker-side task failure) — the recovery path decides whether to
    re-dispatch or fail the query."""

    def __init__(self, msg: str, task_error: bool = False):
        super().__init__(msg)
        # True when the TASK failed on a healthy worker (the fragment
        # raised; X-Task-Error from the results endpoint): re-dispatch
        # is still attempted (the fault may be environmental) but the
        # node is NOT excluded — workers_excluded counts node loss only
        self.task_error = task_error


@dataclasses.dataclass
class _TaskState:
    """One logical task (= one split share of the fragment) and its
    current placement. `next_token` is the count of pages the
    coordinator has consumed and `hasher` a rolling sha256 of their
    serialized bytes — a re-dispatched task resumes fetching at
    next_token AFTER the new placement's regenerated prefix is verified
    byte-identical to what was consumed (deterministic generation makes
    that the common case; a mismatch — e.g. the survivor's device-OOM
    ladder re-chunked its page boundaries — fails the query loudly
    instead of silently skipping/duplicating rows)."""

    uri: str
    task_id: str
    payload: Dict
    next_token: int = 0
    retries_used: int = 0
    trace_t0: float = 0.0  # dispatch instant on the trace clock
    hasher: "hashlib._Hash" = dataclasses.field(
        default_factory=lambda: hashlib.sha256())


def _replace_node(root, target, repl):
    """Structural replace of one subtree in a frozen plan tree."""
    if root is target:
        return repl
    changes = {}
    for f in dataclasses.fields(root):
        v = getattr(root, f.name)
        if isinstance(v, P.PhysicalNode):
            nv = _replace_node(v, target, repl)
            if nv is not v:
                changes[f.name] = nv
        elif isinstance(v, tuple) and v and isinstance(
            v[0], P.PhysicalNode
        ):
            nv = tuple(_replace_node(x, target, repl) for x in v)
            if any(a is not b for a, b in zip(nv, v)):
                changes[f.name] = nv
    return dataclasses.replace(root, **changes) if changes else root


class DcnRunner:
    """Coordinator over N worker processes (single fat workers each).

    execute(sql) returns (names, rows) like LocalRunner.execute's
    underlying executor, with the heavy PARTIAL pipeline fanned out.
    """

    def __init__(self, catalogs, worker_uris: List[str], *,
                 default_catalog: Optional[str] = None,
                 page_rows: int = 1 << 16,
                 fetch_retries: int = 3,
                 session_props: Optional[Dict] = None,
                 partition_threshold: int = 1 << 17,
                 listeners=()):
        from presto_tpu.runner import LocalRunner
        from presto_tpu.session import Session

        self.worker_uris = list(worker_uris)
        self.fetch_retries = fetch_retries
        self.partition_threshold = partition_threshold
        # introspection: distribution used by the last execute()
        # ("hash" partitioned join | "roundrobin" | "local")
        self.last_distribution = "local"
        # workers the last execute() actually submitted to (the
        # heartbeat-gated pool; FAILED nodes are never picked)
        self.last_pool: List[str] = []
        # stage-DAG introspection: the last StageScheduler (per-stage
        # pools, task placements) and an optional test/chaos hook
        # called after each completed stage (deterministic mid-query
        # fault injection)
        self.last_scheduler = None
        self._stage_hook = None
        # coordinator HA (ISSUE 20): the active query's checkpoint
        # handle (dist/checkpoint.QueryCheckpoint) — the stage
        # scheduler journals placements/root/drain through it; None =
        # checkpointing off
        self.checkpoint_handle = None
        # output column names of the last execute() (every path —
        # DAG, legacy cuts, local fallback): the serving layer needs
        # them for the protocol's columns block
        self.last_output_names: Optional[List[str]] = None
        self.session_props = dict(session_props or {})
        self.listeners = list(listeners)
        # fault-tolerance bookkeeping: nodes excluded after a mid-query
        # failure (re-admitted only on a fresh successful ping — a
        # rebooted worker on the same uri rejoins between queries, the
        # reference's node-rejoin model)
        self._excluded: set = set()
        self._rng = random.Random()
        # the serving coordinator's trace of the statement being run
        # (server/http_server._DcnServerRunner sets it around execute)
        self.handed_trace = None
        cat = default_catalog or next(iter(catalogs))
        self.runner = LocalRunner(
            catalogs,
            page_rows=page_rows,
            default_catalog=cat,
            session=Session(catalog=cat,
                            properties=self.session_props),
        )
        self.heartbeat = HeartbeatFailureDetector(
            [f"{u}" for u in self.worker_uris]
        )
        # fleet-cache index (ISSUE 19): per-worker bloom summaries of
        # cached fragment keys, refreshed by every heartbeat ping —
        # the scheduler's pre-dispatch probe consults it so the
        # common cache miss never touches the wire
        from presto_tpu.dist.cacheprobe import RemoteCacheIndex

        self.cache_index = RemoteCacheIndex()
        self.heartbeat.on_info = self.cache_index.update_from_info
        # background detector: dead-node connect timeouts are paid on
        # the daemon thread, never on the query path (the submit gate
        # reads CACHED state; reference: NodeScheduler consulting an
        # async failure detector)
        self.heartbeat.start()
        # per-node rate limit for synchronous re-admission probes of
        # excluded nodes (a still-dead node costs its connect timeout
        # at most once per heartbeat interval, not per query)
        self._probe_at: Dict[str, float] = {}

    def close(self) -> None:
        """Stop the background heartbeat thread. DcnRunner owns it, so
        long-lived embedders (and the chaos harness) can shut it down
        instead of leaking a pinging daemon per runner."""
        self.heartbeat.stop()

    @property
    def release_skips(self) -> int:
        """DELETE-release skips on dead workers. ONE owner — the
        executor's registry counter (exec/counters.py), which
        /metrics, system.metrics, and EXPLAIN ANALYZE render — so the
        chaos harness and the fleet surfaces can never drift apart."""
        return self.runner.executor.release_skips

    # ------------------------------------------------- session-prop knobs
    def _retry_attempts(self) -> int:
        return int(self.runner.session.get("task_retry_attempts"))

    def _backoff_ms(self) -> int:
        return int(self.runner.session.get("retry_backoff_ms"))

    # --------------------------------------------------------- protocol
    def _task_spans(self, st: _TaskState) -> List[Dict]:
        """One best-effort status poll for a task's worker-side spans
        (queue/run/attempt, shipped on the status plane). Transport
        errors return [] — the timeline loses the worker detail, the
        query loses nothing."""
        try:
            with CONNPOOL.request(
                f"{st.uri}/v1/task/{st.task_id}", timeout=5
            ) as r:
                return json.loads(r.read().decode()).get("spans") or []
        except (urllib.error.URLError, ConnectionError, OSError,
                ValueError):
            return []

    def _post_task(self, uri: str, payload: Dict) -> Dict:
        # connpool never replays a POST on a reused socket — a task
        # submit must reach the worker at most once per attempt
        with CONNPOOL.request(
            f"{uri}/v1/task",
            method="POST",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            timeout=30,
        ) as resp:
            return json.loads(resp.read().decode())

    def _probe_cached_task(self, partial, split_table: str,
                           index: int, count: int, task_id: str,
                           pool) -> Optional[str]:
        """Fleet cache probe for the classic dispatch path (ISSUE
        19): ask bloom-positive pool members to serve this split
        share's fragment from their result cache. Returns the uri
        that parked the pages as pre-finished task ``task_id`` (the
        ordinary spool-fetch plane reads them), or None — every
        failure here is advisory and reads as a miss. Round-robin
        splits only: the hash split mode wraps connectors differently
        on the worker, so its keys are not what this mirror computes."""
        from presto_tpu.dist.cacheprobe import fragment_cache_key

        ex = self.runner.executor
        timeout = self._probe_budget(ex)
        if timeout is None:
            return None
        try:
            key = fragment_cache_key(
                partial, self.runner.catalogs,
                split_table=split_table, split_index=index,
                split_count=count, collect_k=ex.collect_k,
                page_rows=ex.page_rows,
            )
        except Exception:  # noqa: BLE001 - advisory probe
            return None
        if key is None:
            return None
        idx = self.cache_index
        for uri in pool:
            if uri in self._excluded or \
                    not idx.might_contain(uri, key):
                continue
            try:
                with CONNPOOL.request(
                    f"{uri}/v1/cache/task",
                    method="POST",
                    data=json.dumps(
                        {"taskId": task_id, "key": key}).encode(),
                    headers={"Content-Type": "application/json"},
                    timeout=timeout,
                ) as r:
                    out = json.loads(r.read().decode())
            except (urllib.error.URLError, ConnectionError,
                    OSError, ValueError):
                continue  # bloom false positive / slow peer: dispatch
            if out.get("hit"):
                return uri
        return None

    @staticmethod
    def _raise_if_task_error(e: BaseException, uri: str,
                             task_id: str) -> None:
        """X-Task-Error on a results response marks a DETERMINISTIC
        task failure on a healthy worker: surface the real error text
        at once instead of spinning fetch retries against a dead
        task."""
        if isinstance(e, urllib.error.HTTPError) and \
                e.headers.get("X-Task-Error"):
            try:
                msg = json.loads(e.read().decode()).get("error", "")
            except (ValueError, OSError):
                msg = ""
            # classify with the SHARED marker list (exec/faults.py):
            # a worker-side device-memory fault is environmental — the
            # retry message says so, and the coordinator's own OOM
            # ladder stays out of it (is_device_fault's exact-type
            # check rejects _TaskLost even though it quotes the text)
            note = (" [worker device-memory fault]"
                    if FAULTS.text_matches(msg) else "")
            raise _TaskLost(
                f"task {task_id} FAILED on worker {uri}: "
                f"{msg or e}{note}",
                task_error=True,
            ) from e

    @staticmethod
    def _check_deadline(deadline: Optional[float]) -> None:
        if deadline is not None and time.monotonic() > deadline:
            raise QueryDeadlineExceeded(
                "query exceeded query_max_run_time in the DCN fetch "
                "loop"
            )

    def _sleep_backoff(self, attempt: int,
                       deadline: Optional[float]) -> None:
        """Exponential backoff with jitter between retries (reference:
        HttpPageBufferClient's backoff; jitter de-synchronizes N
        coordinators hammering one recovering worker). Never sleeps
        past the query deadline."""
        base = self._backoff_ms() / 1000.0
        delay = min(base * (2 ** max(attempt - 1, 0)), 5.0)
        delay *= 0.5 + self._rng.random()  # jitter: [0.5x, 1.5x)
        if deadline is not None:
            delay = min(delay, max(deadline - time.monotonic(), 0.0))
        if delay > 0:
            time.sleep(delay)

    def _probe_budget(self, ex) -> Optional[float]:
        """Deadline-aware retry budget for the remote-cache probe
        plane (ISSUE 20 satellite): a probe against a dying holder
        must not burn wall clock the query doesn't have. Returns the
        probe timeout — capped at a fraction of the remaining
        query_max_run_time — or None when the deadline can't afford
        one; the caller falls back to normal dispatch."""
        deadline = ex.query_deadline
        if deadline is None:
            return 5.0
        remaining = deadline - time.monotonic()
        if remaining < 2.0:
            return None
        return min(5.0, 0.25 * remaining)

    @staticmethod
    def _deadline_timeout(deadline: Optional[float],
                          cap: float = 60.0) -> float:
        """Per-request timeout bounded by the query's remaining
        deadline (ISSUE 20 satellite: a fetch against a dying worker
        must not block past query_max_run_time — the deadline check on
        the next loop iteration then fails the query on time)."""
        if deadline is None:
            return cap
        return max(1.0, min(cap, deadline - time.monotonic()))

    def _fetch_pages(self, st: _TaskState,
                     deadline: Optional[float]):
        """Token-acked page fetch with bounded, backed-off retries (the
        HttpPageBufferClient protocol: at-least-once + dedupe by
        token). Starts at st.next_token — a re-dispatched task resumes
        where the dead worker left off. Raises _TaskLost when this
        placement is unreachable; the caller decides recovery. A
        corrupt frame (PageWireError — bit rot or a fault-injected
        flip on the wire) retries the SAME token bounded times (the
        token only advances on a decoded frame), then surfaces as
        _TaskLost so the replay ladder re-pulls from a survivor — the
        PR-16 loud-fail contract: never garbage rows."""
        while True:
            attempt = 0
            while True:
                self._check_deadline(deadline)
                try:
                    # no ?part: the coordinator drains gather edges
                    # only (partition 0 / legacy byte buffers) —
                    # worker-to-worker partition fetches live in
                    # dist/spool.fetch_spool_blobs. ?max streams up
                    # to a bounded window of page frames per request
                    # (pooled keep-alive connection), decoded
                    # incrementally: the token, hasher, and yield
                    # advance one FRAME at a time, so a mid-stream
                    # transport failure resumes at the first
                    # unconsumed page with the replay hash intact.
                    with CONNPOOL.request(
                        f"{st.uri}/v1/task/{st.task_id}/results/"
                        f"{st.next_token}"
                        f"?max={SPOOL.FETCH_WINDOW_BYTES}",
                        timeout=self._deadline_timeout(deadline),
                    ) as r:
                        if r.status == 204:
                            if r.headers.get("X-Done") == "1":
                                return
                            break  # long-poll timeout; re-ask
                        for body in SPOOL.iter_response_frames(r):
                            page = serde.deserialize_page(body)
                            st.hasher.update(body)
                            st.next_token += 1
                            yield page
                        break
                except serde.PageWireError as e:
                    # decode failed BEFORE the token advanced: the
                    # re-request resumes at the first unconsumed page
                    attempt += 1
                    if attempt > self.fetch_retries:
                        raise _TaskLost(
                            f"worker {st.uri} task {st.task_id}: "
                            f"corrupt page frame at token "
                            f"{st.next_token} after "
                            f"{self.fetch_retries} retries: {e}"
                        ) from e
                    self._sleep_backoff(attempt, deadline)
                except (urllib.error.URLError, urllib.error.HTTPError,
                        ConnectionError, OSError) as e:
                    self._raise_if_task_error(e, st.uri, st.task_id)
                    attempt += 1
                    if attempt > self.fetch_retries:
                        raise _TaskLost(
                            f"worker {st.uri} task {st.task_id}: page "
                            f"fetch failed after {self.fetch_retries} "
                            f"retries: {e}"
                        ) from e
                    self._sleep_backoff(attempt, deadline)

    def _prefix_matches(self, uri: str, task_id: str, st: _TaskState,
                        deadline: Optional[float]) -> bool:
        """Verify a re-dispatched task regenerated the already-consumed
        page prefix byte-for-byte before resuming at st.next_token —
        dedupe-by-token is only sound for identical sequences.
        Deterministic generation makes a match the common case; re-
        fetching the prefix is cheap (workers buffer the full page
        list). Raises _TaskLost(task_error=True) if the new task
        failed; lets transport errors through after bounded retries so
        the recovery loop excludes this placement too."""
        h = hashlib.sha256()
        token = 0
        attempt = 0
        while token < st.next_token:
            self._check_deadline(deadline)
            try:
                with CONNPOOL.request(
                    f"{uri}/v1/task/{task_id}/results/{token}"
                    f"?max={SPOOL.FETCH_WINDOW_BYTES}", timeout=60,
                ) as r:
                    if r.status == 204:
                        if r.headers.get("X-Done") == "1":
                            return False  # fewer pages than consumed
                        continue  # long-poll timeout; re-ask
                    for body in SPOOL.iter_response_frames(r):
                        h.update(body)
                        token += 1
                        if token >= st.next_token:
                            # frames past the consumed prefix are NOT
                            # part of the hash; the response close
                            # discards the remainder
                            break
                    attempt = 0
            except (urllib.error.URLError, ConnectionError, OSError) as e:
                self._raise_if_task_error(e, uri, task_id)
                attempt += 1
                if attempt > self.fetch_retries:
                    raise
                self._sleep_backoff(attempt, deadline)
        return h.hexdigest() == st.hasher.hexdigest()

    def _release_task(self, uri: str, task_id: str) -> None:
        """DELETE one worker task's buffers/spools (reference: task
        expiry). Scoped to transport errors ONLY — a programming error
        in the release path must surface, not vanish; dead-worker
        skips are counted, not swallowed silently — on the executor's
        registry counter (exec/counters.py), the one copy every
        surface (EXPLAIN ANALYZE, /metrics, system.metrics,
        DcnRunner.release_skips) reads. THE one release
        site for both the legacy cuts and the stage-DAG scheduler."""
        try:
            with CONNPOOL.request(
                f"{uri}/v1/task/{task_id}", method="DELETE", timeout=5
            ) as r:
                r.read()
        except (urllib.error.URLError, OSError, TimeoutError):
            self.runner.executor.release_skips += 1

    # ------------------------------------------------------- fault model
    def _exclude(self, uri: str) -> None:
        if uri not in self._excluded:
            self._excluded.add(uri)
            self.runner.executor.workers_excluded += 1

    def _alive_for_submit(self) -> List[str]:
        """The heartbeat-gated worker pool for this query: nodes the
        detector marks FAILED are never picked (reference:
        NodeScheduler consulting the failure detector). State is read
        from the BACKGROUND detector's cache — no pings on the query
        path except re-admission probes of excluded nodes (a fresh
        successful, recorded probe lets a worker rebooted on the same
        uri rejoin between queries), and those are rate-limited per
        node so a still-dead node costs its connect timeout at most
        once per heartbeat interval. A node that died since the last
        heartbeat tick is caught by the submit-failure recovery path."""
        pool = []
        now = time.monotonic()
        for u in self.worker_uris:
            if u in self._excluded:
                # excluded nodes are probed REGARDLESS of cached state:
                # a worker killed mid-query is usually FAILED in the
                # cache too, and a reboot on the same uri must be able
                # to rejoin before the background loop's next tick
                last = self._probe_at.get(u)
                if last is not None and \
                        now - last < self.heartbeat.interval_s:
                    continue  # probed recently and still excluded
                self._probe_at[u] = now
                if not self.heartbeat.probe(u):
                    continue
                self._excluded.discard(u)
                self._probe_at.pop(u, None)
            elif not self.heartbeat.is_alive(u):
                continue
            pool.append(u)
        return pool

    def _recover_task(self, st: _TaskState, pool: List[str],
                      retry_attempts: int, deadline: Optional[float],
                      cause: BaseException) -> None:
        """Re-dispatch one lost task to a surviving ALIVE worker: same
        fragment, same split assignment (splitIndex/splitCount), new
        taskId — the survivor re-generates the split share
        deterministically at the scan, and the fetch loop resumes at
        st.next_token so already-consumed pages dedupe by token.
        Raises DcnQueryFailed when retries are exhausted (or pinned
        off) or no survivors remain."""
        from presto_tpu import events as E

        if not getattr(cause, "task_error", False):
            # node loss (unreachable / dead). A DETERMINISTIC task
            # failure on a healthy worker is NOT excluded — the node is
            # fine, the fragment raised; re-dispatch is still tried in
            # case the fault was environmental (e.g. device pressure)
            self._exclude(st.uri)
        while True:
            if st.retries_used >= retry_attempts:
                raise DcnQueryFailed(
                    f"worker {st.uri} task {st.task_id}: {cause} "
                    f"(task retries exhausted: "
                    f"task_retry_attempts={retry_attempts})"
                ) from cause
            # prefer a DIFFERENT worker: the failed placement's node
            # sorts last (it stays in the pool only for task_error)
            survivors = sorted(
                (u for u in pool if u not in self._excluded),
                key=lambda u: u == st.uri)
            if not survivors:
                raise DcnQueryFailed(
                    f"task {st.task_id}: no surviving workers to "
                    f"re-dispatch to (pool {pool}, all excluded)"
                ) from cause
            st.retries_used += 1
            self._sleep_backoff(st.retries_used, deadline)
            self._check_deadline(deadline)
            target = survivors[(st.retries_used - 1) % len(survivors)]
            base_id = st.payload["taskId"].split(".r", 1)[0]
            new_id = f"{base_id}.r{st.retries_used}"
            payload = dict(st.payload, taskId=new_id)
            from_uri = st.uri
            try:
                self._post_task(target, payload)
                prefix_ok = (st.next_token == 0 or self._prefix_matches(
                    target, new_id, st, deadline))
            except (urllib.error.URLError, OSError) as e:
                # the survivor failed too: exclude it and keep going
                # (each failed placement consumes one retry)
                self._exclude(target)
                st.uri, cause = target, e
                continue
            except _TaskLost as e:
                # the re-dispatched task itself failed deterministically
                # during prefix verification
                st.uri, cause = target, e
                continue
            if not prefix_ok:
                raise DcnQueryFailed(
                    f"task {new_id}: the re-dispatched placement "
                    f"regenerated a DIFFERENT page sequence for the "
                    f"already-consumed prefix ({st.next_token} pages) "
                    f"— non-deterministic task output (e.g. the "
                    f"survivor's device-OOM ladder re-chunked page "
                    f"boundaries); failing loudly instead of silently "
                    f"skipping or duplicating rows"
                ) from cause
            st.uri, st.task_id, st.payload = target, new_id, payload
            self.runner.executor.task_retries += 1
            tr = self.runner.executor.trace
            if tr is not None:
                # recovery annotation on the query timeline
                tr.complete("retry", new_id, tr.now(), tr.now(),
                            to=target, attempt=st.retries_used,
                            cause=str(cause)[:120])
                self.runner.executor.trace_spans += 1
            E.dispatch(
                self.listeners, "task_retried", E.TaskRetryEvent(
                    query_id=base_id.split(".", 1)[0],
                    task_id=new_id, from_uri=from_uri, to_uri=target,
                    attempt=st.retries_used, cause=str(cause)[:400],
                ),
                on_error=self.runner.executor.count_listener_error,
            )
            return

    # ----------------------------------------------------- stage DAG
    def _try_stage_dag(self, plan):
        """Fragment the plan into a general stage DAG (ANY shape, not
        just the three special-cased cuts). Returns a StageDag or None
        when the plan is not worth/safe to DAG-distribute."""
        from presto_tpu.dist.fragmenter import fragment_dag

        return fragment_dag(
            self.runner.executor, plan, self.runner.catalogs,
            **self.runner._session_dist_options(),
        )

    def _begin_trace(self, qid: str, sql: Optional[str] = None):
        """(trace, whether this runner owns it): the coordinator's
        trace of the statement where the serving coordinator handed
        one over (``handed_trace``; its owner ends and writes it),
        else one of this runner's own when the session traces."""
        from presto_tpu import obs as OBS

        trace, owned = self.handed_trace, False
        if trace is None:
            trace = OBS.maybe_trace(self.runner.session, query_id=qid,
                                    sql=sql)
            owned = True
        if trace is not None:
            OBS.attach(self.runner.executor, trace)
        return trace, owned

    def _end_trace(self, trace, owned: bool) -> None:
        from presto_tpu import obs as OBS

        if not owned:
            OBS.detach(self.runner.executor, trace)
            return
        if trace is not None:
            OBS.finalize(self.runner.executor, trace,
                         self.runner.session.get("query_trace_dir"))
        self.runner.last_trace = trace

    def _execute_dag(self, dag):
        """Run a fragmented DAG through the general stage scheduler
        (dist/scheduler.py): spooled exchanges, non-leaf replay,
        straggler speculation, per-stage pool recomputation."""
        import uuid as _uuid

        from presto_tpu.dist.scheduler import StageScheduler

        self.last_distribution = "stage-dag"
        qid = _uuid.uuid4().hex[:12]
        # lifecycle tracing: attach BEFORE constructing the scheduler
        # (it snapshots ex.trace); the coordinator's root-fragment
        # execute() records its attempt/operator spans into the same
        # trace, so one timeline covers stages + final drain
        trace, owned = self._begin_trace(qid)
        sched = StageScheduler(self, dag, qid,
                               stage_hook=self._stage_hook)
        self.last_scheduler = sched
        try:
            rows = sched.run()
            self.last_output_names = getattr(sched, "root_names",
                                             None)
            return rows
        finally:
            self._end_trace(trace, owned)

    # ---------------------------------------------------------- execute
    def execute(self, sql: str):
        plan = self.runner.plan(sql)
        ex = self.runner.executor
        retry_attempts = self._retry_attempts()
        # general stage-DAG scheduling (ISSUE 7): "true" forces the
        # DAG scheduler for every distributable plan; "auto" keeps the
        # tuned legacy shapes first and engages the DAG only where
        # they would fall back to a single process (closing ROADMAP
        # item 1's "everything else runs on one worker" gap)
        stage_mode = self.runner.session.get("stage_scheduler")
        if stage_mode == "true":
            dag = self._try_stage_dag(plan)
            if dag is not None:
                self.runner.apply_session()
                return self._execute_dag(dag)
        cut = find_partial_cut(plan)
        partial = coord_plan = partition_cols = split_table = None
        if cut is not None:
            # best shape: PARTIAL/FINAL aggregation split — workers
            # ship tiny accumulator-state pages. PARTITIONED JOIN
            # first (the hash-repartition exchange: both big join
            # sides co-partitioned by key hash, build state 1/N per
            # worker); round-robin split-table fan-out (replicated
            # builds) is the fallback shape
            partition_cols = hash_fanout_plan(
                cut, self.runner.catalogs,
                partition_threshold=self.partition_threshold,
            )
            split_table = largest_table(cut.source,
                                        self.runner.catalogs)
            if partition_cols is not None or (
                split_table is not None
                and fanout_safe(cut, split_table)
            ):
                self.last_distribution = (
                    "hash" if partition_cols is not None
                    else "roundrobin"
                )
                partial = dataclasses.replace(cut, step="partial")
        if partial is None:
            # general shape: UNION CUT — workers execute the topmost
            # row-local subtree (multi-join pipelines, no aggregation
            # required) over their split share; the coordinator unions
            # the pages and runs everything above (sort/topN/window/
            # non-decomposable aggregation). Reference: a leaf-stage
            # fragment under a GATHER exchange.
            split_table = largest_table(plan, self.runner.catalogs)
            ucut = (find_union_cut(plan, split_table)
                    if split_table is not None else None)
            if ucut is None:
                if stage_mode == "auto":
                    # the legacy shapes don't apply — exactly the gap
                    # the general stage-DAG scheduler exists to close.
                    # Auto mode preserves the pre-DAG contract for a
                    # dead pool: such queries used to run locally, so
                    # with no ALIVE workers we still fall back local
                    # instead of failing (forced mode fails loudly,
                    # like any distributable shape with no workers)
                    dag = self._try_stage_dag(plan)
                    if dag is not None and (
                        self._alive_for_submit()
                        if retry_attempts > 0 else self.worker_uris
                    ):
                        self.runner.apply_session()
                        return self._execute_dag(dag)
                # nothing distributable: run locally rather than wrong
                # (no pool computed — local queries never pay dead-node
                # probe timeouts)
                self.last_distribution = "local"
                self.last_pool = []
                res = self.runner.execute(sql, trace=self.handed_trace)
                self.last_output_names = list(res.column_names)
                return res.rows
            partition_cols = hash_fanout_source(
                ucut, self.runner.catalogs,
                partition_threshold=self.partition_threshold,
            )
            self.last_distribution = (
                "union-hash" if partition_cols is not None
                else "union-roundrobin"
            )
            cut, partial = ucut, ucut
        # coordinator-side final stage honors the same session the
        # workers were sent (also anchors ex.query_deadline from
        # query_max_run_time — query start for deadline purposes)
        self.runner.apply_session()
        deadline = ex.query_deadline

        # the plan IS distributable from here on — now workers are
        # mandatory. task_retry_attempts=0 pins the classic model end
        # to end: all configured workers are picked (no heartbeat
        # gate), the first submit/fetch failure fails the QUERY cleanly
        pool = (self._alive_for_submit() if retry_attempts > 0
                else list(self.worker_uris))
        self.last_pool = list(pool)
        if not pool:
            raise DcnQueryFailed(
                f"no ALIVE workers among {self.worker_uris}"
            )
        # launch one task per pooled worker; the task body carries the
        # SERIALIZED fragment (plan shipping — reference:
        # TaskUpdateRequest.fragment), not SQL to replay
        fragment = plan_serde.dumps(partial)
        qid = uuid.uuid4().hex[:12]
        # lifecycle tracing for the legacy cuts: one trace covering
        # dispatch, the token-acked fetches, recovery annotations, and
        # the coordinator-side final stage's attempt/operator spans
        trace, owned = self._begin_trace(qid, sql)
        tasks: List[_TaskState] = []
        key = f"dcn-{qid}"
        check_payloads = ex._plan_check_on()
        # fleet cache probe (ISSUE 19), classic-path edition: gated
        # so the common miss is free (bloom summaries answer
        # "definitely not cached" locally). Round-robin splits only —
        # the hash split mode's worker-side wrap computes other keys.
        sess = self.runner.session
        probe_on = (
            partition_cols is None and split_table is not None
            and self.cache_index.known()
            and bool(sess.get("result_cache_enabled"))
            and bool(sess.get("result_cache_remote_probe"))
        )
        try:
            for w, uri in enumerate(pool):
                payload = {
                    "taskId": f"{qid}.{w}",
                    "fragment": fragment,
                    "splitTable": split_table,
                    "splitIndex": w,
                    "splitCount": len(pool),
                    "session": self.session_props,
                }
                if trace is not None:
                    payload["trace"] = True
                if partition_cols is not None:
                    payload["splitMode"] = "hash"
                    payload["partitionColumns"] = partition_cols
                if check_payloads:
                    # deterministic-split invariant (exec/plan_check.py):
                    # the PR-5 retry path re-generates EXACTLY this
                    # (splitIndex, splitCount) share on a survivor — a
                    # payload without it could not be re-dispatched.
                    # Same auto gate as the executor's plan verifier.
                    from presto_tpu.exec import plan_check as PC

                    PC.check_task_payload(payload)
                st = _TaskState(uri=uri, task_id=payload["taskId"],
                                payload=payload)
                d0 = trace.now() if trace is not None else 0.0
                hit_uri = (self._probe_cached_task(
                    partial, split_table, w, len(pool),
                    payload["taskId"], pool) if probe_on else None)
                if hit_uri is not None:
                    # some fleet member already holds this split
                    # share's pages — no dispatch; the supplier
                    # fetches the parked pre-finished task over the
                    # ordinary pooled plane (and a mid-fetch loss
                    # still recovers: the payload carries the full
                    # fragment for re-dispatch on a survivor)
                    st.uri = hit_uri
                    ex.cache_remote_hits += 1
                    if trace is not None:
                        now = trace.now()
                        trace.complete("cache",
                                       f"remote-hit:{st.task_id}",
                                       now, now, uri=hit_uri)
                        ex.trace_spans += 1
                else:
                    try:
                        self._post_task(uri, payload)
                    except (urllib.error.URLError, OSError) as e:
                        if retry_attempts <= 0:
                            raise DcnQueryFailed(
                                f"worker {uri}: task submit failed: "
                                f"{e}"
                            ) from e
                        # submit retry: re-dispatch this split share
                        # to a different ALIVE worker (it runs two
                        # tasks)
                        self._recover_task(st, pool, retry_attempts,
                                           deadline, e)
                if trace is not None:
                    st.trace_t0 = d0
                    trace.complete("dispatch", st.task_id, d0,
                                   trace.now(), uri=st.uri)
                    ex.trace_spans += 1
                tasks.append(st)

            # coordinator-side plan: shipped subtree -> RemoteSource
            state_types = tuple(ex.output_types(partial))
            remote = P.RemoteSource(types=state_types, key=key,
                                    origin=partial)
            if partial is cut:  # union cut: consume the union as-is
                coord_plan = _replace_node(plan, cut, remote)
            else:  # aggregation cut: FINAL step over the state pages
                final = dataclasses.replace(cut, step="final",
                                            source=remote)
                coord_plan = _replace_node(plan, cut, final)

            def supplier():
                for st in tasks:
                    # a fresh supplier invocation (coordinator boosted
                    # retry re-pulling the remote source) refetches from
                    # token 0 — workers buffer the full page list; within
                    # ONE invocation next_token advances so a re-dispatched
                    # task resumes at the consumed token (dedupe)
                    st.next_token = 0
                    st.hasher = hashlib.sha256()
                    f0 = trace.now() if trace is not None else 0.0
                    while True:
                        try:
                            yield from self._fetch_pages(st, deadline)
                            break
                        except _TaskLost as e:
                            if retry_attempts <= 0:
                                raise DcnQueryFailed(str(e)) from e
                            # worker death mid-query: exclude the node and
                            # re-run ONLY the lost fragment on a survivor,
                            # resuming the fetch at the consumed token
                            self._recover_task(st, pool, retry_attempts,
                                               deadline, e)
                    if trace is not None:
                        trace.complete("fetch", st.task_id, f0,
                                       trace.now(), uri=st.uri,
                                       pages=st.next_token)
                        ex.trace_spans += 1
                        # one status poll per drained task ingests the
                        # worker's queue/run/attempt spans into the
                        # timeline (the stage-DAG path gets these from
                        # its completion polls; the legacy path must
                        # ask once, or workers record for no reader)
                        ex.trace_spans += trace.ingest(
                            self._task_spans(st), trace.root,
                            st.trace_t0, trace.now())

            ex.remote_sources[key] = supplier
            names, rows = ex.execute(coord_plan)
            self.last_output_names = list(names)
            return rows
        finally:
            ex.remote_sources.pop(key, None)
            # release worker-side page buffers (reference: task
            # expiry) — shared with the stage-DAG scheduler's cleanup
            for st in tasks:
                self._release_task(st.uri, st.task_id)
            self._end_trace(trace, owned)
