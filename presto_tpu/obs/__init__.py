"""THE query-lifecycle observability package (ISSUE 9).

Reference: presto-main's stats plane — the QueryInfo/StageInfo/TaskInfo
trees served by /v1/query, OperatorStats feeding them, QueryMonitor
building EventListener payloads, and the airlift TimeStat/Distribution
histograms behind JMX. Ours is one package with three surfaces:

  trace.py    the span recorder: query -> stage -> task -> attempt ->
              operator spans on ONE monotonic clock with ONE wall
              anchor per query, exported as a live QueryInfo tree
              (/v1/query/{id}, system.runtime_tasks) and a Chrome-trace
              (Perfetto-loadable) JSON file.
  histo.py    log-bucketed latency histograms with Prometheus
              exposition — the p50/p95/p99 surface the concurrent-load
              benchmark (ROADMAP item 1) reads from /metrics.
  profile.py  the persisted observed-stats profile store keyed by
              (canonical plan fingerprint, connector snapshot):
              settled capacity bucket + observed cardinalities, the
              input adaptive execution (ROADMAP item 4) replans from.

SPAN_KINDS below is the span analog of exec/counters.QUERY_COUNTERS:
every span kind emitted anywhere in the engine is declared here, and
tools/lint's `spans` rule fails the build when an emission site uses
an undeclared kind (or a declared kind has no emission site) — so the
trace vocabulary cannot drift between the recorder, the QueryInfo
tree, and the tools that read them.

Tracing is strictly off the jit path: spans are recorded at page /
attempt / stage boundaries by driver code only (never inside traced
functions), canonical jit keys carry no trace state, and with tracing
off the only cost is one `is None` check per driver loop
(`trace_spans` counter pins that at zero).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from presto_tpu.obs.trace import QueryTrace  # noqa: F401

# span kind -> help text (rendered nowhere yet; the declaration is the
# contract the lint enforces, exactly like QUERY_COUNTERS' help column)
SPAN_KINDS: Dict[str, str] = {
    "query": "the whole query: wall anchor + every child span",
    "execute": "one local executor run of a plan (the overflow-ladder "
               "driver; the coordinator's root fragment and every "
               "LocalRunner query get one); a statement's own run is "
               "a phase, on the profiler's host plane "
               "execute:<query id>",
    "attempt": "one overflow-ladder attempt (attrs: capacity boost, "
               "launches: device program launches by label; a query "
               "with N-1 boosted retries has N of these)",
    "operator": "per-plan-node wall/rows/pages from the EXPLAIN "
                "ANALYZE accounting, anchored at its attempt's start",
    "stage": "one stage-DAG wave dispatched by dist/scheduler.py",
    "task": "one logical task of a stage (coordinator view; attrs: "
            "uri, retries, pages; worker-side spans nest inside)",
    "dispatch": "one task-submit POST to a worker",
    "queue": "coordinator-side phase: statement submitted -> admitted "
             "(attrs: gate, the one of resource group, footprint "
             "arbiter and execution lock that held it longest, and "
             "each gate's wait in microseconds); worker-side: task "
             "created -> fragment execution started",
    "parse": "phase: admitted -> statement parsed, session applied "
             "and access checked (the runner's front end before "
             "planning)",
    "plan": "phase: analyze + plan + optimize + fragment, up to the "
            "instant the executor takes the plan (plan-time scalar "
            "subqueries' execute spans nest inside; attrs: "
            "constants_folded, the Call nodes the statement's "
            "expressions lost to the planner's constant fold)",
    "encode": "coordinator-side phase: executor done -> rows encoded "
              "as JSON protocol values; the root ends with it",
    "run": "worker-side: fragment execution (attrs: pages, spooled)",
    "fetch": "coordinator-side page drain of one task's results",
    "retry": "one task re-dispatch (attrs: from/to uri, cause) — the "
             "fault-tolerance paths' trace annotation",
    "speculate": "one straggler-speculation copy dispatched (attrs: "
                 "uri); win/loss lands on the task span",
    "replan": "one adaptive re-plan evaluated at a stage boundary "
              "(presto_tpu/adaptive/): attrs carry the flip/seed/"
              "skew-hint counts, or rejected=true with the "
              "verify_dag reason when the mutation rolled back — "
              "the interval is the stats-summation + re-verify wall "
              "the re-plan cost model prices",
    "xfer": "one metered host<->device crossing (exec/xfer.py choke "
            "points): d2h:<label> pulls pages/arrays to host (spill, "
            "exchange serialization, result decode), h2d:<label> "
            "stages host pages onto the device (restream, cache "
            "replay, remote-source ingest); attrs carry bytes, and "
            "the summed span wall equals the query's transfer_wall_s "
            "counter — the copy-time phase ROADMAP item 6 drives "
            "toward zero; a child of the open attempt; on the "
            "profiler's host plane a pull is wait:<label>, a "
            "staging xfer:h2d:<label>",
    "launch": "one call of a device program on the driver thread "
              "(exec/programs.launch, named by the program's label): "
              "the interval is the call's host wall, cut from the "
              "clock readings dispatch_wall_us is summed from; a "
              "child of the attempt, on the profiler's host plane "
              "launch:<label>",
    "wait": "one host read that blocks on the device and crosses no "
            "page (xfer.device_wait: devsync.drain), named by its "
            "site; with the d2h xfer spans its wall sums to "
            "device_wait_us; on the profiler's host plane "
            "wait:<site>",
    "eager": "one stretch of the driver thread in jnp dispatches "
             "outside _jit (xfer.eager: the page.num_rows() pair a "
             "pages() boundary keeps for the query trace on one "
             "device), named by its site: host time in which the "
             "runtime may make the call wait for the device; on the "
             "profiler's host plane eager:<site>",
    "resident_load": "one table loaded into the device-resident "
                     "store (connectors/cached.py), recorded on the "
                     "statement whose scan touched it first; attrs: "
                     "columns, slots, bytes; on the profiler's host "
                     "plane resident_load:<table>",
    "join_build": "one stored join's lookup structure built "
                  "(Executor._stored_build), named by the build "
                  "side's table: source lookup to the build "
                  "program's enqueue, whose launch span lies inside "
                  "it; attrs: table, rows, capacity, structure, bytes, "
                  "riders (the tables of the joins probed inside this "
                  "build's program, join_probes_at_build); "
                  "its wall sums to join_build_wall_us; on the "
                  "profiler's host plane join_build:<table>",
    "cache": "one result-cache point served (presto_tpu/cache/): "
             "hit:<Node> replays stored pages (attrs: pages, key) in "
             "the span's interval — compile+launch skipped; "
             "miss:<Node> marks the lookup, the real execution "
             "follows as ordinary attempt/operator spans",
    "checkpoint": "one durable coordinator-journal publish "
                  "(dist/checkpoint.py): attrs carry the record "
                  "state and serialized bytes — the barrier-write "
                  "cost the checkpoint model prices against the "
                  "stage wall it rides on",
}


def maybe_trace(session, query_id: Optional[str] = None,
                sql: Optional[str] = None,
                anchor_mono: Optional[float] = None,
                anchor_wall: Optional[float] = None
                ) -> Optional[QueryTrace]:
    """A QueryTrace when the session enables tracing, else None (the
    near-zero-cost off switch: every recording site guards on the
    executor's `trace is None`). The coordinator anchors the trace at
    the statement's submission."""
    if not (bool(session.get("query_trace_enabled"))
            or session.get("query_trace_dir")):
        return None
    if query_id is None:
        import uuid

        query_id = f"q-{uuid.uuid4().hex[:12]}"
    return QueryTrace(query_id, sql=sql, anchor_mono=anchor_mono,
                      anchor_wall=anchor_wall)


def attach(executor, trace: QueryTrace) -> None:
    """Hand a trace to an executor for the next query; resets the
    per-query `trace_spans` counter the tracing-off test pins."""
    executor.trace = trace
    executor.trace_parent = None
    executor.trace_spans = 0


def detach(executor, trace: QueryTrace) -> None:
    """Take the trace off the executor and settle the span-count
    counter; the trace's owner ends and writes it (``close``)."""
    executor.trace = None
    executor.trace_parent = None
    executor.trace_spans = trace.span_count


def finalize(executor, trace: QueryTrace,
             trace_dir: Optional[str] = None) -> None:
    """What the owner of a trace attached to its own executor does at
    the end: detach, end the root span, write the file."""
    detach(executor, trace)
    close(trace, trace_dir)


def close(trace: QueryTrace, trace_dir: Optional[str] = None,
          at_mono: Optional[float] = None) -> None:
    """End the root span (at the owner's finish clock where it has
    one) and write the Chrome-trace file when a directory is
    configured (session prop `query_trace_dir` / etc key
    `query-trace.dir`). The file write degrades gracefully (same
    discipline as profile.ProfileStore.record): this runs inside
    callers' finally blocks, so an unwritable trace dir must neither
    fail a successful query nor mask an in-flight error."""
    trace.finish(at_mono)
    if trace_dir:
        try:
            os.makedirs(trace_dir, exist_ok=True)
            trace.write_chrome(
                os.path.join(trace_dir,
                             f"{trace.query_id}.trace.json")
            )
        except OSError:
            pass  # observability must never fail the query
