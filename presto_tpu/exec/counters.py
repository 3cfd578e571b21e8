"""THE execution-counter registry.

Reference: presto-main OperatorStats/QueryStats — every runtime counter
the engine maintains is declared once and every surfacing layer
renders the same declared set (JMX beans enumerate the declared stats;
nothing is hand-listed per endpoint):

  - QUERY_COUNTERS declares every integer counter the Executor (and
    the DCN coordinator, via mirrored attributes) maintains;
  - Executor.execute_with_stats builds its EXPLAIN ANALYZE counter
    dict FROM the registry (plus the few computed entries listed in
    COMPUTED_COUNTERS);
  - the HTTP server's /metrics exposition and system.metrics table
    iterate the registry;
  - tools/lint's `counters` rule fails the build when a `self.x += 1`
    counter in exec/ or dist/ is missing from the registry.

Adding a counter = initialize it to 0 in Executor.__init__, increment
it, and add one row here; every surface picks it up.
"""

from __future__ import annotations

from typing import Dict

# attr name on Executor -> (prometheus kind, help text).
# "counter" = monotonically increasing over the executor's lifetime or
# per query; "gauge" = per-attempt/per-query level.
QUERY_COUNTERS: Dict[str, tuple] = {
    "gathers_deferred": (
        "gauge", "per-page column gathers skipped at join-output time "
        "(late materialization; per-attempt)"),
    "gathers_materialized": (
        "gauge", "per-page column value gathers actually performed "
        "(late-materialization lift + chain-boundary finish)"),
    "fused_partial_aggs": (
        "gauge", "scan→filter→project→partial-agg chains compiled to "
        "one XLA program per split this attempt"),
    "program_launches": (
        "gauge", "fused-scan program launches this attempt "
        "(split-batched execution)"),
    "device_launches": (
        "gauge", "calls of a program made by Executor._jit this "
        "attempt, counted at the one launch point "
        "(exec/programs.launch) on the calling executor; "
        "program_launches is the fused-scan part of it"),
    "exchange_launches": (
        "gauge", "the part of device_launches whose program moves "
        "rows between chips this attempt (family exchange in "
        "exec/programs.PROGRAM_LABELS: all_to_all repartition, "
        "all_gather, residue split); 0 on one device"),
    "mesh_fused_rounds": (
        "gauge", "scan rounds of this attempt that ran their whole "
        "SHARDED chain (generator, generated joins, filter, project) "
        "as one program over the mesh (d_fused: "
        "dist/executor.DistExecutor._fused_rounds); 0 where the chain "
        "fell back to one program a plan node, and on one device"),
    "mesh_batched_rounds": (
        "gauge", "the ones among mesh_fused_rounds that ran inside a "
        "launch of more than one round (d_fused_batch: a sequential "
        "loop over a chip's splits, sized by split_batch_size's "
        "rule); 0 where a round is a launch"),
    "row_counts_launched": (
        "gauge", "(plan node, page) row counts this attempt kept for "
        "the query trace or EXPLAIN ANALYZE that rode in the launch "
        "that made the page (Page.rows: over a mesh every program "
        "returns its page's count, a chip's own sum a chip); 0 with "
        "tracing off"),
    "row_counts_eager": (
        "gauge", "the ones that Executor.pages computed with "
        "page.num_rows() instead, two eager programs dispatched from "
        "the driver thread between launches: every count on one "
        "device, 0 over a mesh for a statement whose pages all come "
        "out of programs"),
    "plan_constants_folded": (
        "counter", "Call nodes the planner's constant fold replaced, "
        "over every planning pass so far (expr/fold.py: an "
        "allow-listed call on non-NULL constants of exact types "
        "becomes the Constant of its value; 3 a pass over TPC-H Q6, 0 "
        "over Q3). The runner adds a pass's as it ends: one a "
        "statement on the serial path, two on the concurrent path "
        "(admission's estimate_memory plans, then the execution)"),
    "resident_table_bytes": (
        "gauge", "device bytes the catalogs' stored tables hold now "
        "(connectors/cached.py: every column and the validity of each "
        "resident table, pad included); comes off the governor's "
        "budget"),
    "resident_loads": (
        "counter", "tables loaded into the device-resident store (a "
        "table's first touch, or its first after a write moved the "
        "snapshot; catalog lifetime)"),
    "resident_load_wall_us": (
        "counter", "microseconds those loads took, start to the last "
        "byte on the device (catalog lifetime)"),
    "resident_splits_scanned": (
        "gauge", "real splits whose columns this attempt's fused-scan "
        "launches read from a stored table (the part of "
        "splits_scanned that generated nothing)"),
    "resident_bytes_scanned": (
        "gauge", "bytes of stored columns and validity in those "
        "splits' slices (padded rows x the touched buffers' widths), "
        "counted at the launch from shapes: no device read"),
    "join_builds": (
        "gauge", "lookup structures this attempt built over stored "
        "tables (Executor._stored_build: one a join whose build side "
        "is a stored table, once a statement; program stored_build)"),
    "join_probes_at_build": (
        "gauge", "the joins among them that were probed inside "
        "another join's build program, once a BUILD row and not once "
        "a probe slot (Executor._ride_stored_joins: an inner join "
        "whose key an earlier stored join's build carries; TPC-H Q3 "
        "1, Q5 3); such a join has no step in the fused scan"),
    "join_build_rows": (
        "gauge", "stored slots those builds read (the build tables' "
        "sizes: from shapes, no device read)"),
    "join_build_bytes": (
        "gauge", "device bytes those structures hold (direct-address "
        "table, key floor, the build side's page; from shapes)"),
    "join_build_wall_us": (
        "gauge", "host microseconds of those builds, start to the "
        "build program's enqueue (the join_build spans' sum; the "
        "device's part is the trace's jit_stored_build)"),
    "dispatch_wall_us": (
        "gauge", "host microseconds inside those calls this attempt: "
        "trace-cache lookup, argument handling, enqueue (and a "
        "program's first call's tracing, lowering and compile or "
        "cache load)"),
    "device_wait_us": (
        "gauge", "host microseconds blocked on the device this "
        "attempt: every exec/xfer.py pull (to_host, np_host; the "
        "overflow flags' one pull and the row counts' among them) "
        "and devsync.drain"),
    "splits_scanned": (
        "gauge", "real (unpadded) splits covered by this attempt's "
        "fused-scan launches — splits_per_launch is the ratio"),
    "split_batch_fallbacks": (
        "counter", "streams that fell back to the per-split loop "
        "because the chain did not trace under vmap/scan"),
    "generated_joins_used": (
        "counter", "build-free generated joins taken (lifetime; "
        "EXPLAIN ANALYZE reports the per-query delta)"),
    "pallas_joins_used": (
        "counter", "Pallas join kernel engagements (lifetime; EXPLAIN "
        "ANALYZE reports the per-query delta)"),
    "ici_exchanges": (
        "counter", "repartition exchanges lowered to an in-program "
        "lax.all_to_all over the co-resident mesh instead of the "
        "spool/HTTP plane (dist/scheduler.py mesh-exchange plane; "
        "coordinator lifetime)"),
    "ici_bytes": (
        "counter", "bytes routed through mesh all_to_all exchange "
        "programs (send-buffer footprint of the settled attempt — "
        "interconnect traffic, never a host crossing; coordinator "
        "lifetime)"),
    "mesh_exchange_fallbacks": (
        "counter", "mesh-lowered exchanges that fell back LOUDLY to "
        "the authoritative spool plane (trace failure or unsettled "
        "overflow ladder) — counted, never a silent wrong answer"),
    "programs_compiled": (
        "gauge", "real XLA backend compiles attributed to this query "
        "(a persistent-cache hit counts as program_cache_hits)"),
    "program_cache_hits": (
        "gauge", "persistent compile-cache hits attributed to this "
        "query"),
    "spill_partitions_used": (
        "gauge", "grace-partition passes taken by joins/aggregations "
        "this query (spill_threshold_bytes / governed sizing)"),
    "host_spill_pages": (
        "gauge", "intermediate pages staged to host RAM this query "
        "(PageStore host tier)"),
    "disk_spill_pages": (
        "gauge", "intermediate pages written to disk spill files this "
        "query (PageStore disk tier)"),
    "skew_chunks_used": (
        "gauge", "hot grace-join partitions rebalanced by position "
        "chunking on boosted retries"),
    "memory_chunked_pipelines": (
        "gauge", "pipelines the HBM governor rewrote into "
        "chunked/streaming form this attempt (exec/membudget.py)"),
    "device_oom_retries": (
        "gauge", "device-OOM re-entries this query, each under a "
        "halved device-memory budget"),
    "task_retries": (
        "counter", "DCN fragments re-dispatched to a surviving worker "
        "(coordinator lifetime)"),
    "workers_excluded": (
        "counter", "DCN nodes dropped from the dispatch pool after a "
        "mid-query failure (coordinator lifetime)"),
    "release_skips": (
        "counter", "worker page-buffer DELETE releases skipped because "
        "the worker was unreachable (dead-worker cleanup, counted not "
        "swallowed; mirrored from the DCN coordinator)"),
    "stages_scheduled": (
        "counter", "stage-DAG fragments dispatched as worker task "
        "waves by the general scheduler (dist/scheduler.py; "
        "coordinator lifetime)"),
    "spooled_exchange_pages": (
        "counter", "pages published into worker-side spooled-exchange "
        "partitions (PageStore host/disk tiers on the producing "
        "worker; coordinator lifetime)"),
    "nonleaf_replays": (
        "counter", "lost NON-LEAF stage-DAG tasks re-dispatched to "
        "replay from spooled upstream pages instead of failing the "
        "query (coordinator lifetime)"),
    "speculative_tasks_won": (
        "counter", "straggler speculation races where the "
        "re-dispatched copy finished first and became the task's "
        "placement"),
    "speculative_tasks_lost": (
        "counter", "straggler speculation races the original "
        "placement won (the speculated copy was cancelled)"),
    "capacity_boost_retries": (
        "gauge", "overflow-ladder boosted re-entries this query "
        "(0 on a profile-seeded repeat run — the observed-stats "
        "profile contract, obs/profile.py)"),
    "profile_store_hits": (
        "gauge", "runs whose starting capacity bucket was seeded "
        "from a persisted observed-stats profile (obs/profile.py; "
        "per query)"),
    "result_cache_hits": (
        "counter", "result-cache hits: fragment page replays + full-"
        "statement row replays (presto_tpu/cache/; executor lifetime "
        "— /metrics and system.metrics overlay the process-shared "
        "store's totals)"),
    "result_cache_misses": (
        "counter", "result-cache lookups that executed for real (the "
        "entry is published when the attempt completes overflow-free)"),
    "result_cache_evictions": (
        "counter", "result-cache entries dropped by the byte-budget "
        "LRU or TTL aging (result_cache_bytes / result_cache_ttl_ms)"),
    "result_cache_invalidations": (
        "counter", "result-cache entries reclaimed by the write-path "
        "invalidation hook after DML/CTAS to their scanned tables "
        "(staleness itself is structural: snapshot_version rides in "
        "every key)"),
    "cache_warm_loads": (
        "counter", "persisted result-cache entries re-admitted at the "
        "warm-start pass (cache/persist.py manifest load): snapshot "
        "tokens re-validated against live connectors, pages decoded "
        "from the wire-serde payload files"),
    "cache_manifest_drops": (
        "counter", "persisted result-cache entries dropped LOUDLY at "
        "warm load: snapshot token moved, payload file missing or "
        "corrupt, manifest truncated, or wire-serde fingerprint "
        "mismatch — never served, never a crash"),
    "checkpoints_written": (
        "counter", "durable coordinator checkpoint records published "
        "to the query journal (dist/checkpoint.py): admission, stage "
        "barriers, root registration, drain progress, client-token "
        "advances (coordinator lifetime)"),
    "coordinator_reattaches": (
        "counter", "journaled queries a RESTARTED coordinator "
        "recovered — final-stage suppliers re-registered from "
        "persisted placements (spool resume) or the statement re-run "
        "from the journal (coordinator lifetime)"),
    "reattach_redispatches": (
        "counter", "dead final-stage placements re-dispatched from "
        "persisted payloads during coordinator re-attach (the lost "
        "suffix, through the normal replay ladder; coordinator "
        "lifetime)"),
    "checkpoint_drops": (
        "counter", "checkpoint records dropped LOUDLY: journal "
        "generations unreadable at boot (version/fingerprint skew, "
        "torn appends, partial compaction) or barrier writes that "
        "failed to serialize — recovery degrades to the re-run rung, "
        "never a crash, never stale state served"),
    "cache_remote_hits": (
        "counter", "leaf tasks short-circuited by a FLEET member's "
        "fragment cache: the coordinator's pre-dispatch probe "
        "(dist/cacheprobe.py) found the fragment's pages on a worker "
        "and replayed them over the pooled spool-fetch plane instead "
        "of executing the task"),
    "cache_subsumed_hits": (
        "counter", "fragments served by CONTAINMENT rewrite "
        "(cache/rules.py): a cached sibling with a wider single-"
        "column range/IN filter replayed through this fragment's own "
        "predicate as a residual re-filter"),
    "h2d_bytes": (
        "gauge", "bytes staged host->device through the exec/xfer.py "
        "choke points this query (0 on a cache replay served from "
        "host pages — the ISSUE 12 zero-copy contract)"),
    "d2h_bytes": (
        "gauge", "bytes pulled device->host through the exec/xfer.py "
        "choke points this query (spill, exchange serialization, "
        "result decode)"),
    "h2d_transfers": (
        "gauge", "host->device crossings this query (exec/xfer.py; "
        "transfer_wall_s carries their summed wall as a computed "
        "entry)"),
    "d2h_transfers": (
        "gauge", "device->host crossings this query (exec/xfer.py)"),
    "buffers_donated": (
        "gauge", "donated-program invocations this attempt "
        "(fold/topn merge accumulators reusing their input's HBM in "
        "place via donate_argnums; buffer_donation_enabled)"),
    "exchange_wire_bytes": (
        "counter", "exchange-page bytes actually shipped on the wire "
        "by dist/serde.serialize_page (post-codec blob size; "
        "executor lifetime — exchange_raw_bytes / exchange_wire_bytes "
        "is the wire compression ratio)"),
    "exchange_raw_bytes": (
        "counter", "pre-codec array bytes behind the serialized "
        "exchange pages (what a raw wire would have shipped; "
        "executor lifetime)"),
    "exchange_fetch_reused_conns": (
        "counter", "shuffle-plane HTTP requests served on a reused "
        "keep-alive connection from dist/connpool.py instead of a "
        "fresh TCP connect (executor lifetime)"),
    "mesh_local_exchanges": (
        "counter", "exchanges that never left the device/process: "
        "spooled edges served Pages directly between same-process "
        "placements (dist/spool.local_source_pages — no HTTP, no "
        "serde) and DistExecutor collective exchanges compiled onto "
        "the mesh (all_to_all/all_gather; executor lifetime)"),
    "delta_pages_folded": (
        "counter", "delta partial-state pages folded into persisted "
        "materialized-view state by incremental refreshes "
        "(streaming/ivm.py — the O(new rows) refresh input; executor "
        "lifetime)"),
    "ivm_refreshes": (
        "counter", "incremental materialized-view refreshes completed "
        "(delta fold through the partial-agg kernels + finalize; "
        "streaming/ivm.py)"),
    "ivm_full_recomputes": (
        "counter", "view refreshes that fell back to a FULL recompute "
        "(non-IVM-safe plan shape or ivm_enabled=false) — the loud, "
        "counted degradation path, never a silent wrong answer"),
    "cursor_polls": (
        "counter", "tailing /v1/statement cursor polls served "
        "(stream_tail_enabled; each poll long-polls the append log "
        "and emits only delta-derived rows)"),
    "stream_appends_seen": (
        "counter", "append batches observed on append-only stream "
        "connectors: the runner's INSERT advance path plus tail "
        "polls that saw the log offset move"),
    "adaptive_replans": (
        "counter", "stage-boundary re-plans applied by the adaptive "
        "executor (presto_tpu/adaptive/): the not-yet-dispatched "
        "suffix of a stage DAG was re-optimized from exact spool "
        "stats and re-verified before dispatch (coordinator "
        "lifetime)"),
    "adaptive_dist_flips": (
        "counter", "join distributions flipped at runtime by the "
        "adaptive re-planner (partitioned -> broadcast reads of a "
        "small observed build, repartition producers degraded to "
        "passthrough) — the AddExchanges decision re-made on "
        "measured bytes"),
    "adaptive_capacity_seeds": (
        "counter", "downstream fragment capacities re-bucketed onto "
        "the shapes.py ladder from observed exchange cardinality "
        "(aggregation capacities, RemoteSource est_rows stamps) so "
        "first runs start at the settled bucket instead of climbing "
        "the boost ladder"),
    "adaptive_replan_rejected": (
        "counter", "adaptive re-plans DISCARDED because the mutated "
        "DAG failed plan_check.verify_dag (or the per-query "
        "adaptive_max_replans bound was hit) — the static plan runs "
        "instead, counted loudly, never a silent wrong answer"),
    "skew_preempted": (
        "counter", "grace-join passes that started in the skew-"
        "rebalanced position-chunking mode on their FIRST attempt "
        "because the adaptive re-planner saw a hot partition in the "
        "upstream spool histogram (vs discovering it via an overflow "
        "retry; worker counts mirror onto the coordinator)"),
    "trace_spans": (
        "gauge", "spans recorded into this query's lifecycle trace "
        "(obs/trace.py; pinned 0 when tracing is off)"),
    "listener_errors": (
        "counter", "EventListener exceptions swallowed by the "
        "events.dispatch choke point — counted here instead of lost "
        "silently (executor lifetime)"),
    "cross_query_batches": (
        "counter", "shared cross-query device steps dispatched by "
        "this executor as a gather-group LEADER "
        "(server/launch_batcher.py; executor lifetime — the leader's "
        "one launch covers every ganged query)"),
    "cross_query_batched_queries": (
        "counter", "launches this executor served FROM a shared "
        "cross-query batch instead of a solo program (leader and "
        "follower slots both count; executor lifetime)"),
    "batch_gather_wait_ms": (
        "counter", "milliseconds this executor's launches spent in "
        "the cross-query gather window (bounded by "
        "cross_query_batch_wait_ms per launch; executor lifetime)"),
    "queries_per_launch": (
        "gauge", "widest cross-query batch this executor rode (slots "
        "per shared launch; 0 = every launch ran solo)"),
}

# stats-dict entries that are COMPUTED in execute_with_stats rather
# than read off an executor attribute (the lint's counters rule knows
# not to look for `self.<name> +=` sites for these).
COMPUTED_COUNTERS = (
    "splits_per_launch",     # splits_scanned / program_launches
    "compile_wall_s",        # float wall, not an int counter
    "transfer_wall_s",       # float wall of metered crossings (xfer)
    "peak_device_bytes",     # high-water gauge (max, not +=)
    "deadline_ms_remaining",  # derived from query_deadline
)


def snapshot(ex) -> Dict[str, int]:
    """Registry-driven counter snapshot of one executor — the shared
    source for /metrics and system.metrics (missing attributes read 0
    so a bare Executor and a DCN coordinator render the same rows)."""
    return {name: int(getattr(ex, name, 0)) for name in QUERY_COUNTERS}
