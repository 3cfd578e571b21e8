"""Typed session properties + per-query session state.

Reference: presto-main SystemSessionProperties.java (typed, defaulted,
per-query overrides settable via SET SESSION / X-Presto-Session headers)
and Session.java (user, catalog, property map). The north-star's
`tpu_offload_enabled` gate lives here: it decides whether query kernels
run as compiled XLA programs on the accelerator path or fall back to
op-by-op eager evaluation (the row-oracle fallback, BASELINE.json).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class PropertyMetadata:
    """Reference: spi/session/PropertyMetadata.java."""

    name: str
    description: str
    type: type  # bool | int | str
    default: Any
    validate: Optional[Callable[[Any], bool]] = None


def _parse_value(prop: PropertyMetadata, value: Any) -> Any:
    if prop.type is str:
        # tri-state and enum properties: accept python bools and any
        # casing ("SET SESSION x = TRUE" arrives as a string either way)
        if isinstance(value, bool):
            return "true" if value else "false"
        value = str(value).strip()
        # normalize case only for enum-domain properties (those with a
        # validator); free-form string values keep their casing
        return value.lower() if prop.validate is not None else value
    if isinstance(value, str) and prop.type is bool:
        low = value.strip().lower()
        if low in ("true", "1", "on"):
            return True
        if low in ("false", "0", "off"):
            return False
        raise ValueError(f"{prop.name}: expected boolean, got {value!r}")
    if isinstance(value, str) and prop.type is int:
        return int(value)
    if not isinstance(value, prop.type):
        try:
            return prop.type(value)
        except Exception:
            raise ValueError(
                f"{prop.name}: expected {prop.type.__name__}, "
                f"got {value!r}"
            )
    return value


SYSTEM_SESSION_PROPERTIES: Dict[str, PropertyMetadata] = {
    p.name: p
    for p in [
        PropertyMetadata(
            "tpu_offload_enabled",
            "compile operator pipelines to XLA and run them on the "
            "accelerator; false falls back to eager op-by-op execution",
            bool, True,
        ),
        PropertyMetadata(
            "join_distribution_type",
            "auto | broadcast | partitioned (reference: "
            "join_distribution_type)",
            str, "auto",
            validate=lambda v: v in ("auto", "broadcast", "partitioned"),
        ),
        PropertyMetadata(
            "broadcast_join_rows",
            "build sides up to this many estimated rows replicate to "
            "every mesh device instead of repartitioning",
            int, 1 << 21,
        ),
        PropertyMetadata(
            "agg_gather_capacity",
            "grouped aggregations up to this capacity gather partial "
            "states to one stream; larger ones repartition by group key",
            int, 1 << 17,
        ),
        PropertyMetadata(
            "page_rows",
            "target rows per page (split granularity)",
            int, 1 << 18,
        ),
        PropertyMetadata(
            "array_agg_max_elements",
            "per-group value-slot bound for array_agg/map_agg/"
            "approx_percentile collect state; a group exceeding it "
            "fails with a clear error (raise and re-run)",
            int, 1024,
        ),
        PropertyMetadata(
            "query_max_memory_bytes",
            "fail queries whose largest page footprint exceeds this many "
            "bytes (0 = unlimited; reference: query.max-memory)",
            int, 0,
        ),
        PropertyMetadata(
            "hash_partition_count",
            "devices used for repartitioned stages (0 = whole mesh)",
            int, 0,
        ),
        PropertyMetadata(
            "pallas_join_enabled",
            "use the Pallas dim probe (ops/pallas_join.py) as the "
            "range finder of eligible joins whose build side holds at "
            "most 2,048 rows (general equi-join + unique-key fast "
            "path); larger builds take the sort join. auto = on a TPU "
            "only; true = also off a TPU, where the kernel runs "
            "interpreted (the test path, not speed); false = never",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "mesh_exchange_mode",
            "lower a repartition exchange to an in-program "
            "lax.all_to_all when its producer spools and consumer "
            "readers are co-resident on one process mesh (ISSUE 18); "
            "auto = co-resident stages only, false = always the "
            "spooled HTTP plane (the authoritative path for "
            "DCN-remote consumers and replay recovery)",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "spill_threshold_bytes",
            "joins/aggregations whose state estimate exceeds this many "
            "bytes run in hash-partition passes (grace-style spill; 0 = "
            "disabled; reference: spill-enabled + revocable memory)",
            int, 0,
        ),
        PropertyMetadata(
            "generated_join_enabled",
            "allow the build-free generated join (closed-form key "
            "inverse + generate-at-index) for eligible joins over "
            "generator-connector tables; off forces the materialized "
            "build paths (hash/sort/Pallas/partitioned)",
            bool, True,
        ),
        PropertyMetadata(
            "agg_optimistic_rows",
            "optimistic group-capacity clamp for blocking aggregations: "
            "state buffers start at min(planner estimate, this) and grow "
            "on the overflow-retry ladder; sorts/scatters in the grouped "
            "path scale with capacity, so a tight start is much faster "
            "when the planner over-estimates (0 = trust the estimate). "
            "The first attempt's hash-partition decision and join-output "
            "compaction buffer are sized from the same number",
            int, 1 << 18,
        ),
        PropertyMetadata(
            "agg_compact_enabled",
            "when an aggregation consumes a join's output, densify the "
            "input stream through a rolling compacted accumulator of "
            "agg_optimistic_rows capacity first (join outputs are "
            "capacity-sparse; blocking-op cost scales with slots, not "
            "valid rows). Rows beyond the accumulator ride the "
            "overflow-retry ladder",
            bool, True,
        ),
        PropertyMetadata(
            "max_join_build_rows",
            "partition a join whenever the build-side row estimate "
            "exceeds this many rows, regardless of the byte threshold "
            "(kernel-size ceiling for runtimes that fault on huge "
            "buffers; 0 = disabled)",
            int, 0,
        ),
        PropertyMetadata(
            "host_spill_bytes",
            "materialized intermediates (multi-pass operator sources) "
            "estimated above this many bytes stage to host RAM instead "
            "of staying HBM-resident (0 = always device-resident; "
            "reference: spiller/FileSingleStreamSpiller). Default 4GB "
            "keeps huge intermediates from pinning device memory",
            int, 1 << 32,
        ),
        PropertyMetadata(
            "disk_spill_bytes",
            "materialized intermediates estimated above this many "
            "bytes stage to DISK files instead of host RAM (0 = "
            "disabled; the third spill tier — SF100 partitioned state "
            "can exceed host RAM per SURVEY §6.4's sizing). Default "
            "64GB engages only when host RAM would be at risk",
            int, 1 << 36,
        ),
        PropertyMetadata(
            "spill_path",
            "directory for disk-spill files (empty = the system temp "
            "dir; reference: spiller-spill-path config)",
            str, "",
        ),
        PropertyMetadata(
            "late_materialization_enabled",
            "join chains defer carried build columns as row-id "
            "indirections and gather values ONCE at the first consumer "
            "that needs them (reference: DictionaryBlock outputs of "
            "LookupJoinOperator); off gathers every carried column at "
            "every join. auto = on when running on TPU (the win is "
            "HBM gather bandwidth, ~25M rows/s per carried column), "
            "off elsewhere (extra per-join programs cost CPU compile "
            "time). Observability: gathers_deferred / "
            "gathers_materialized counters in EXPLAIN ANALYZE",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "fused_partial_agg_enabled",
            "compile scan->filter->project->partial-aggregation chains "
            "to ONE XLA program per split (extends whole-pipeline "
            "fusion through the partial agg step; fused_partial_aggs "
            "counter in EXPLAIN ANALYZE). Grouped aggregations fuse in "
            "the dense/MXU regime only. auto = on when running on TPU "
            "(the win is per-launch overhead), off elsewhere "
            "(bigger fused programs cost real CPU compile time)",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "split_batch_size",
            "fold up to this many splits of a fused scan pipeline "
            "into ONE XLA program launch (a lax.scan over split "
            "indices with the partial-aggregation state as carry for "
            "scan->filter->project->partial-agg chains; a vmapped "
            "[B, page] stacked batch emitted as one page for "
            "page-emitting chains; over a mesh a launch of up to this "
            "many scan rounds, a sequential loop over a chip's "
            "splits emitted as one page). auto = on when running on TPU "
            "with the default max batch (the win is the per-launch "
            "launch overhead, which CPU doesn't pay — the "
            "pallas_join_enabled policy); false = per-split launches. "
            "Observability: program_launches / splits_per_launch "
            "counters in EXPLAIN ANALYZE",
            str, "auto",
            validate=lambda v: v in ("auto", "false", "off")
            or v.isdigit(),
        ),
        PropertyMetadata(
            "compile_cache_dir",
            "directory for jax's persistent compilation cache: programs "
            "compile once per canonical shape per MACHINE, not per "
            "process (empty = in-process caching only; see "
            "presto_tpu/compilecache.py). Observability: "
            "programs_compiled / program_cache_hits / compile_wall_s "
            "counters in EXPLAIN ANALYZE",
            str, "",
        ),
        PropertyMetadata(
            "device_memory_budget",
            "device-memory budget in bytes for the HBM governor "
            "(exec/membudget.py): pipelines whose planned peak device "
            "footprint exceeds their budget share rewrite into "
            "chunked/streaming form (grace-partition join passes, "
            "probe-side position chunking, generation-chunked scans, "
            "partitioned aggregation, PageStore host/disk overflow) "
            "before anything launches. 0 = auto: real HBM minus "
            "headroom on TPU, a generous cap on CPU. Observability: "
            "peak_device_bytes / memory_chunked_pipelines counters in "
            "EXPLAIN ANALYZE",
            int, 0,
        ),
        PropertyMetadata(
            "task_retry_attempts",
            "fault-tolerant execution (reference: Project Tardigrade's "
            "task-level retry): re-dispatch a lost DCN task to a "
            "surviving ALIVE worker up to this many times — the "
            "fragment re-generates its split share deterministically "
            "at the scan and already-consumed pages dedupe by fetch "
            "token, so delivery stays effectively exactly-once. Also "
            "bounds the executor's device-OOM re-entries (each under a "
            "halved device-memory budget). 0 pins the classic "
            "fail-query-cleanly model",
            int, 2,
        ),
        PropertyMetadata(
            "retry_backoff_ms",
            "base delay for the exponential-backoff-with-jitter ladder "
            "between DCN fetch/submit retries (reference: "
            "HttpPageBufferClient backoff)",
            int, 100,
        ),
        PropertyMetadata(
            "query_max_run_time",
            "wall-clock deadline in milliseconds for a query "
            "(0 = unlimited; reference: query.max-run-time). Enforced "
            "in QueryManager, at executor page boundaries, and in the "
            "DCN fetch loop — expiry surfaces as FAILED with a "
            "QueryDeadlineExceeded cause instead of hanging",
            int, 0,
        ),
        PropertyMetadata(
            "plan_check",
            "pre-compile plan verification (exec/plan_check.py): "
            "schema-consistent operator/fragment edges, ladder-"
            "quantized capacities under the device fault line, "
            "canonical jit-cache key material, deterministic split "
            "assignment fields. auto = on under pytest or "
            "PRESTO_TPU_PLAN_CHECK=1, off on the hot serving path; "
            "true/false force. Violations fail the query BEFORE "
            "compile with a pointed PlanCheckError",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "stage_scheduler",
            "general fragment-DAG scheduling for DCN queries "
            "(dist/scheduler.py): cut ANY plan into a stage DAG with "
            "gather/broadcast/hash-repartition exchanges and dispatch "
            "it task-by-task across the worker pool, every inter-stage "
            "exchange spooled through PageStore tiers on the producing "
            "worker so lost non-leaf tasks replay instead of failing "
            "the query. auto = engage when the special-cased shapes "
            "(agg-cut / union-cut / hash-fanout) do not apply; true "
            "forces DAG scheduling first; false disables it. "
            "Observability: stages_scheduled / spooled_exchange_pages "
            "/ nonleaf_replays counters in EXPLAIN ANALYZE",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "speculation_enabled",
            "straggler speculation as a stage-scheduler policy "
            "(reference: Project Tardigrade speculative execution): "
            "race a re-dispatched copy of a stage's slowest running "
            "task on another worker and take whichever placement "
            "finishes first (deterministic fragments make the outputs "
            "byte-identical, so the loser is simply cancelled). "
            "Counters: speculative_tasks_won / speculative_tasks_lost",
            bool, False,
        ),
        PropertyMetadata(
            "spool_exchange_bytes",
            "per-task resident-byte budget for spooled-exchange "
            "partitions on a worker: serialized exchange pages beyond "
            "it spill to disk-tier PageStore files instead of host "
            "RAM (0 = never spill to disk; the spooled shuffle tier "
            "that makes non-leaf task replay and mid-query rejoin "
            "scheduler policies). On the device-exchange tier the "
            "same budget bounds device-RESIDENT spool bytes — a page "
            "past it materializes to host eagerly",
            int, 1 << 30,
        ),
        PropertyMetadata(
            "device_exchange_enabled",
            "partition spooled-exchange pages ON DEVICE "
            "(dist/spool.device_partition_pages: jitted splitmix64 "
            "radix partition + ladder-bucket compaction) and spool "
            "device Pages that materialize to host bytes lazily — "
            "mesh-local exchanges then complete with zero h2d/d2h; "
            "auto = on when running on TPU, off elsewhere (the "
            "partition programs cost real CPU compile time for "
            "copies the CPU backend barely pays)",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "buffer_donation_enabled",
            "thread donate_argnums through the jit wrapper for the "
            "fold/topn merge accumulator programs so chained merges "
            "and the overflow-retry ladder reuse HBM in place "
            "instead of reallocating per step (buffers_donated "
            "counter); auto = on when running on TPU, off elsewhere",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "query_trace_enabled",
            "record a query-lifecycle span trace (presto_tpu/obs/): "
            "query -> stage -> task -> attempt -> operator spans on "
            "one monotonic clock with one wall anchor, served live as "
            "the /v1/query/{id} QueryInfo tree and "
            "system.runtime_tasks. Off = zero recording cost "
            "(trace_spans counter pins 0). The HTTP server enables "
            "this by default for its queries",
            bool, False,
        ),
        PropertyMetadata(
            "query_trace_dir",
            "directory for per-query Chrome-trace (Perfetto-loadable) "
            "JSON exports; setting it also enables tracing (empty = "
            "no files). Each query writes "
            "<query-id>.trace.json on completion",
            str, "",
        ),
        PropertyMetadata(
            "stats_profile_dir",
            "directory for persisted observed-stats profiles "
            "(presto_tpu/obs/profile.py), keyed by (canonical plan "
            "fingerprint, connector snapshot): settled capacity "
            "bucket + observed cardinalities. Repeated queries seed "
            "their starting capacity from the profile and skip the "
            "overflow-retry ladder (capacity_boost_retries -> 0); "
            "empty = disabled",
            str, "",
        ),
        PropertyMetadata(
            "result_cache_enabled",
            "serve repeated work from the two-level result cache "
            "(presto_tpu/cache/): cacheable plan subtrees replay "
            "their pages (skipping compile+launch) and identical "
            "full statements return the finished row set, keyed by "
            "(canonical plan/statement fingerprint, connector "
            "snapshot versions) so a write to any scanned table "
            "structurally invalidates. The store is process-shared "
            "across concurrent queries. Observability: "
            "result_cache_hits / result_cache_misses / "
            "result_cache_evictions / result_cache_invalidations "
            "counters in EXPLAIN ANALYZE",
            bool, False,
        ),
        PropertyMetadata(
            "result_cache_bytes",
            "host-resident byte budget for the result cache: LRU "
            "page entries past it demote to disk-tier PageStore "
            "spill files, and total bytes past 4x the budget evict "
            "outright (result_cache_evictions counts both reclaim "
            "paths). An entry larger than the whole budget is never "
            "admitted",
            int, 1 << 28,
        ),
        PropertyMetadata(
            "result_cache_ttl_ms",
            "age bound for result-cache entries in milliseconds: an "
            "entry older than this reads as a miss and is reclaimed "
            "(0 = no age bound; snapshot-version keying already "
            "handles write staleness — TTL exists for wall-clock "
            "freshness policies on slowly-polled dashboards)",
            int, 0,
        ),
        PropertyMetadata(
            "result_cache_persist_dir",
            "directory for the persistent warm-start tier of the "
            "result cache (cache/persist.py): completed fragment "
            "entries publish a wire-serde payload file plus a row in "
            "an atomically-renamed versioned manifest (entry key, "
            "snapshot tokens, stream watermark, serde fingerprint), "
            "and the first enabled session after a process boot "
            "warm-loads every entry whose snapshot tokens still "
            "match the live connectors (cache_warm_loads counter); "
            "stale/corrupt/mismatched entries drop loudly "
            "(cache_manifest_drops). Empty = memory-only (the PR-10 "
            "behavior)",
            str, "",
        ),
        PropertyMetadata(
            "result_cache_remote_probe",
            "let the DCN coordinator probe fleet members' fragment "
            "caches before dispatching a leaf task "
            "(dist/cacheprobe.py): any worker's cached fragment "
            "short-circuits the task (cache_remote_hits) and its "
            "pages replay over the existing pooled spool-fetch "
            "plane; probes are gated by bloom-style summaries "
            "refreshed with heartbeats, so the common miss costs "
            "nothing on the wire",
            bool, True,
        ),
        PropertyMetadata(
            "result_cache_subsumption",
            "serve a fragment whose single-column range/IN filter is "
            "CONTAINED by an already-cached sibling (same scan + "
            "projection chain) by re-filtering the cached pages "
            "(cache/rules.py descriptor containment): WHERE d < 5 "
            "replays the cached WHERE d < 10 pages through a "
            "residual filter instead of rescanning "
            "(cache_subsumed_hits); anything beyond single-column "
            "range/IN stays exact-match",
            bool, False,
        ),
        PropertyMetadata(
            "checkpoint_enabled",
            "journal coordinator query state durably at natural "
            "barriers (dist/checkpoint.py): admission, every "
            "spooled-stage boundary (placements + spool tokens + "
            "page digests), final-stage supplier registration, and "
            "client-protocol token advances — so a restarted "
            "coordinator re-attaches RUNNING queries whose producer "
            "spools still answer instead of losing them "
            "(coordinator_reattaches / checkpoints_written). "
            "Effective only when a journal directory is configured "
            "(checkpoint_dir session prop or the server's "
            "checkpoint.dir etc key); false disables journaling "
            "even when a directory is set",
            bool, True,
        ),
        PropertyMetadata(
            "checkpoint_dir",
            "directory for the durable coordinator journal "
            "(dist/checkpoint.py): one generation-numbered manifest "
            "(shared cache/persist.py ManifestStore discipline — "
            "atomic tmp+rename publishes, O(1) appends, compaction "
            "past a record threshold) holding one record per "
            "in-flight query; on restart the server replays the "
            "journal and re-attaches or loudly fails each pending "
            "query (never a hang, never duplicate or missing rows). "
            "Empty = checkpointing off (the pre-restart behavior)",
            str, "",
        ),
        PropertyMetadata(
            "ivm_enabled",
            "maintain registered materialized views incrementally "
            "(streaming/ivm.py): a refresh folds ONLY the pages "
            "appended since the view's offset watermark through the "
            "partial-aggregation kernels into persisted settled "
            "state — O(new rows) instead of a full recompute. false "
            "forces full recomputes (counted loudly on "
            "ivm_full_recomputes; results identical either way). "
            "Non-IVM-safe view shapes always recompute in full",
            bool, True,
        ),
        PropertyMetadata(
            "stream_tail_enabled",
            "turn /v1/statement into a TAILING cursor for queries "
            "over append-only stream tables (connectors/stream.py): "
            "nextUri never terminates — each poll long-polls the log "
            "and emits only rows derived from new offsets, riding "
            "the incremental-view-maintenance path when the "
            "statement matches a registered view's shape. Set per "
            "request via the X-Presto-Session header (the protocol's "
            "per-request flag) or session-wide via SET SESSION; "
            "DELETE the statement to stop tailing",
            bool, False,
        ),
        PropertyMetadata(
            "stream_poll_ms",
            "long-poll interval in milliseconds for tailing "
            "/v1/statement cursors: a poll with no new offsets "
            "returns an empty page (with a fresh nextUri) after this "
            "long; an append wakes waiting pollers immediately",
            int, 1000,
        ),
        PropertyMetadata(
            "adaptive_execution",
            "runtime re-planning at spooled-exchange stage "
            "boundaries (presto_tpu/adaptive/): when a stage's "
            "spools finish, the not-yet-dispatched DAG suffix "
            "re-optimizes from EXACT observed row/byte counts — "
            "broadcast-vs-partitioned flips, join build re-orders, "
            "capacity re-buckets onto the shapes ladder, skew "
            "pre-engagement — re-verified by plan_check.verify_dag "
            "before dispatch (a failed re-verify falls back to the "
            "static plan, counted on adaptive_replan_rejected). "
            "auto = on under the stage scheduler; false disables. "
            "Counters: adaptive_replans / adaptive_dist_flips / "
            "adaptive_capacity_seeds / adaptive_replan_rejected / "
            "skew_preempted in EXPLAIN ANALYZE",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "adaptive_max_replans",
            "per-query bound on adaptive re-plans applied at stage "
            "boundaries (each re-plan re-verifies the mutated DAG; "
            "the bound keeps re-verification wall off long DAGs "
            "once the plan has settled). 0 observes stats but never "
            "mutates",
            int, 4,
        ),
        PropertyMetadata(
            "join_skew_rebalance",
            "on boosted retries, rebalance hot grace-join partitions "
            "by chunking build rows by position (buffers stay at the "
            "unboosted size; one probe pass per chunk) instead of "
            "growing every buffer — a genuinely hot key cannot be "
            "split by hash (SURVEY §6.7 per-partition rebalancing)",
            bool, True,
        ),
        PropertyMetadata(
            "cross_query_batching",
            "gang compatible fused-pipeline launches from CONCURRENT "
            "queries into one shared vmapped device step with "
            "in-program per-query demux (server/launch_batcher.py) — "
            "the PR-3 split-batching amortization applied across "
            "queries, the batching-inference-server shape. auto = on "
            "under the concurrent server path only (raw Executors "
            "and the serial path never batch); false forces solo "
            "launches. Counters: cross_query_batches / "
            "cross_query_batched_queries / batch_gather_wait_ms / "
            "queries_per_launch in EXPLAIN ANALYZE",
            str, "auto",
            validate=lambda v: v in ("auto", "true", "false"),
        ),
        PropertyMetadata(
            "cross_query_batch_wait_ms",
            "bounded gather window in milliseconds for cross-query "
            "launch batching: the first compatible launch (the group "
            "leader) waits at most this long for peers before "
            "dispatching (extended while a same-key step is already "
            "executing — continuous batching), so a lone query never "
            "stalls past the window; the window is only ever paid "
            "when >= 2 queries are running server-wide; 0 batches "
            "only launches already pending at submit time",
            int, 25,
        ),
    ]
}


class Session:
    """Reference: Session.java — user + catalog + property overrides."""

    def __init__(
        self,
        user: str = "presto",
        catalog: Optional[str] = None,
        schema: str = "default",
        properties: Optional[Dict[str, Any]] = None,
    ):
        self.user = user
        self.catalog = catalog
        self.schema = schema
        self._values: Dict[str, Any] = {}
        for k, v in (properties or {}).items():
            self.set(k, v)

    def set(self, name: str, value: Any) -> None:
        prop = SYSTEM_SESSION_PROPERTIES.get(name)
        if prop is None:
            raise KeyError(f"unknown session property: {name}")
        parsed = _parse_value(prop, value)
        if prop.validate and not prop.validate(parsed):
            raise ValueError(
                f"invalid value for {name}: {value!r}"
            )
        self._values[name] = parsed

    def get(self, name: str) -> Any:
        prop = SYSTEM_SESSION_PROPERTIES.get(name)
        if prop is None:
            raise KeyError(f"unknown session property: {name}")
        return self._values.get(name, prop.default)

    def unset(self, name: str) -> None:
        """Remove an override so the default shows again (reference:
        RESET SESSION)."""
        self._values.pop(name, None)

    def is_set(self, name: str) -> bool:
        """True when the property was explicitly set (SET SESSION /
        header / set()) rather than defaulting — consumers that must
        distinguish an override from the default (e.g. page_rows vs a
        constructor argument) check this, never _values directly."""
        return name in self._values

    def rows(self) -> List[tuple]:
        """SHOW SESSION rows: (name, value, default, type, description)."""
        out = []
        for name, p in sorted(SYSTEM_SESSION_PROPERTIES.items()):
            out.append((
                name,
                str(self._values.get(name, p.default)).lower()
                if p.type is bool else str(self._values.get(name, p.default)),
                str(p.default).lower() if p.type is bool else str(p.default),
                p.type.__name__,
                p.description,
            ))
        return out
