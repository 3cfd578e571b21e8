"""Device-memory governor: plan-time HBM budget accounting.

Reference: presto-main's memory/MemoryPool + LocalMemoryContext
hierarchy and the spill decisions AddLocalExchanges/spiller make under
memory pressure. The reference REACTS to allocation (revocable memory,
spill-on-pressure); the TPU translation can do better: every buffer
capacity the executor allocates quantizes onto the exec/shapes.py
ladder BEFORE compile, so a pipeline's peak live device bytes is a
static function of the plan — computable, checkable, and fixable
(by chunked rewrites) before a single program launches.

The model:

    bytes(buffer)   = bucket(rows) * row_bytes        (the allocation)
    bytes(pipeline) = sum of concurrently-live buffer footprints
    chunks          = ceil(peak / budget-share)

Governed decisions, each a *chunked rewrite* of the pipeline rather
than a failure:

  - join builds:   grace-partition passes sized to fit (parts_for)
  - join outputs:  probe pages position-chunked so output capacity
                   stays under its share
  - scans:         generation chunk (page) size shrunk to fit — a
                   Q1/Q6-shaped pipeline streams an arbitrarily large
                   table through fixed-size resident buffers
  - aggregations:  hash-partition passes when state exceeds its share
  - intermediates: PageStore host/disk tiers engage when a
                   materialization exceeds its share

The budget itself: session property `device_memory_budget` (bytes;
0 = auto). Auto resolves to the device's real HBM minus headroom on
TPU and a generous cap on CPU (tier-1 tests see no behavior change
unless they force a tiny budget), less what the catalogs hold resident
on the device (a stored table, connectors/cached.py: the store checks
at load that it fits, the executor plans every buffer with what is
left).

Shares: one pipeline holds several live buffers at once (build +
probe page + output page + downstream materialization), so no single
buffer may claim the whole budget. The divisors are deliberately
coarse powers of two — the ladder absorbs the slack.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

from presto_tpu.exec import shapes as SH

# Fraction of HBM held back from the governor: runtime scratch,
# compiled-program buffers, XLA temp allocations. budget = HBM * 7/8.
HEADROOM_DIV = 8

# CPU "budget": effectively unbounded for tier-1 scale, small enough
# that a genuinely absurd plan still trips the audit. 16 GiB.
CPU_BUDGET = 1 << 34

# Budget shares (divisors of the resolved budget):
#   join build materialization / aggregation state / sort-window merge
BUILD_SHARE_DIV = 4
#   a single join-output or landing page
PAGE_SHARE_DIV = 8
#   one scan generation buffer (many are live across a fused batch)
SCAN_SHARE_DIV = 8
#   a restreamable intermediate staying device-resident (PageStore)
STORE_SHARE_DIV = 2


def device_hbm_bytes() -> Optional[int]:
    """Physical device memory of the default backend's first device,
    None when the runtime does not expose it (the CPU backend)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if stats:
        limit = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit"
        )
        if limit:
            return int(limit)
    return None


def resolve_budget(setting: int, backend: Optional[str] = None,
                   resident: int = 0) -> int:
    """device_memory_budget resolution: an explicit positive setting
    wins; 0 (auto) = real HBM minus headroom on TPU, the generous
    CPU_BUDGET elsewhere. A TPU whose runtime reports no memory limit
    is an error, not a guessed size.

    ``resident`` is what the catalogs hold on the device for good
    (stored tables, connectors/cached.py): it comes off the auto
    budget, and an explicit setting is held to what is left, so the
    governor never plans with memory a resident table has. With
    nothing resident an explicit setting is taken as it is."""
    explicit = int(setting) if setting and int(setting) > 0 else 0
    if explicit and not resident:
        return explicit
    import jax

    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        auto = CPU_BUDGET
    else:
        hbm = device_hbm_bytes()
        if not hbm:
            raise RuntimeError(
                "device_memory_budget=auto needs the device's HBM size, "
                "and memory_stats() of "
                f"{jax.local_devices()[0].device_kind!r} reports no "
                "bytes_limit; set device_memory_budget explicitly"
            )
        auto = hbm - hbm // HEADROOM_DIV
    left = max(auto - int(resident), 0)
    return min(explicit, left) if explicit else left


def group_share_bytes(share: float, setting: int = 0,
                      backend: Optional[str] = None) -> int:
    """Resolve a resource group's fractional HBM ``memory_share``
    (server/resource_groups.py, ISSUE 17) into the governed
    device_memory_budget for ONE admitted query: the share of the
    resolved whole-device budget, floored so a tiny share still
    leaves the governor a workable chunk size (it rewrites pipelines
    to fit rather than failing them). 0 when no share is configured —
    the session/default budget applies unchanged."""
    if share <= 0:
        return 0
    total = resolve_budget(setting, backend)
    return max(int(total * share), 1 << 24)


def rows_cap(row_bytes: int, budget: int, fault_rows: Optional[int],
             share_div: int) -> Optional[int]:
    """Largest governed buffer capacity (in rows, on the ladder) for a
    buffer of `row_bytes`-wide rows claiming budget/share_div bytes,
    additionally under the device fault line when one applies.
    None = unconstrained (no budget, no fault line)."""
    caps = []
    if budget:
        share = budget // share_div
        caps.append(max(share // max(int(row_bytes), 1), SH.LADDER_MIN))
    if fault_rows:
        caps.append(int(fault_rows))
    if not caps:
        return None
    cap = min(caps)
    # round DOWN to the ladder (bucket rounds up; a cap must not)
    b = SH.bucket(cap)
    return b if b <= cap else b >> 1


# ------------------------------------------------------------- audit
@dataclasses.dataclass
class BufferPlan:
    """One planned device buffer: what the executor will allocate for
    this node under the current session, per the shared sizing model."""

    label: str
    rows: int          # ladder-bucketed capacity
    row_bytes: int
    chunked: bool = False   # a governed rewrite resized/partitioned it
    # buffer donation (ISSUE 13): the executor's merge-accumulator
    # programs take this buffer via donate_argnums, so the merge input
    # and output SHARE one allocation — the donated input must not
    # double-count against the concurrent-footprint model
    donated: bool = False

    @property
    def bytes(self) -> int:
        return self.rows * self.row_bytes

    @property
    def live_bytes(self) -> int:
        """Contribution to the concurrent-footprint model: a donated
        accumulator holds ONE allocation across the merge chain (the
        in-place reuse donate_argnums buys), where the non-donated
        path holds the dying input alongside the fresh output — half
        the undonated model's 2x charge."""
        return self.bytes // 2 if self.donated else self.bytes


@dataclasses.dataclass
class AuditReport:
    budget: int
    fault_rows: Optional[int]
    buffers: List[BufferPlan]

    @property
    def peak_bytes(self) -> int:
        """Model pipeline peak: the sum of the two largest concurrent
        buffers plus one page share — a deliberate over- rather than
        under-estimate (streaming keeps most buffers dead). Donated
        accumulators count at live_bytes (in-place reuse)."""
        sizes = sorted((b.live_bytes for b in self.buffers),
                       reverse=True)
        return sum(sizes[:2]) + (sizes[2] if len(sizes) > 2 else 0) // 2

    @property
    def max_buffer_bytes(self) -> int:
        return max((b.bytes for b in self.buffers), default=0)

    def over_fault_line(self) -> List[BufferPlan]:
        """Buffers planned STRICTLY past the governed row ceiling — a
        buffer sized exactly at the cap is the governor doing its job
        (the real device fault line sits a ladder rung above it)."""
        if not self.fault_rows:
            return []
        return [b for b in self.buffers if b.rows > self.fault_rows]

    def over_budget(self) -> List[BufferPlan]:
        return [b for b in self.buffers if b.bytes > self.budget]

    @property
    def ok(self) -> bool:
        return not self.over_fault_line() and not self.over_budget()

    @property
    def chunked_count(self) -> int:
        return sum(1 for b in self.buffers if b.chunked)


def audit(ex, node) -> AuditReport:
    """Static per-plan footprint prediction: walk the physical plan
    recording every device buffer the executor WILL allocate under its
    current knobs — the same sizing functions the streaming paths call,
    so the prediction and the execution cannot drift apart. No pages
    are generated and nothing touches the device."""
    from presto_tpu.exec import plan as P
    from presto_tpu.exec.executor import _row_bytes, _stored_link

    budget = ex._budget()
    fault = ex._fault_rows()
    donate = ex._donate_on()
    buffers: List[BufferPlan] = []

    def add(label, rows, row_b, chunked=False, donated=False):
        buffers.append(BufferPlan(label, SH.bucket(rows), max(row_b, 1),
                                  chunked=chunked,
                                  donated=donated and donate))

    def emit_cap(n) -> Optional[int]:
        """Upper bound on the page capacity a subtree can EMIT — the
        executor's own clamps, which a raw cardinality estimate does
        not know about (a blocking sort above an aggregation merges the
        aggregation's clamped output, not the fact table)."""
        if isinstance(n, (P.Filter, P.Project, P.Exchange, P.Limit,
                          P.Output)):
            src = emit_cap(n.source)
            if isinstance(n, P.Limit):
                lim = SH.bucket(max(n.count + n.offset, 8))
                return lim if src is None else min(src, lim)
            return src
        if isinstance(n, P.Aggregation):
            if not n.group_channels:
                return SH.LADDER_MIN
            return ex._agg_sizing(n).cap
        if isinstance(n, P.TopN):
            return SH.bucket(max(n.limit, 8))
        return None

    # (TopN running-merge buffers are donated too — executor
    # topn_merge site — but TopN never reaches add(): its candidate
    # set is bounded by the limit bucket, noise next to real buffers)

    ridden = {}     # id(HashJoin) -> its StoredJoin as the chain has it

    def walk(n):
        if isinstance(n, P.TableScan):
            types = ex.output_types(n)
            row_b = _row_bytes(types)
            target = ex._governed_target_rows(types, count=False)
            add(f"scan {n.table} page", target, row_b,
                chunked=target < ex.page_rows)
            return
        if isinstance(n, P.HashJoin):
            left_types = ex.output_types(n.left)
            right_types = ex.output_types(n.right)
            gj = ex._generated_join_info(n, left_types)
            if gj is not None:
                # build-free: zero join state — but the fused chain's
                # page carries left+right columns per slot, and the
                # governor chunks generation by that WIDEST width
                out_types = ex.output_types(n)
                out_row_b = _row_bytes(out_types)
                target = ex._governed_target_rows(
                    out_types, count=False, row_bytes=out_row_b
                )
                add(f"genjoin chain page ({n.join_type})", target,
                    out_row_b, chunked=target < ex.page_rows)
                walk(n.left)
                return
            sj = ex._stored_join_info(n, left_types)
            if sj is not None:
                # a stored build (connectors/cached.py): its lookup
                # structure lives beside the resident tables for the
                # statement: the build side's page, a row a stored
                # slot, and the direct-address table's entries spread
                # over those rows; the probe is a step of the fused
                # chain, whose page is as wide as a generated join's.
                # A build's page also holds the columns of the joins
                # that ride on it (Executor._ride_stored_joins), which
                # the chain says, so the chain's links are kept from
                # its topmost join down
                if id(n) not in ridden:
                    walked = ex._scan_chain(n, through_joins=True,
                                            stored_joins=True)
                    for link in walked[1] if walked else ():
                        if _stored_link(link):
                            ridden.setdefault(id(link[0]), link[1])
                sj = ridden.get(id(n), sj)
                add(f"stored join build {sj.scan.table} "
                    f"({n.join_type})", sj.rows,
                    -(-sj.nbytes // max(sj.rows, 1)))
                out_types = ex.output_types(n)
                out_row_b = _row_bytes(out_types)
                target = ex._governed_target_rows(
                    out_types, count=False, row_bytes=out_row_b
                )
                add(f"stored join chain page ({n.join_type})", target,
                    out_row_b, chunked=target < ex.page_rows)
                walk(n.left)
                return
            row_b = _row_bytes(right_types)
            est_build = ex.estimate_rows(n.right)
            parts, governed = ex._join_parts(
                n, left_types, right_types, est_build, row_b
            )
            if parts == 1:
                per_pass = SH.bucket(est_build)
            else:
                # per-pass chunks carry 2x slack over 1/parts occupancy
                # (the same factor _join_parts governs for)
                per_pass = -(-SH.bucket(est_build) * 2 // parts)
            add(
                f"join build {n.join_type} (1/{parts} pass)",
                per_pass, row_b, chunked=governed,
            )
            out_row_b = row_b + _row_bytes(left_types)
            oc_cap = rows_cap(out_row_b, budget, fault, PAGE_SHARE_DIV)
            probe_rows = min(
                ex.page_rows, SH.bucket(ex.estimate_rows(n.left))
            )
            oc = SH.bucket(
                min(max(probe_rows * 2, 8192),
                    max(4 * ex.page_rows, 1 << 19))
            )
            add(
                f"join output {n.join_type}",
                min(oc, oc_cap) if oc_cap else oc, out_row_b,
                chunked=bool(oc_cap and oc > oc_cap),
            )
            walk(n.left)
            walk(n.right)
            return
        if isinstance(n, P.Aggregation):
            types = ex.output_types(n)
            row_b = _row_bytes(types)
            if not n.group_channels:
                add("global agg state", SH.LADDER_MIN, row_b)
            else:
                # the executor's own first-attempt decision
                # (Executor._agg_sizing): single path at sz.cap, or
                # hash-partition passes of the planner's capacity
                sz = ex._agg_sizing(n)
                if sz.parts > 1:
                    label = f"agg state (1/{sz.parts} pass)"
                    cap = SH.chunk_bucket(
                        SH.bucket(n.capacity * ex._capacity_boost),
                        sz.parts)
                else:
                    label, cap = "agg state", sz.cap
                # row ceiling = the executor's governed FOLD cap
                # (fr>>2), the largest state the single path can hold
                state_cap = rows_cap(
                    row_b, budget,
                    fault and max(fault >> 2, 8192),
                    BUILD_SHARE_DIV,
                )
                # the fold accumulator is a donated merge input when
                # buffer donation is on — the chained merges reuse
                # one allocation in place (executor agg_merge sites)
                add(label, min(cap, state_cap) if state_cap else cap,
                    row_b,
                    chunked=sz.parts > 1
                    or bool(state_cap and cap > state_cap),
                    donated=True)
                if sz.compact_rows:
                    add("agg compaction", sz.compact_rows,
                        _row_bytes(ex.output_types(n.source)))
            walk(n.source)
            return
        if isinstance(n, (P.Sort, P.Window, P.MarkDistinct)):
            # blocking whole-input merge: no chunked rewrite exists for
            # these yet — the audit REPORTS them so an over-line plan
            # fails loudly before the device faults. The estimate is
            # bounded by what the source can actually emit (an
            # aggregation's clamped output, a TopN's limit).
            types = ex.output_types(n)
            est = ex.estimate_rows(n)
            cap = emit_cap(n.source)
            if cap is not None:
                est = min(est, cap)
            add(f"{type(n).__name__.lower()} merge", est,
                _row_bytes(types))
            walk(n.source)
            return
        if isinstance(n, P.CrossJoin):
            add("cross build", 4096, _row_bytes(
                ex.output_types(n.right)))
            walk(n.left)
            walk(n.right)
            return
        for c in n.children():
            walk(c)

    walk(node)
    return AuditReport(budget=budget, fault_rows=fault, buffers=buffers)


def render(report: AuditReport) -> str:
    lines = [
        f"budget {report.budget / 1e6:.1f} MB, fault line "
        f"{report.fault_rows or '—'} rows; model peak "
        f"{report.peak_bytes / 1e6:.2f} MB; "
        f"{report.chunked_count} governed rewrites"
    ]
    over_line = set(map(id, report.over_fault_line()))
    for b in sorted(report.buffers, key=lambda x: -x.bytes):
        flag = ""
        if id(b) in over_line:
            flag = "  ** OVER FAULT LINE **"
        elif b.bytes > report.budget:
            flag = "  ** OVER BUDGET **"
        elif b.chunked:
            flag = "  [chunked]"
        elif b.donated:
            flag = "  [donated]"
        lines.append(
            f"  {b.label:<38} {b.rows:>10} rows x {b.row_bytes:>4} B "
            f"= {b.bytes / 1e6:>10.2f} MB{flag}"
        )
    return "\n".join(lines)
