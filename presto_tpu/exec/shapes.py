"""The shared program-shape bucket ladder.

Presto amortizes per-query codegen by aggressively reusing compiled
artifacts across queries (reference: sql/gen/ExpressionCompiler's
compiled-expression LRU, keyed on canonical expression shape). The
JAX-native analog has two halves: a persistent compilation cache
(presto_tpu/compilecache.py) and — the half that makes the cache
actually HIT — canonicalizing every dynamic capacity the executor
feeds into program shapes onto ONE power-of-two ladder.

Every join build/output capacity, aggregation group capacity,
grace-partition chunk size, fragment buffer size, and boosted-retry
size quantizes through `bucket` below. Two consequences:

  - a retry or a slightly different planner estimate lands on a rung
    an earlier compilation already paid for (same HLO -> engine jit
    cache hit, or at worst a persistent-cache hit instead of a fresh
    XLA compile);
  - distinct program shapes per operator family are bounded by the
    ladder's log2 depth instead of by the number of distinct
    estimates the planner can produce.

The overflow-retry ladder is part of the same contract: a boost
multiplies by BOOST_STEP (a power of two), so a boosted capacity
re-enters the ladder exactly BOOST_STEP.bit_length()-1 rungs up —
never an off-ladder ad-hoc size that would mint a fresh shape.
"""

from __future__ import annotations

# The ladder floor: no operator buffer is ever sized below this many
# slots (tiny shapes cost a full compile each just like big ones).
LADDER_MIN = 8

# Overflow-retry multiplier: each boosted attempt climbs exactly two
# rungs. Shared by Executor.execute() and the worker-fragment
# stream_fragment() path so a retried fragment's shapes coincide with
# a bigger query's first-attempt shapes.
BOOST_STEP = 4


def bucket(n: int, floor: int = LADDER_MIN) -> int:
    """Quantize a capacity/size onto the ladder: the smallest power of
    two >= max(n, floor). THE canonical quantizer — every program-shape
    size in the engine routes through here."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def next_bucket(n: int) -> int:
    """The rung strictly above n: where a size that overflowed its
    bucket re-enters the ladder (never an ad-hoc `n * 2`-ish size)."""
    b = bucket(n)
    return b * 2 if b <= n else b


def next_boost(boost: int) -> int:
    """The next rung of the retry ladder (see BOOST_STEP)."""
    return boost * BOOST_STEP


def chunk_bucket(total: int, parts: int, floor: int = 1024) -> int:
    """Per-partition chunk capacity for grace-style partitioned passes
    (aggregation state, join builds, skew-rebalance chunks): ~2x the
    expected total/parts occupancy — absorbing partition-hash
    fluctuation without a boosted retry — quantized to the ladder."""
    return bucket(max(total // max(parts, 1) * 2, floor))


def exchange_partition_cap(capacity: int, nparts: int,
                           boost: int) -> int:
    """Landing capacity of ONE partition page the device repartition
    kernel compacts to (dist/spool.device_partition_pages): the grace-
    chunk sizing scaled by the overflow-retry boost, never past the
    source page's own bucket. Boost is a ladder power of two, so a
    skewed key distribution re-enters exactly BOOST_STEP rungs up —
    the exchange shares the shapes contract of every other buffer."""
    if nparts <= 1:
        return bucket(capacity)
    return min(bucket(capacity),
               chunk_bucket(capacity, nparts) * bucket(boost, 1))


# ------------------------------------------------ device-memory model
# An earlier XLA:TPU runtime faulted kernels touching >=~4M-row buffers
# (bisected round 4; not re-verified on the current chip, see ROADMAP
# A2; the reason max_join_build_rows and SPLIT_BATCH_ROWS_MAX exist). The memory governor (exec/membudget.py)
# keeps every PLANNED buffer capacity under this line by construction.
DEVICE_FAULT_ROWS = 1 << 22

# Construction headroom under the fault line: governed buffers size to
# at most half of it, so one boosted-retry rung (x4 capped by the
# governor's own chunking) cannot land exactly ON the line.
SAFE_BUFFER_ROWS = DEVICE_FAULT_ROWS >> 1


def buffer_bytes(rows: int, row_bytes: int) -> int:
    """Static footprint of one operator buffer sized for `rows`: the
    capacity quantizes to the ladder first (that IS the allocation the
    executor makes), so the byte model predicts real allocations, not
    raw row counts."""
    return bucket(rows) * max(int(row_bytes), 1)


def parts_for(rows: int, row_bytes: int, rows_cap, bytes_cap,
              max_parts: int = 256) -> int:
    """Grace-partition pass count that keeps ONE pass's materialization
    of `rows` x `row_bytes` under both caps (None = unconstrained).
    Power of two so partition passes land on the shared ladder."""
    need = 1
    b = bucket(rows)
    if rows_cap:
        need = max(need, -(-b // int(rows_cap)))
    if bytes_cap:
        per_row = max(int(row_bytes), 1)
        need = max(need, -(-(b * per_row) // int(bytes_cap)))
    if need <= 1:
        return 1
    return min(bucket(need, floor=2), max_parts)


# --------------------------------------------------- split batching
# Split-batched execution (exec/executor._fused_stream): how many
# splits of a fused scan pipeline fold into ONE XLA program launch.
# The per-launch overhead (~6 ms on an earlier TPU runtime; not
# re-verified on the current chip, see ROADMAP A2) multiplies by
# splits x programs; batching divides the split factor away. 64 bounds
# the tail-batch padding waste (a padded slot still runs the full
# generator) while keeping SF100's ~600 splits at ~10 launches.
SPLIT_BATCH_MAX = 64

# vmapped page-emitting batches materialize [B, n_pad] stacked buffers
# for the whole batch at once; B * n_pad stays under the
# >=4M-row kernel fault line (the same ceiling max_join_build_rows
# exists for). The lax.scan paths carry one split at a time and are
# exempt.
SPLIT_BATCH_ROWS_MAX = DEVICE_FAULT_ROWS


def split_batch_bucket(n: int) -> int:
    """Batch-size bucket for split-batched execution: the smallest
    power of two >= n (floor 2, not LADDER_MIN — batch counts are a
    different family from row capacities). Full batches are sized to a
    power of two by the caller, so only the tail batch pads — with
    traced zero row counts that mask every generated row out — and
    distinct batched programs per pipeline are bounded by the ladder's
    log2 depth, composing with the persistent compile cache exactly
    like every other program shape."""
    return bucket(n, floor=2)
