"""Group-by as segmented reduction (the TPU replacement for the reference's
open-addressing hash tables).

Reference: presto-main operator/HashAggregationOperator.java drives
operator/GroupByHash.java (BigintGroupByHash fast path /
MultiChannelGroupByHash) with per-row probe/insert — pointer-chasing that maps
terribly to a vector unit. TPU-native design (BASELINE north-star: "hash
aggregation as segmented reduction"):

  - **sorted path** (general): lexsort rows by null-aware key encodings, mark
    group boundaries where adjacent keys differ, group id = prefix-sum of
    boundaries, then jax.ops.segment_* reductions with indices_are_sorted.
    O(n log n) but fully vectorized, no collisions, deterministic.
  - **dense path** (small key spaces, e.g. dictionary-coded flag columns):
    group id computed arithmetically from codes, direct segment reductions
    with a static group count — this is the Q1 fast path, analogous to the
    reference's BigintGroupByHash small-range optimization.

Output is fixed-capacity with a group validity mask plus an ``overflow`` flag
(true if real group count exceeded capacity) so drivers can re-run with a
larger capacity — the compiled-branch escape for dynamic cardinality
(SURVEY §8.2.1).

Partial/final split (reference: AggregationNode.Step PARTIAL/FINAL) is
expressed by running the same primitives over partial-state pages with merge
kinds (sum->sum, count->sum, min->min, max->max).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Primitive accumulator kinds. Compound SQL aggregates decompose into these
# (avg -> sum+count with a finalize divide; reference analog: the
# @AggregationFunction state/input/combine/output decomposition).
SUM = "sum"
COUNT = "count"  # counts non-null inputs
COUNT_STAR = "count_star"
MIN = "min"
MAX = "max"
ANY = "any"  # arbitrary non-null value (used for grouped key passthrough)
BOOL_OR = "bool_or"
BOOL_AND = "bool_and"
# HyperLogLog kinds: tuple-data states, handled by the executor kernels
# against ops/hll.py (not by aggregate() below)
HLL_INSERT = "hll_insert"
HLL_MERGE = "hll_merge"


@dataclasses.dataclass(frozen=True)
class AggInput:
    kind: str
    # data/nulls indices into the arrays passed alongside; COUNT_STAR has none
    has_input: bool = True


def _null_aware_sort_keys(
    key_cols: Sequence[jnp.ndarray],
    key_nulls: Sequence[Optional[jnp.ndarray]],
    valid: jnp.ndarray,
) -> List[jnp.ndarray]:
    """Sort keys: validity first (valid rows to front), then per key column a
    (null-flag, normalized-value) pair so SQL NULLs form their own group."""
    keys: List[jnp.ndarray] = [
        jnp.where(valid, jnp.uint64(0), jnp.uint64(1))
    ]
    for col, null in zip(key_cols, key_nulls):
        if null is None:
            keys.append(jnp.zeros(col.shape, dtype=jnp.uint64))
            keys.append(col)
        else:
            keys.append(jnp.where(null, jnp.uint64(1), jnp.uint64(0)))
            keys.append(jnp.where(null, jnp.uint64(0), col))
    return keys


def _lexsort(keys: List[jnp.ndarray]) -> jnp.ndarray:
    # jnp.lexsort: LAST key is primary; ours are listed primary-first.
    return jnp.lexsort(tuple(reversed(keys)))


@dataclasses.dataclass
class GroupbyResult:
    group_ids: jnp.ndarray  # int64[cap_in] group id per input row (clipped)
    row_valid: jnp.ndarray  # contributing rows (input valid)
    rep_index: jnp.ndarray  # int64[out_cap] representative input row per group
    group_valid: jnp.ndarray  # bool[out_cap]
    num_groups: jnp.ndarray  # traced scalar
    overflow: jnp.ndarray  # traced bool
    # dense path only: per-key-column code-space sizes. Group id is the
    # mixed-radix encoding of the key codes, so callers can synthesize
    # key columns arithmetically from arange(out_cap) instead of
    # gathering through rep_index — XLA then dead-code-eliminates the
    # rep scatter entirely.
    dense_sizes: Optional[Tuple[int, ...]] = None
    # sorted path only: the group-sort permutation and the per-SORTED-
    # row group id (invalid rows = out_cap, sorted to the tail). With
    # these, aggregate() computes SUM/COUNT via gather+cumsum+boundary
    # differences — no scatter at all (scatter: ~14M rows/s on TPU;
    # sort+cumsum: ~250M rows/s). The input-order group_ids scatter
    # above is then dead code XLA eliminates.
    sort_perm: Optional[jnp.ndarray] = None
    gid_sorted: Optional[jnp.ndarray] = None
    # group g occupies sorted positions [seg_start[g], seg_end[g]);
    # computed once per page with one scatter-min (group ids from the
    # sort are consecutive, so end[g] = start[g+1])
    seg_start: Optional[jnp.ndarray] = None
    seg_end: Optional[jnp.ndarray] = None


def compute_groups_sorted(
    key_cols: Sequence[jnp.ndarray],
    key_nulls: Sequence[Optional[jnp.ndarray]],
    valid: jnp.ndarray,
    out_capacity: int,
) -> GroupbyResult:
    """Assign group ids via sort; no aggregation yet.

    Reference analog: GroupByHash.getGroupIds(Page) — returns a group id per
    input position; aggregation happens against those ids.
    """
    from presto_tpu.ops import keys as K
    from presto_tpu.ops.sort import packed_argsort

    # bit-pack (validity, per-key null flag + word) and sort via LSD
    # chained single-word argsorts: one multi-operand lexsort compiles
    # for minutes on XLA:TPU, k two-operand argsorts compile in seconds
    parts = [(jnp.where(valid, jnp.uint64(0), jnp.uint64(1)), 1)]
    cmp_words: List[jnp.ndarray] = []
    for col, null in zip(key_cols, key_nulls):
        if null is not None:
            nw = null.astype(jnp.uint64)
            parts.append((nw, 1))
            cmp_words.append(nw)
            col = jnp.where(null, jnp.uint64(0), col)
        parts.append((col, 64))
        cmp_words.append(col)
    words = K.pack_sort_keys(parts)
    perm = packed_argsort(words, valid.shape[0])
    svalid = valid[perm]

    diff = jnp.zeros(valid.shape, dtype=jnp.bool_)
    for k in cmp_words:
        sk = k[perm]
        d = jnp.concatenate(
            [jnp.ones((1,), dtype=jnp.bool_), sk[1:] != sk[:-1]]
        )
        diff = diff | d
    boundary = svalid & diff
    gid_sorted = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    num_groups = jnp.sum(boundary.astype(jnp.int64))
    overflow = num_groups > out_capacity

    # scatter sorted-order group ids back to input order
    gids = jnp.zeros(valid.shape, dtype=jnp.int64)
    gids = gids.at[perm].set(jnp.clip(gid_sorted, 0, out_capacity - 1))

    # group g occupies sorted positions [start[g], end[g]). Group ids
    # from the sort are CONSECUTIVE (cumsum of boundaries), so one
    # scatter-min of boundary positions gives every start and
    # end[g] = start[g+1] (n_valid for the last group). This is the
    # only scatter the sorted path pays per page; every reduction then
    # runs scatter-free on [start, end) cumsum differences.
    gid_x = jnp.where(svalid, gid_sorted, out_capacity)
    n = valid.shape[0]
    idxs = jnp.arange(n, dtype=jnp.int64)
    n_valid = jnp.sum(svalid.astype(jnp.int64))
    start = (
        jnp.full((out_capacity + 1,), jnp.int64(n))
        .at[jnp.where(boundary & (gid_sorted < out_capacity),
                      gid_sorted, out_capacity)]
        .min(idxs, mode="drop")
    )
    start = jnp.minimum(start, n_valid)
    seg_start = start[:out_capacity]
    seg_end = jnp.concatenate(
        [start[1:out_capacity], n_valid[None]]
    )
    seg_end = jnp.maximum(seg_start, seg_end)
    rep = perm[jnp.clip(seg_start, 0, n - 1)].astype(jnp.int64)
    group_valid = jnp.arange(out_capacity, dtype=jnp.int64) < num_groups
    return GroupbyResult(
        group_ids=gids,
        row_valid=valid,
        rep_index=rep,
        group_valid=group_valid,
        num_groups=num_groups,
        overflow=overflow,
        sort_perm=perm,
        gid_sorted=gid_x,
        seg_start=seg_start,
        seg_end=seg_end,
    )


def compute_groups_dense(
    group_ids: jnp.ndarray,
    valid: jnp.ndarray,
    num_groups: int,
    out_capacity: Optional[int] = None,
    sizes: Optional[Tuple[int, ...]] = None,
) -> GroupbyResult:
    """Group ids already computed arithmetically (e.g. from dictionary codes:
    gid = code_a * |dict_b| + code_b). Static group count, no sort, no hash
    table — the Q1 fast path (reference analog: BigintGroupByHash's
    small-range optimization). Output arrays are padded to out_capacity
    (>= num_groups) so callers can mix this with the hashed path.
    """
    cap = out_capacity or num_groups
    assert cap >= num_groups
    # Segment ops (the rep scatter below) run over num_groups+1 segments,
    # NOT cap+1: segment count must match the true key space (6 for Q1),
    # never the caller's generic capacity.
    ids = jnp.where(valid, group_ids.astype(jnp.int64), num_groups)
    if _mm_backend_ok() and num_groups <= MATMUL_AGG_MAX_GROUPS:
        counts = _mm_count(ids, num_groups)
    else:
        counts = jax.ops.segment_sum(
            jnp.ones(valid.shape, dtype=jnp.int64),
            ids,
            num_segments=num_groups + 1,
        )[:num_groups]
    pad = cap - num_groups
    group_valid = jnp.pad(counts > 0, (0, pad))
    # representative row per group: min input index holding that gid
    idx = jnp.arange(valid.shape[0], dtype=jnp.int64)
    rep = jax.ops.segment_min(
        jnp.where(valid, idx, jnp.int64(2**62)),
        ids,
        num_segments=num_groups + 1,
    )[:num_groups]
    rep = jnp.pad(jnp.clip(rep, 0, valid.shape[0] - 1), (0, pad))
    return GroupbyResult(
        group_ids=jnp.clip(ids, 0, cap - 1),
        row_valid=valid,
        rep_index=rep,
        group_valid=group_valid,
        num_groups=jnp.sum(group_valid.astype(jnp.int64)),
        overflow=jnp.asarray(False),
        dense_sizes=sizes,
    )


def compute_groups_hashed(
    key_cols: Sequence[jnp.ndarray],
    key_nulls: Sequence[Optional[jnp.ndarray]],
    valid: jnp.ndarray,
    out_capacity: int,
    max_iters: int = 64,
) -> GroupbyResult:
    """Group assignment via a vectorized linear-probing hash table — the
    TPU-native GroupByHash (reference: operator/GroupByHash.java's
    open-addressing probe/insert, re-expressed as data-parallel rounds).

    Each round, every unsettled row claims its current slot with a
    scatter-min of its row index (deterministic winner), then checks whether
    the slot's owner carries an equal key; matching rows settle, losers probe
    the next slot. Equal-key rows start at the same hash slot and observe the
    same owners, so they advance in lockstep and can never split into two
    groups; scatter-min is commutative, so the whole procedure is
    deterministic. Compile cost is a handful of gather/scatter ops inside one
    while_loop body — versus a multi-operand u64 lexsort whose XLA:TPU
    comparator blows up exponentially in key count (measured: 17s -> 66s
    compile going from 1 to 2 u64 sort operands).

    Table capacity is 2x out_capacity (load factor <= 0.5 when the group
    count fits). Unresolved rows after max_iters or group count overflow set
    the overflow flag — callers retry with doubled capacity (SURVEY §8.2.1).
    """
    from presto_tpu.ops import hashing as H

    n = valid.shape[0]
    cols: List[jnp.ndarray] = []
    for c, nl in zip(key_cols, key_nulls):
        if nl is None:
            cols.append(c.astype(jnp.uint64))
        else:
            # fold the null flag in as its own word: NULL groups with NULL
            cols.append(jnp.where(nl, jnp.uint64(0), c.astype(jnp.uint64)))
            cols.append(nl.astype(jnp.uint64))
    h = H.hash_columns(cols, [None] * len(cols))

    cap = max(2 * out_capacity, 16)
    cap = 1 << (cap - 1).bit_length()  # pow2 for mask probing
    mask = jnp.int64(cap - 1)
    BIG = jnp.int64(n)
    row_idx = jnp.arange(n, dtype=jnp.int64)
    init_slot = (h & jnp.uint64(cap - 1)).astype(jnp.int64)

    def key_eq_owner(owner, slot):
        """settled mask: does the row's slot owner carry an equal key?"""
        win = owner[slot]
        winc = jnp.clip(win, 0, n - 1)
        ok = win < n
        for c in cols:
            ok = ok & (c[winc] == c)
        return valid & ok

    def cond(state):
        owner, slot, it = state
        unsettled = valid & ~key_eq_owner(owner, slot)
        return jnp.any(unsettled) & (it < max_iters)

    def body(state):
        owner, slot, it = state
        settled = key_eq_owner(owner, slot)
        claim = jnp.where(settled | ~valid, BIG, row_idx)
        owner = owner.at[slot].min(claim)
        settled2 = key_eq_owner(owner, slot)
        slot = jnp.where(settled2 | ~valid, slot, (slot + 1) & mask)
        return owner, slot, it + 1

    owner0 = jnp.full((cap,), BIG, dtype=jnp.int64)
    owner, slot, _ = jax.lax.while_loop(
        cond, body, (owner0, init_slot, jnp.int64(0))
    )

    settled = key_eq_owner(owner, slot)
    unresolved = jnp.any(valid & ~settled)
    # occupied slots = slots some row actually settled in (ghost claims from
    # rows that probed past are excluded by deriving occupancy from rows)
    used = (
        jnp.zeros((cap + 1,), dtype=jnp.bool_)
        .at[jnp.where(settled, slot, cap)]
        .set(True, mode="drop")[:cap]
    )
    gid_slot = jnp.cumsum(used.astype(jnp.int64)) - 1
    num_groups = jnp.sum(used.astype(jnp.int64))
    overflow = unresolved | (num_groups > out_capacity)

    gids = jnp.clip(gid_slot[slot], 0, out_capacity - 1)
    rep = (
        jnp.full((out_capacity + 1,), jnp.int64(2**62))
        .at[jnp.where(settled, gids, out_capacity)]
        .min(row_idx, mode="drop")[:out_capacity]
    )
    rep = jnp.clip(rep, 0, n - 1)
    group_valid = jnp.arange(out_capacity, dtype=jnp.int64) < num_groups
    return GroupbyResult(
        group_ids=gids,
        row_valid=valid & settled,
        rep_index=rep,
        group_valid=group_valid,
        num_groups=num_groups,
        overflow=overflow,
    )


# Above this group capacity the one-hot matmul aggregation falls back to
# XLA scatter. n x G int8 MACs are effectively free on the MXU up to here
# (measured: G=4096 over 256k rows adds < 1ms to a launch; scatter costs
# ~80ms per 1M rows regardless of G).
MATMUL_AGG_MAX_GROUPS = 4096


def _onehot(ids: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """n x G int8 one-hot of group ids. Rows whose id is outside
    [0, num_groups) are all-zero — callers route invalid/null rows to
    id == num_groups so they drop out of every matmul for free. XLA
    fuses the compare into the dot; the n x G matrix never hits HBM."""
    return (
        ids[:, None] == jnp.arange(num_groups, dtype=ids.dtype)[None, :]
    ).astype(jnp.int8)


def _mm_count(ids: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Per-group row count as an MXU matmul: ones-vector x one-hot with
    int32 accumulation (exact for any page <= 2^31 rows)."""
    ones = jnp.ones((1, ids.shape[0]), dtype=jnp.int8)
    acc = jax.lax.dot_general(
        ones, _onehot(ids, num_groups), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )[0]
    return acc.astype(jnp.int64)


def _mm_sum_int(
    data: jnp.ndarray, ids: jnp.ndarray, num_groups: int
) -> jnp.ndarray:
    """Exact int64 per-group sum on the MXU (the scatter replacement
    that makes hash aggregation MXU-bound instead of scatter-bound).

    Decompose each value into 16 unsigned 4-bit limbs of its u64 bit
    pattern, matmul all limbs against the one-hot in one s8xs8->s32
    dot (per-limb group sums <= 15 * n < 2^31 for any n <= 2^27), then
    recombine with wrapping u64 shifts — addition mod 2^64 distributes
    over the limb decomposition, so the result equals the two's-
    complement int64 sum exactly, negatives included.

    int64<->uint64 moves use astype (two's-complement wrapping
    conversion: identical bits) rather than bitcast_convert_type — an
    earlier TPU compile service SIGSEGVs on 64-bit bitcasts (see
    exec/executor._collect_encode), and astype avoids the op class
    entirely."""
    u = data.astype(jnp.int64).astype(jnp.uint64)
    limbs = jnp.stack(
        [((u >> jnp.uint64(4 * k)) & jnp.uint64(0xF)).astype(jnp.int8)
         for k in range(16)]
    )  # (16, n)
    acc = jax.lax.dot_general(
        limbs, _onehot(ids, num_groups), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (16, G)
    shifts = (jnp.uint64(1) << (jnp.uint64(4)
                                * jnp.arange(16, dtype=jnp.uint64)))
    total = jnp.sum(
        acc.astype(jnp.uint64) * shifts[:, None], axis=0,
        dtype=jnp.uint64,
    )
    return total.astype(jnp.int64)


_MM_BACKEND: Optional[bool] = None


def _mm_backend_ok() -> bool:
    """One-hot matmul aggregation only where the compiler fuses the
    n x G one-hot into the dot (MXU path). XLA:CPU materializes it —
    gigabytes at served shapes — so CPU (tests, oracle children) keeps
    the scatter path, which computes identical results.
    PRESTO_TPU_MM_AGG=1/0 overrides (CPU parity tests force it on
    tiny shapes)."""
    global _MM_BACKEND
    if _MM_BACKEND is None:
        import os

        v = os.environ.get("PRESTO_TPU_MM_AGG")
        if v is not None:
            _MM_BACKEND = v == "1"
        else:
            _MM_BACKEND = jax.default_backend() == "tpu"
    return _MM_BACKEND


def _mm_eligible(kind: str, num_groups: int, data) -> bool:
    if num_groups > MATMUL_AGG_MAX_GROUPS or not _mm_backend_ok():
        return False
    if kind in (COUNT, COUNT_STAR, BOOL_OR, BOOL_AND):
        return True
    return kind == SUM and data is not None and jnp.issubdtype(
        data.dtype, jnp.integer
    )


def _minmax_identity(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if is_min else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(is_min, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if is_min else info.min, dtype=dtype)


def _sorted_aggregate(
    groups: GroupbyResult,
    kind: str,
    out_capacity: int,
    data: Optional[jnp.ndarray],
    nulls: Optional[jnp.ndarray],
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Scatter-free segmented reduction over the sorted group layout:
    gather rows into group order, one cumulative sum, difference at
    group boundaries (start positions come from a method='sort'
    searchsorted over the sorted group ids). Exact for integers
    (prefix sums stay in-range: |page total| < 2^63); float SUM keeps
    the scatter path for accumulation-order stability."""
    perm, gidx = groups.sort_perm, groups.gid_sorted
    n = perm.shape[0]
    contributing_sorted = gidx < out_capacity
    if nulls is not None:
        contributing_sorted = contributing_sorted & ~nulls[perm]

    if kind in (SUM, BOOL_OR, BOOL_AND):
        assert data is not None
        ds = data[perm]
    if kind == SUM:
        x = jnp.where(contributing_sorted, ds,
                      jnp.zeros((), dtype=data.dtype))
    elif kind in (BOOL_OR, BOOL_AND):
        x = jnp.where(
            contributing_sorted & ds.astype(jnp.bool_),
            jnp.int64(1), jnp.int64(0),
        )
    else:  # COUNT / COUNT_STAR
        x = contributing_sorted.astype(jnp.int64)

    csum = jnp.cumsum(x)
    start, end = groups.seg_start, groups.seg_end
    pcs = jnp.concatenate([jnp.zeros((1,), dtype=csum.dtype), csum])
    totals = pcs[end] - pcs[start]

    if kind == COUNT_STAR:
        return totals, None
    ncontrib = (end - start).astype(jnp.int64)
    if nulls is not None:
        pcn = jnp.concatenate([
            jnp.zeros((1,), dtype=jnp.int64),
            jnp.cumsum(contributing_sorted.astype(jnp.int64)),
        ])
        ncontrib = pcn[end] - pcn[start]
    empty = ncontrib == 0
    if kind == COUNT:
        return ncontrib, None
    if kind == BOOL_OR:
        return (totals > 0), empty
    if kind == BOOL_AND:
        return (totals == ncontrib) & ~empty, empty
    return totals, empty  # SUM


def aggregate(
    groups: GroupbyResult,
    kind: str,
    out_capacity: int,
    data: Optional[jnp.ndarray] = None,
    nulls: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One primitive aggregation over assigned group ids.

    Returns (values[out_capacity], null_mask or None). SQL semantics: SUM /
    MIN / MAX / ANY over zero non-null inputs yield NULL; COUNT yields 0.
    """
    ids = jnp.where(groups.row_valid, groups.group_ids, out_capacity)
    nseg = out_capacity + 1
    mm = _mm_eligible(kind, out_capacity, data)
    if (groups.sort_perm is not None and not mm
            and kind in (SUM, COUNT, COUNT_STAR, BOOL_OR, BOOL_AND)
            and (data is None or not isinstance(data, tuple))
            and (kind != SUM
                 or jnp.issubdtype(data.dtype, jnp.integer))):
        return _sorted_aggregate(groups, kind, out_capacity, data, nulls)

    if kind == COUNT_STAR:
        if mm:
            return _mm_count(ids, out_capacity), None
        ones = jnp.ones(groups.row_valid.shape, dtype=jnp.int64)
        out = jax.ops.segment_sum(ones, ids, num_segments=nseg)[:out_capacity]
        return out, None

    assert data is not None
    contributing = groups.row_valid
    if nulls is not None:
        contributing = contributing & ~nulls
    cids = jnp.where(contributing, groups.group_ids, out_capacity)
    if mm:
        ncontrib = _mm_count(cids, out_capacity)
    else:
        ncontrib = jax.ops.segment_sum(
            jnp.ones(contributing.shape, dtype=jnp.int64),
            cids,
            num_segments=nseg,
        )[:out_capacity]
    empty = ncontrib == 0

    if kind == COUNT:
        return ncontrib, None
    if kind == SUM:
        if mm:
            out = _mm_sum_int(data, cids, out_capacity)
            return out.astype(data.dtype), empty
        zero = jnp.zeros((), dtype=data.dtype)
        out = jax.ops.segment_sum(
            jnp.where(contributing, data, zero), cids, num_segments=nseg
        )[:out_capacity]
        return out, empty
    if kind == BOOL_OR and mm:
        trues = _mm_count(
            jnp.where(data.astype(jnp.bool_), cids, out_capacity),
            out_capacity,
        )
        return (trues > 0), empty
    if kind == BOOL_AND and mm:
        trues = _mm_count(
            jnp.where(data.astype(jnp.bool_), cids, out_capacity),
            out_capacity,
        )
        return (trues == ncontrib) & ~empty, empty
    if kind in (MIN, MAX):
        ident = _minmax_identity(data.dtype, kind == MIN)
        filled = jnp.where(contributing, data, ident)
        seg = jax.ops.segment_min if kind == MIN else jax.ops.segment_max
        out = seg(filled, cids, num_segments=nseg)[:out_capacity]
        out = jnp.where(empty, jnp.zeros((), dtype=data.dtype), out)
        return out, empty
    if kind == ANY:
        # value at min contributing row index
        idx = jnp.arange(data.shape[0], dtype=jnp.int64)
        first = jax.ops.segment_min(
            jnp.where(contributing, idx, jnp.int64(2**62)),
            cids,
            num_segments=nseg,
        )[:out_capacity]
        first = jnp.clip(first, 0, data.shape[0] - 1)
        return data[first], empty
    if kind == BOOL_OR:
        out = jax.ops.segment_max(
            jnp.where(contributing, data.astype(jnp.int32), 0),
            cids,
            num_segments=nseg,
        )[:out_capacity]
        return out.astype(jnp.bool_), empty
    if kind == BOOL_AND:
        out = jax.ops.segment_min(
            jnp.where(contributing, data.astype(jnp.int32), 1),
            cids,
            num_segments=nseg,
        )[:out_capacity]
        return out.astype(jnp.bool_), empty
    raise ValueError(f"unknown aggregation kind: {kind}")


def global_aggregate(
    kind: str,
    valid: jnp.ndarray,
    data: Optional[jnp.ndarray] = None,
    nulls: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Ungrouped aggregation (reference: operator/AggregationOperator.java).
    Returns (scalar value, scalar is_null). COUNT of empty input is 0, SUM is
    NULL — SQL global aggregates always produce exactly one row."""
    if kind == COUNT_STAR:
        return jnp.sum(valid.astype(jnp.int64)), jnp.asarray(False)
    assert data is not None
    contributing = valid
    if nulls is not None:
        contributing = contributing & ~nulls
    n = jnp.sum(contributing.astype(jnp.int64))
    empty = n == 0
    if kind == COUNT:
        return n, jnp.asarray(False)
    if kind == SUM:
        zero = jnp.zeros((), dtype=data.dtype)
        return jnp.sum(jnp.where(contributing, data, zero)), empty
    if kind in (MIN, MAX):
        ident = _minmax_identity(data.dtype, kind == MIN)
        filled = jnp.where(contributing, data, ident)
        val = jnp.min(filled) if kind == MIN else jnp.max(filled)
        return jnp.where(empty, jnp.zeros((), dtype=data.dtype), val), empty
    if kind == ANY:
        idx = jnp.arange(data.shape[0], dtype=jnp.int64)
        first = jnp.min(jnp.where(contributing, idx, jnp.int64(2**62)))
        first = jnp.clip(first, 0, data.shape[0] - 1)
        return data[first], empty
    if kind == BOOL_OR:
        return jnp.any(contributing & data.astype(jnp.bool_)), empty
    if kind == BOOL_AND:
        return (
            jnp.all(jnp.where(contributing, data.astype(jnp.bool_), True))
            & ~empty,
            empty,
        )
    raise ValueError(f"unknown aggregation kind: {kind}")


MERGE_KIND = {
    SUM: SUM,
    COUNT: SUM,
    COUNT_STAR: SUM,
    MIN: MIN,
    MAX: MAX,
    ANY: ANY,
    BOOL_OR: BOOL_OR,
    BOOL_AND: BOOL_AND,
}
