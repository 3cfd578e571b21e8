"""Equi-join kernel: sort-searchsorted hash join with fixed-capacity match
expansion.

Reference: presto-main operator/HashBuilderOperator.java builds a PagesIndex +
JoinHash (open-addressing over row addresses); operator/LookupJoinOperator
probes row-at-a-time via JoinProbe. Pointer-chasing again — the TPU design
replaces both with sorted arrays + vectorized binary search:

  build:  hash build keys -> sort build rows by hash (one lexsort)
  probe:  searchsorted(left/right) gives each probe row a candidate range
          [lo, hi); range width = candidate match count
  expand: fixed-capacity output; slot j belongs to probe row
          searchsorted(cumsum(counts), j) at offset j - prefix — a branch-free
          flattening of the variable-fanout probe loop
  verify: gathered candidate keys compared for true equality, so 64-bit hash
          collisions cost only wasted slots, never wrong results

Dynamic output cardinality is handled capacity+overflow-flag style (SURVEY
§8.2.1): callers size out_capacity, check ``overflow``, and retry bigger. The
planner picks build/probe sides (reference: AddExchanges join distribution);
outer-row emission (LEFT/RIGHT/FULL) and semi joins assemble from the match
statistics returned here (reference: LookupJoinOperators factories,
HashSemiJoinOperator).

The Pallas dim probe in presto_tpu/ops/pallas_join.py replaces the
searchsorted range finder on TPU for builds of at most 2,048 rows — it
produces the same per-probe-row [lo, lo+count) candidate ranges and
shares expand_matches() below for verified expansion. The executor
picks per join (pallas_join_enabled=auto: Pallas on TPU for such
builds, sort elsewhere).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax.numpy as jnp

from presto_tpu.ops import hashing as H


@dataclasses.dataclass
class JoinMatches:
    probe_idx: jnp.ndarray  # int64[out_cap] probe row per slot
    build_idx: jnp.ndarray  # int64[out_cap] build row per slot
    match: jnp.ndarray  # bool[out_cap] verified match
    probe_match_count: jnp.ndarray  # int64[probe_cap]
    build_matched: jnp.ndarray  # bool[build_cap]
    total_candidates: jnp.ndarray  # traced scalar (pre-verification)
    overflow: jnp.ndarray  # traced bool


def _fold_nulls(
    cols: Sequence[jnp.ndarray],
    nulls: Sequence[Optional[jnp.ndarray]],
    null_equals_null: bool,
) -> tuple[List[jnp.ndarray], jnp.ndarray]:
    """Returns (normalized key cols, any_null_disqualifies mask).

    SQL equi-join: a NULL key never matches (unless IS NOT DISTINCT FROM
    semantics, null_equals_null=True, where NULL matches NULL)."""
    n = cols[0].shape[0]
    any_null = jnp.zeros((n,), dtype=jnp.bool_)
    out_cols: List[jnp.ndarray] = []
    for c, nl in zip(cols, nulls):
        if nl is None:
            out_cols.append(c)
            if null_equals_null:
                # keep column counts symmetric across sides even when only
                # one side has a nulls mask
                out_cols.append(jnp.zeros((n,), dtype=jnp.uint64))
            continue
        out_cols.append(jnp.where(nl, jnp.uint64(0), c))
        if null_equals_null:
            out_cols.append(nl.astype(jnp.uint64))
        else:
            any_null = any_null | nl
    return out_cols, any_null


def build_join_index(
    build_cols: Sequence[jnp.ndarray],
    build_nulls: Sequence[Optional[jnp.ndarray]],
    build_valid: jnp.ndarray,
    *,
    null_equals_null: bool = False,
):
    """Build-side index, computed ONCE per join and reused by every probe
    page (reference: HashBuilderOperator's LookupSource shared across
    LookupJoinOperators). The index is a pytree: (folded key cols,
    validity, hash-sorted array, sort permutation).

    Build rows sort by hash with invalid rows poisoned to the max hash —
    ONE sort operand, not two: every extra u64 sort operand roughly
    doubles XLA:TPU's sort compile time, and the equality verification in
    the probe rejects any real-hash collisions with the poison value."""
    bcols, b_null_out = _fold_nulls(build_cols, build_nulls, null_equals_null)
    bvalid = build_valid & ~b_null_out
    bhash = H.hash_columns(bcols, [None] * len(bcols))
    poisoned = jnp.where(bvalid, bhash, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    perm = jnp.argsort(poisoned)
    return (tuple(bcols), bvalid, poisoned[perm], perm)


def hash_join_match(
    build_cols: Optional[Sequence[jnp.ndarray]],
    build_nulls: Optional[Sequence[Optional[jnp.ndarray]]],
    build_valid: Optional[jnp.ndarray],
    probe_cols: Sequence[jnp.ndarray],
    probe_nulls: Sequence[Optional[jnp.ndarray]],
    probe_valid: jnp.ndarray,
    out_capacity: int,
    *,
    null_equals_null: bool = False,
    index=None,
) -> JoinMatches:
    """Match probe rows against build rows on equality-encoded uint64 keys.

    Pass a prebuilt ``index`` (build_join_index) to skip re-sorting the
    build side per probe page."""
    if index is None:
        index = build_join_index(
            build_cols, build_nulls, build_valid,
            null_equals_null=null_equals_null,
        )
    bcols, bvalid, sorted_hash, perm = index

    pcols, p_null_out = _fold_nulls(probe_cols, probe_nulls, null_equals_null)
    pvalid = probe_valid & ~p_null_out
    phash = H.hash_columns(pcols, [None] * len(pcols))

    # method="sort" lowers to a concat-sort rank computation instead
    # of a log2(n)-iteration gather loop — measured 13x faster on TPU
    # (64ms vs 1.06s for lo+hi at 2M x 1M; an earlier TPU runtime)
    lo = jnp.searchsorted(sorted_hash, phash, side="left", method="sort")
    hi = jnp.searchsorted(sorted_hash, phash, side="right", method="sort")
    counts = (hi - lo).astype(jnp.int64)

    return expand_matches(
        bcols, bvalid, perm, pcols, pvalid, lo, counts, out_capacity
    )


def expand_matches(
    bcols,
    bvalid: jnp.ndarray,
    perm: jnp.ndarray,
    pcols,
    pvalid: jnp.ndarray,
    lo: jnp.ndarray,
    counts: jnp.ndarray,
    out_capacity: int,
) -> JoinMatches:
    """Flatten per-probe-row candidate ranges [lo, lo+counts) over the
    hash-sorted build order `perm` into a fixed-capacity match list,
    verifying true key equality per slot. Shared tail of the sort join
    (searchsorted ranges) and the Pallas dim probe (kernel-probed
    ranges) — the range *finder* is the only thing that differs."""
    build_cap = bvalid.shape[0]
    probe_cap = pvalid.shape[0]
    counts = jnp.where(pvalid, counts.astype(jnp.int64), 0)

    cum = jnp.cumsum(counts)
    total = cum[-1] if counts.shape[0] else jnp.int64(0)
    overflow = total > out_capacity

    slots = jnp.arange(out_capacity, dtype=jnp.int64)
    pid = jnp.searchsorted(cum, slots, side="right", method="sort")
    pid_c = jnp.clip(pid, 0, probe_cap - 1)
    prev = jnp.concatenate([jnp.zeros((1,), dtype=cum.dtype), cum[:-1]])
    off = slots - prev[pid_c]
    sorted_pos = jnp.clip(lo[pid_c].astype(jnp.int64) + off, 0, build_cap - 1)
    bid = perm[sorted_pos].astype(jnp.int64)

    in_range = slots < total
    match = in_range & pvalid[pid_c] & bvalid[bid]
    for bc, pc in zip(bcols, pcols):
        match = match & (bc[bid] == pc[pid_c])

    probe_match_count = (
        jnp.zeros((probe_cap + 1,), dtype=jnp.int64)
        .at[jnp.where(match, pid_c, probe_cap)]
        .add(1, mode="drop")[:probe_cap]
    )
    build_matched = (
        jnp.zeros((build_cap + 1,), dtype=jnp.bool_)
        .at[jnp.where(match, bid, build_cap)]
        .max(True, mode="drop")[:build_cap]
    )

    return JoinMatches(
        probe_idx=pid_c,
        build_idx=bid,
        match=match,
        probe_match_count=probe_match_count,
        build_matched=build_matched,
        total_candidates=total,
        overflow=overflow,
    )


def unique_join_lookup(
    bcols,
    bvalid: jnp.ndarray,
    perm: jnp.ndarray,
    pcols,
    pvalid: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
):
    """FK-join fast path: build keys are provably unique, so every
    probe row has <= 1 true match — no expansion, no output-capacity
    machinery; the output is the probe page itself plus gathered build
    columns (reference: LookupJoinOperator's unique-positions path).

    Only the FIRST candidate in the probe row's hash range is checked.
    A range wider than 1 means distinct unique keys collided in the
    u64 hash (~2^-64 per pair); ``collision`` flags it for the
    boosted-retry ladder, where eligibility falls back to the general
    expansion — wasted work, never wrong results.

    Returns (build_idx[int64, probe_cap], found[bool], collision)."""
    build_cap = bvalid.shape[0]
    pos = jnp.clip(lo.astype(jnp.int64), 0, build_cap - 1)
    bid = perm[pos].astype(jnp.int64)
    in_range = (hi - lo) >= 1
    found = in_range & pvalid & bvalid[bid]
    for bc, pc in zip(bcols, pcols):
        found = found & (bc[bid] == pc)
    collision = jnp.any(pvalid & ((hi - lo) > 1))
    return bid, found, collision


def semi_join_mask(
    build_cols: Sequence[jnp.ndarray],
    build_nulls: Sequence[Optional[jnp.ndarray]],
    build_valid: jnp.ndarray,
    probe_cols: Sequence[jnp.ndarray],
    probe_nulls: Sequence[Optional[jnp.ndarray]],
    probe_valid: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-probe-row (has_match, null_result) for IN / semi-join predicates.

    Reference: operator/HashSemiJoinOperator.java + SetBuilderOperator.
    null_result marks SQL three-valued unknown: probe key NULL, or no match
    while the build set contains a NULL (x IN (...NULL...) is NULL, not
    false).
    """
    bcols, b_null = _fold_nulls(build_cols, build_nulls, False)
    pcols, p_null = _fold_nulls(probe_cols, probe_nulls, False)
    bvalid = build_valid & ~b_null
    build_has_null = jnp.any(build_valid & b_null)

    none_nulls = [None] * len(bcols)
    bhash = H.hash_columns(bcols, none_nulls)
    phash = H.hash_columns(pcols, none_nulls)
    poisoned = jnp.where(bvalid, bhash, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    perm = jnp.argsort(poisoned)
    sorted_hash = poisoned[perm]
    lo = jnp.searchsorted(sorted_hash, phash, side="left", method="sort")
    hi = jnp.searchsorted(sorted_hash, phash, side="right", method="sort")

    # verify within a bounded window (hash collisions beyond window are
    # astronomically unlikely; window also bounds compile size)
    WINDOW = 4
    has_match = jnp.zeros(probe_valid.shape, dtype=jnp.bool_)
    build_cap = bvalid.shape[0]
    for w in range(WINDOW):
        pos = jnp.clip(lo + w, 0, build_cap - 1)
        bid = perm[pos]
        ok = (lo + w < hi) & bvalid[bid]
        for bc, pc in zip(bcols, pcols):
            ok = ok & (bc[bid] == pc)
        has_match = has_match | ok
    # fall back for pathological windows: any remaining candidates counted as
    # match only if hashes matched exactly (collision risk accepted 2^-64)
    has_match = has_match | ((hi - lo) > WINDOW)

    null_result = probe_valid & (
        p_null | (~has_match & build_has_null)
    )
    return probe_valid & has_match & ~p_null, null_result
