"""Bounded per-group value collection — the state behind array_agg /
map_agg / approx_percentile.

Reference: presto-main operator/aggregation/ArrayAggregationFunction
(grouped BlockBuilder state), MapAggregationFunction, and
ApproximatePercentileAggregations (qdigest sketch). The TPU translation
keeps static shapes: every group owns K slots of a [cap, K] int64 state
matrix (K = the ``array_agg_max_elements`` session property); a group
exceeding K raises a clear error rather than silently truncating.
Values encode into int64 (ints/dates/bools/short decimals directly,
dictionary-coded types by code, floats via an ORDER-PRESERVING
arithmetic sign/exponent/mantissa pack — see executor._collect_encode;
no 64-bit bitcast compiles on an earlier TPU toolchain).
approx_percentile finalizes by sorting each group's K slots and
selecting — EXACT percentiles within the K bound, strictly stronger
than the reference's sketch.

Null semantics (reference parity): array_agg INCLUDES null elements
(a parallel null-flag matrix rides the state); map_agg skips null keys
but preserves null values; approx_percentile ignores nulls. Row order
within a group follows input order (the reference's array_agg order is
unspecified)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

EMPTY = jnp.int64(0)


def _group_ranks(ids: jnp.ndarray, n_invalid_id: int):
    """rank of each row within its group (stable input order). ids of
    invalid rows must equal n_invalid_id (sorted to the end)."""
    n = ids.shape[0]
    perm = jnp.argsort(ids, stable=True)
    sid = ids[perm]
    idxs = jnp.arange(n, dtype=jnp.int64)
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sid[1:] != sid[:-1]]
    )
    run_start = jax.lax.cummax(jnp.where(boundary, idxs, 0))
    rank_sorted = idxs - run_start
    return perm, sid, rank_sorted


def insert(
    group_ids: jnp.ndarray,
    contributing: jnp.ndarray,
    out_cap: int,
    vals_i64: jnp.ndarray,
    K: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Collect contributing rows' values into [out_cap, K] group slots
    (input order). Returns (state, overflow: any group exceeded K)."""
    ids = jnp.where(contributing, group_ids.astype(jnp.int64), out_cap)
    perm, sid, rank = _group_ranks(ids, out_cap)
    flat = jnp.where(
        (sid < out_cap) & (rank < K), sid * K + rank, out_cap * K
    )
    state = (
        jnp.zeros((out_cap * K + 1,), dtype=jnp.int64)
        .at[flat]
        .set(vals_i64[perm], mode="drop")[: out_cap * K]
        .reshape(out_cap, K)
    )
    overflow = jnp.any((sid < out_cap) & (rank >= K))
    return state, overflow


def merge(
    group_ids: jnp.ndarray,
    row_valid: jnp.ndarray,
    out_cap: int,
    state: jnp.ndarray,
    counts: jnp.ndarray,
    K: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge partial collect states: each input row carries a [K] slot
    vector holding ``counts`` values; concatenate per output group in
    row order. Returns (merged [out_cap, K], overflow)."""
    n = row_valid.shape[0]
    counts = jnp.where(row_valid, counts.astype(jnp.int64), 0)
    ids = jnp.where(row_valid, group_ids.astype(jnp.int64), out_cap)
    perm, sid, _rank = _group_ranks(ids, out_cap)
    csort = counts[perm]
    # base offset of each input row inside its output group = prefix
    # sum of earlier member rows' counts (segmented prefix sum)
    cum = jnp.cumsum(csort)
    idxs = jnp.arange(n, dtype=jnp.int64)
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sid[1:] != sid[:-1]]
    )
    excl = cum - csort  # exclusive prefix over all rows
    run_base = jax.lax.cummax(jnp.where(boundary, excl, 0))
    base = excl - run_base
    # scatter each row's first `count` slots to group base offsets
    k_idx = jnp.arange(K, dtype=jnp.int64)[None, :]
    tgt_rank = base[:, None] + k_idx  # [n, K]
    live = (k_idx < csort[:, None]) & (sid[:, None] < out_cap)
    flat = jnp.where(
        live & (tgt_rank < K),
        sid[:, None] * K + tgt_rank,
        out_cap * K,
    )
    vals_sorted = state[perm]  # [n, K]
    merged = (
        jnp.zeros((out_cap * K + 1,), dtype=jnp.int64)
        .at[flat.reshape(-1)]
        .set(vals_sorted.reshape(-1), mode="drop")[: out_cap * K]
        .reshape(out_cap, K)
    )
    overflow = jnp.any(live & (tgt_rank >= K))
    return merged, overflow


def percentile_select(
    state: jnp.ndarray,
    counts: jnp.ndarray,
    fraction: float,
    K: int,
) -> jnp.ndarray:
    """Per-group percentile over collected values: mask-pad, sort each
    row, select index ceil(p * count) - 1 (reference semantics:
    lower-interpolation percentile of the value multiset). The float
    slot-encoding (exec/executor._collect_encode) is order-preserving,
    so plain int64 ordering is correct for every element type."""
    k_idx = jnp.arange(K, dtype=jnp.int64)[None, :]
    live = k_idx < counts[:, None]
    big = jnp.iinfo(jnp.int64).max
    padded = jnp.where(live, state, big)
    s = jnp.sort(padded, axis=-1)
    want = jnp.ceil(fraction * counts.astype(jnp.float64)).astype(
        jnp.int64
    )
    pick = jnp.clip(want - 1, 0, jnp.maximum(counts - 1, 0))
    return jnp.take_along_axis(s, pick[:, None], axis=-1)[:, 0]
