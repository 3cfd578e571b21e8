"""Aggregate state layouts: decompose SQL aggregates into the primitive
segmented reductions of presto_tpu.ops.agg, with exact wide-decimal sums.

Reference: presto-main operator/aggregation/* — each @AggregationFunction
declares state / input / combine / output; e.g. avg = (sum, count) state with
a divide on output, decimal sums carry 128-bit state
(DecimalSumAggregation + UnscaledDecimal128Arithmetic). The TPU translation
of 128-bit state: split each unscaled i64 into (v >> 32, v & 0xFFFFFFFF) and
segment-sum the halves separately — each half-sum stays exact in i64 up to
2^31 rows per group, and hi*2^32 + lo reconstructs the exact 128-bit total,
emitted as a long-decimal limb Block (base-2^64 two's complement).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.ops import agg as A
from presto_tpu.page import Block

# numpy scalars, not jnp: module-level device buffers embedded as jit
# constants permanently degrade an earlier TPU runtime (see ops/hashing.py)
_MASK32 = np.int64(0xFFFFFFFF)
_U64_SIGN = np.uint64(0x8000000000000000)


@dataclasses.dataclass(frozen=True)
class StateCol:
    """One physical state column: which primitive reduction builds it from
    raw input, and which merges two partial states of it."""

    suffix: str
    input_kind: str  # ops.agg kind applied to raw input
    merge_kind: str  # ops.agg kind applied when combining partials
    type: T.SqlType
    # transform applied to the raw input column before reduction
    pre: Optional[str] = None  # None | 'hi32' | 'lo32'


# collect-state aggregate markers (handled by the executor's collect
# branches against ops/collect.py, never by ops/agg.aggregate)
COLLECT = "collect"
COLLECT_FNS = frozenset({"array_agg", "map_agg", "approx_percentile"})


def state_layout(function: str, in_type: Optional[T.SqlType]) -> List[StateCol]:
    """State columns for an aggregate over an input type (reference analog:
    the generated GroupedAccumulator field layout)."""
    if function == "count_star":
        return [StateCol("count", A.COUNT_STAR, A.SUM, T.BIGINT)]
    if function == "count":
        return [StateCol("count", A.COUNT, A.SUM, T.BIGINT)]
    if function in ("min", "max"):
        kind = A.MIN if function == "min" else A.MAX
        return [StateCol("value", kind, kind, in_type)]
    if function == "any":
        return [StateCol("value", A.ANY, A.ANY, in_type)]
    if function == "bool_or":
        return [StateCol("value", A.BOOL_OR, A.BOOL_OR, T.BOOLEAN)]
    if function == "bool_and":
        return [StateCol("value", A.BOOL_AND, A.BOOL_AND, T.BOOLEAN)]
    if function == "sum":
        if isinstance(in_type, T.DecimalType):
            return [
                StateCol("hi", A.SUM, A.SUM, T.BIGINT, pre="hi32"),
                StateCol("lo", A.SUM, A.SUM, T.BIGINT, pre="lo32"),
            ]
        if T.is_floating(in_type):
            return [StateCol("sum", A.SUM, A.SUM, T.DOUBLE)]
        return [StateCol("sum", A.SUM, A.SUM, T.BIGINT)]
    if function == "avg":
        return state_layout("sum", in_type) + state_layout("count", in_type)
    if function in VARIANCE_FNS:
        # (count, sum, sum-of-squares) double state; the planner casts the
        # input to DOUBLE first. Reference: operator/aggregation/
        # VarianceAggregation uses (count, mean, m2) Welford state — the
        # TPU translation uses moment sums because they are plain segmented
        # reductions (merge = add); m2 is recovered at finalize.
        return [
            StateCol("count", A.COUNT, A.SUM, T.BIGINT),
            StateCol("sum", A.SUM, A.SUM, T.DOUBLE),
            StateCol("sumsq", A.SUM, A.SUM, T.DOUBLE, pre="sq"),
        ]
    if function == "approx_distinct":
        # one tuple-data state column of packed HLL register words;
        # insert/merge/estimate are special-cased in the executor
        # kernels (exec/executor.py) against ops/hll.py. Reference:
        # operator/aggregation/ApproximateCountDistinctAggregation.
        return [StateCol("hll", A.HLL_INSERT, A.HLL_MERGE, T.HLL_STATE)]
    if function == "approx_percentile":
        # [cap, K] collected-value matrix + used-slot count;
        # insert/merge special-cased in the executor kernels against
        # ops/collect.py (reference: ApproximatePercentileAggregations;
        # ours is EXACT within the array_agg_max_elements bound).
        return [
            StateCol("vals", COLLECT, COLLECT, T.CollectStateType(
                in_type if in_type is not None else T.UNKNOWN)),
            StateCol("count", A.COUNT, A.SUM, T.BIGINT),
        ]
    if function == "array_agg":
        # value matrix + element-null-flag matrix + used-slot count
        # (reference: ArrayAggregationFunction — null elements are
        # INCLUDED in the collected array)
        return [
            StateCol("vals", COLLECT, COLLECT, T.CollectStateType(
                in_type if in_type is not None else T.UNKNOWN)),
            StateCol("vnulls", COLLECT, COLLECT,
                     T.CollectStateType(T.UNKNOWN)),
            StateCol("count", A.COUNT, A.SUM, T.BIGINT),
        ]
    if function == "map_agg":
        # collected keys + values + value-null flags + count
        # (reference: MapAggregationFunction's KeyValuePairsState —
        # null keys skipped, null values preserved)
        return [
            StateCol("kvals", COLLECT, COLLECT, T.CollectStateType(
                in_type if in_type is not None else T.UNKNOWN)),
            StateCol("vvals", COLLECT, COLLECT,
                     T.CollectStateType(T.UNKNOWN)),
            StateCol("vnulls", COLLECT, COLLECT,
                     T.CollectStateType(T.UNKNOWN)),
            StateCol("count", A.COUNT, A.SUM, T.BIGINT),
        ]
    if function in _PLUGIN_AGGS:
        return list(_PLUGIN_AGGS[function].state)
    raise ValueError(f"unknown aggregate function: {function}")


VARIANCE_FNS = frozenset(
    {"var_samp", "var_pop", "stddev_samp", "stddev_pop"}
)


@dataclasses.dataclass(frozen=True)
class AggregateFunctionSpec:
    """Plugin aggregate (reference: @AggregationFunction state/input/
    combine/output; spi/Plugin.getFunctions). The TPU decomposition:
    ``state`` columns are built from the primitive segmented-reduction
    kinds of ops/agg (input_kind on raw input, merge_kind on partial
    states — so PARTIAL/FINAL splits, spill partitions, and mesh
    repartition all work unchanged), and ``finalize(xp, states)``
    combines the merged state arrays into ``(data, nulls-or-None)``.

    ``StateCol.pre`` may be a module-level callable (traced transform
    applied to the raw input before reduction); lambdas would defeat
    the jit cache keying, so use named functions."""

    name: str
    state: Tuple[StateCol, ...]
    result: object  # SqlType, or callable(in_type) -> SqlType
    finalize: object  # fn(xp, states) -> (data, nulls or None)


_PLUGIN_AGGS: dict = {}


def register_aggregate(spec: AggregateFunctionSpec) -> None:
    _PLUGIN_AGGS[spec.name] = spec


def is_plugin_aggregate(name: str) -> bool:
    return name in _PLUGIN_AGGS


def result_type(
    function: str,
    in_type: Optional[T.SqlType],
    extra: tuple = (),
) -> T.SqlType:
    """Reference: FunctionRegistry aggregate signatures — sum(bigint)->
    bigint, sum(decimal(p,s))->decimal(38,s), avg(decimal(p,s))->
    decimal(p,s), count->bigint. ``extra`` carries additional input
    types (map_agg's value column)."""
    if function in ("count", "count_star"):
        return T.BIGINT
    if function == "array_agg":
        return T.ArrayType(in_type if in_type is not None else T.UNKNOWN)
    if function == "map_agg":
        return T.MapType(
            in_type if in_type is not None else T.UNKNOWN,
            extra[0] if extra else T.UNKNOWN,
        )
    if function == "approx_percentile":
        return in_type
    if function in ("min", "max", "any"):
        return in_type
    if function in ("bool_or", "bool_and"):
        return T.BOOLEAN
    if function == "sum":
        if isinstance(in_type, T.DecimalType):
            return T.DecimalType(38, in_type.scale)
        if T.is_floating(in_type):
            return T.DOUBLE
        return T.BIGINT
    if function == "avg":
        if isinstance(in_type, T.DecimalType):
            return in_type
        return T.DOUBLE
    if function in VARIANCE_FNS:
        return T.DOUBLE
    if function == "approx_distinct":
        return T.BIGINT
    if function in _PLUGIN_AGGS:
        r = _PLUGIN_AGGS[function].result
        return r(in_type) if callable(r) else r
    raise ValueError(f"unknown aggregate function: {function}")


def pre_transform(pre, data: jnp.ndarray) -> jnp.ndarray:
    if pre is None:
        return data
    if callable(pre):  # plugin aggregates: named traced transform
        return pre(data)
    if pre == "hi32":
        return data >> jnp.int64(32)  # arithmetic: floor(v / 2^32)
    if pre == "lo32":
        return data & _MASK32
    if pre == "sq":
        d = data.astype(jnp.float64)
        return d * d
    raise ValueError(pre)


def split32_to_limbs(hi: jnp.ndarray, lo: jnp.ndarray):
    """(sum of v>>32, sum of v&0xFFFFFFFF) -> base-2^64 two's-complement
    limbs of the exact 128-bit value hi*2^32 + lo."""
    u_shift = hi.astype(jnp.uint64) << jnp.uint64(32)
    u_lo = lo.astype(jnp.uint64)
    lo64 = u_shift + u_lo
    carry = (lo64 < u_shift).astype(jnp.int64)
    hi64 = (hi >> jnp.int64(32)) + carry
    return hi64, lo64.astype(jnp.int64)


def finalize(
    function: str,
    in_type: Optional[T.SqlType],
    out_type: T.SqlType,
    states: List[Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
    xp=jnp,
) -> Block:
    """Combine merged state columns into the SQL result Block."""
    if function in ("count", "count_star"):
        data, _ = states[0]
        return Block(data=data, type=T.BIGINT, nulls=None)
    if function in ("min", "max", "any", "bool_or", "bool_and"):
        data, nulls = states[0]
        return Block(data=data, type=out_type, nulls=nulls)
    if function == "sum":
        if isinstance(in_type, T.DecimalType):
            (hi, hn), (lo, _) = states
            hi64, lo64 = split32_to_limbs(hi, lo)
            return Block(data=(hi64, lo64), type=out_type, nulls=hn)
        data, nulls = states[0]
        return Block(data=data, type=out_type, nulls=nulls)
    if function == "avg":
        if isinstance(in_type, T.DecimalType):
            (hi, hn), (lo, _), (count, _) = states
            cnt = xp.maximum(count, jnp.int64(1))
            # exact two-step 128/64 divide with round-half-up; derivation
            # assumes the non-negative domain (money sums); negative totals
            # fall back through the same path with floor bias ≤ 1 ulp.
            # lo is a segment-sum of 32-bit halves (up to n*2^32 for an
            # n-row group), so fold its high half into the 2^32-weighted
            # dividend first — keeps rest < (n+1)*2^32, in-range through
            # the documented 2^31-rows-per-group bound.
            hi2 = hi + (lo >> jnp.int64(32))
            lo_low = lo & jnp.int64(0xFFFFFFFF)
            qh = hi2 // cnt
            rh = hi2 - qh * cnt
            rest = (rh << jnp.int64(32)) + lo_low
            q2 = (rest + cnt // jnp.int64(2)) // cnt
            avg = (qh << jnp.int64(32)) + q2
            return Block(data=avg, type=out_type, nulls=hn)
        (s, sn), (count, _) = states
        cnt = xp.maximum(count, jnp.int64(1)).astype(jnp.float64)
        data = s.astype(jnp.float64) / cnt
        return Block(data=data, type=T.DOUBLE, nulls=sn)
    if function in VARIANCE_FNS:
        (count, _), (s, _), (sq, _) = states
        n = count.astype(jnp.float64)
        safe_n = xp.maximum(n, 1.0)
        s = s.astype(jnp.float64)
        # m2 = sum((x - mean)^2) = sumsq - sum^2/n; clamp the cancellation
        # residue so rounding never yields a negative variance / NaN sqrt
        m2 = xp.maximum(sq.astype(jnp.float64) - s * s / safe_n, 0.0)
        if function.endswith("_pop"):
            var = m2 / safe_n
            nulls = count == 0
        else:
            var = m2 / xp.maximum(n - 1.0, 1.0)
            nulls = count < 2
        if function.startswith("stddev"):
            var = xp.sqrt(var)
        return Block(data=var, type=T.DOUBLE, nulls=nulls)
    if function in _PLUGIN_AGGS:
        data, nulls = _PLUGIN_AGGS[function].finalize(xp, states)
        return Block(data=data, type=out_type, nulls=nulls)
    raise ValueError(f"unknown aggregate function: {function}")
