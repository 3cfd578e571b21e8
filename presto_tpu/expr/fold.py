"""Constant folding of the expression IR at plan time.

Reference: presto-main sql/planner/ExpressionInterpreter.java (the
optimizer's SimplifyExpressions) and sql/relational/optimizer/
ExpressionOptimizer.java: a CallExpression on constant arguments of a
deterministic function becomes a ConstantExpression.

The value comes from the engine's own implementation: the evaluator
the executor traces (``eval.evaluate`` -> ``functions.eval_call``), run
with ``xp = numpy`` over a one-slot page with no columns. One
implementation gives the row-wise and the folded answer, so month-end clamping, leap days and decimal rescaling
cannot differ between a folded and an unfolded plan.

What folds depends only on what the tree shows: an ``ir.Call`` whose
name is in FOLDABLE_CALLS, whose arguments are all non-NULL
``ir.Constant``s of an exact type, and whose own type is exact. Exact
types are the ones whose host and device arithmetic agree to the bit:
integral, short decimal, boolean, date, timestamp and the two interval
types. DOUBLE / REAL stay (host IEEE arithmetic and the chip's emulated
f64 may differ in the last bit), strings and every dictionary-encoded
type stay (a string constant's Val carries no data), and a call whose
host evaluation raises or yields a NULL stays as it is, so the
statement behaves as it did unfolded. Special forms (AND, IF, IN,
BETWEEN, COALESCE) are walked, never folded themselves.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.expr import ir
from presto_tpu.expr.eval import evaluate
from presto_tpu.page import Page

# The allow-list: operators and date/time field functions whose value
# is a function of their arguments alone. Anything else (random, now,
# current_*, uuid, shuffle, a comparison, a string function) stays a
# Call whatever its arguments are.
FOLDABLE_CALLS = frozenset({
    "add", "subtract", "multiply", "divide", "modulus", "negate", "abs",
    "cast",
    "year", "month", "day", "quarter", "week", "day_of_week",
    "day_of_year", "hour", "minute", "second", "millisecond",
})

# what a constant call is evaluated over: one slot, no column
_ONE_SLOT = Page(blocks=(), valid=np.ones((1,), dtype=bool))

_EXACT = (
    T.BooleanType, T.DateType, T.TimestampType,
    T.IntervalDayTimeType, T.IntervalYearMonthType,
)


def is_exact(t: T.SqlType) -> bool:
    if isinstance(t, T.DecimalType):
        return t.is_short
    return T.is_integral(t) or isinstance(t, _EXACT)


def _host_value(call: ir.Call) -> Optional[object]:
    """The call's value over its constant arguments as ``ir.Constant``
    documents it (int, or bool for BOOLEAN); None where it cannot be
    had on the host."""
    try:
        with np.errstate(all="ignore"):
            out = evaluate(call, _ONE_SLOT, np)
    except Exception:  # noqa: BLE001 - whatever it raised, the node stays
        return None
    if isinstance(out.data, tuple):  # long-decimal limbs
        return None
    if out.nulls is not None and bool(np.any(out.nulls)):
        return None
    got = np.reshape(out.data, (-1,))[0]  # numpy all the way: xp is
    if isinstance(call.type, T.BooleanType):
        return bool(got) if got.dtype.kind == "b" else None
    if got.dtype.kind not in "iu":
        return None
    value = int(got)
    info = np.iinfo(np.dtype(call.type.numpy_dtype))
    return value if info.min <= value <= info.max else None


def _fold_call(call: ir.Call) -> Optional[ir.Constant]:
    if call.name not in FOLDABLE_CALLS or not is_exact(call.type):
        return None
    for a in call.args:
        if not (isinstance(a, ir.Constant) and a.value is not None
                and is_exact(a.type)):
            return None
    value = _host_value(call)
    if value is None:
        return None
    return ir.Constant(value, call.type)


def fold_constants(
    e: ir.RowExpression,
) -> Tuple[ir.RowExpression, int]:
    """Post-order fold: (the tree with every foldable Call replaced by
    the Constant of its value, of the Call's own type; how many Calls
    were replaced). A tree with nothing to fold comes back as the same
    object."""
    if isinstance(e, ir.Lambda):
        body, n = fold_constants(e.body)
        return (dataclasses.replace(e, body=body) if n else e), n
    if not isinstance(e, (ir.Call, ir.SpecialForm)):
        return e, 0
    n = 0
    args = []
    for a in e.args:
        a2, k = fold_constants(a)
        args.append(a2)
        n += k
    if n:
        e = dataclasses.replace(e, args=tuple(args))
    if isinstance(e, ir.Call):
        folded = _fold_call(e)
        if folded is not None:
            return folded, n + 1
    return e, n
