"""SQL type system mapped onto device dtypes.

Reference: presto-spi src/main/java/com/facebook/presto/spi/type/* (Type
interface, BigintType, VarcharType, DecimalType, ...) and presto-main
type/TypeRegistry.java. The reference's Type both describes values and reads /
writes Blocks; here a SqlType describes values and knows its *device
representation* (jnp dtype or dictionary encoding) — block IO lives in
presto_tpu.page.

Device representation decisions (TPU-first):
  - BIGINT/INTEGER/SMALLINT/TINYINT -> int64/int32/int16/int8 arrays.
  - DOUBLE -> float64 (x64 enabled); REAL -> float32.
  - BOOLEAN -> bool arrays.
  - DATE -> int32 days since 1970-01-01 (same as the reference).
  - TIMESTAMP -> int64 epoch micros (reference uses millis; micros is the
    modern choice and documented here).
  - DECIMAL(p, s): p <= 18 -> int64 scaled by 10**s ("short decimal", same
    split as the reference's Slice-backed long decimals at p > 18);
    p > 18 -> two int64 limbs (hi, lo) little-endian, two's complement.
  - VARCHAR/CHAR -> dictionary encoding: int32 codes on device plus a
    host-side Dictionary (presto_tpu.page.Dictionary). TPUs do not branch
    per byte; all string comparison/LIKE run on codes or host-side over the
    dictionary, which is tiny for analytic workloads.
  - VARBINARY -> host-side payloads; on-device only as int32 row handles.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SqlType:
    """Base class for SQL types. Frozen + hashable: types are static pytree
    aux data, so they must compare by value for jit cache hits."""

    name: str = dataclasses.field(init=False, default="unknown")

    @property
    def is_comparable(self) -> bool:
        return True

    @property
    def is_orderable(self) -> bool:
        return True

    # --- device representation -------------------------------------------
    @property
    def device_dtype(self):
        """jnp dtype of the primary device array for this type."""
        raise NotImplementedError(self)

    @property
    def is_dictionary_encoded(self) -> bool:
        return False

    @property
    def numpy_dtype(self):
        return np.dtype(self.device_dtype)

    def display(self) -> str:
        return self.name

    def __str__(self) -> str:  # pragma: no cover - convenience
        return self.display()


@dataclasses.dataclass(frozen=True)
class FixedWidthType(SqlType):
    pass


@dataclasses.dataclass(frozen=True)
class BigintType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="bigint")

    @property
    def device_dtype(self):
        return jnp.int64


@dataclasses.dataclass(frozen=True)
class IntegerType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="integer")

    @property
    def device_dtype(self):
        return jnp.int32


@dataclasses.dataclass(frozen=True)
class SmallintType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="smallint")

    @property
    def device_dtype(self):
        return jnp.int16


@dataclasses.dataclass(frozen=True)
class TinyintType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="tinyint")

    @property
    def device_dtype(self):
        return jnp.int8


@dataclasses.dataclass(frozen=True)
class DoubleType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="double")

    @property
    def device_dtype(self):
        return jnp.float64


@dataclasses.dataclass(frozen=True)
class RealType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="real")

    @property
    def device_dtype(self):
        return jnp.float32


@dataclasses.dataclass(frozen=True)
class BooleanType(FixedWidthType):
    name: str = dataclasses.field(init=False, default="boolean")

    @property
    def device_dtype(self):
        return jnp.bool_


@dataclasses.dataclass(frozen=True)
class DateType(FixedWidthType):
    """Days since the 1970-01-01 epoch, int32 (reference: spi/type/DateType)."""

    name: str = dataclasses.field(init=False, default="date")

    @property
    def device_dtype(self):
        return jnp.int32


@dataclasses.dataclass(frozen=True)
class TimestampType(FixedWidthType):
    """Epoch microseconds, int64."""

    name: str = dataclasses.field(init=False, default="timestamp")

    @property
    def device_dtype(self):
        return jnp.int64


@dataclasses.dataclass(frozen=True)
class DecimalType(FixedWidthType):
    """DECIMAL(precision, scale).

    Reference: spi/type/DecimalType.java + DecimalShortType/LongDecimalType and
    spi/type/UnscaledDecimal128Arithmetic.java for p > 18. Values are exact
    scaled integers — never floats (money must checksum exactly; TPU f64 is
    slow anyway). p <= 18 fits int64; p > 18 uses 2x int64 limbs.
    """

    precision: int = 38
    scale: int = 0
    name: str = dataclasses.field(init=False, default="decimal")

    def __post_init__(self):
        if not (1 <= self.precision <= 38):
            raise ValueError(f"decimal precision out of range: {self.precision}")
        if not (0 <= self.scale <= self.precision):
            raise ValueError(f"decimal scale out of range: {self.scale}")

    @property
    def is_short(self) -> bool:
        return self.precision <= 18

    @property
    def device_dtype(self):
        return jnp.int64

    def display(self) -> str:
        return f"decimal({self.precision},{self.scale})"


@dataclasses.dataclass(frozen=True)
class VarcharType(SqlType):
    """VARCHAR(n). Dictionary-encoded on device (int32 codes)."""

    length: Optional[int] = None  # None = unbounded
    name: str = dataclasses.field(init=False, default="varchar")

    @property
    def device_dtype(self):
        return jnp.int32

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    def display(self) -> str:
        return "varchar" if self.length is None else f"varchar({self.length})"


@dataclasses.dataclass(frozen=True)
class CharType(SqlType):
    """CHAR(n) — space-padded semantics on comparison (host-side)."""

    length: int = 1
    name: str = dataclasses.field(init=False, default="char")

    @property
    def device_dtype(self):
        return jnp.int32

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    def display(self) -> str:
        return f"char({self.length})"


@dataclasses.dataclass(frozen=True)
class VarbinaryType(SqlType):
    name: str = dataclasses.field(init=False, default="varbinary")

    @property
    def device_dtype(self):
        return jnp.int32  # row handle into host-side payload store

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    @property
    def is_orderable(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class IntervalDayTimeType(FixedWidthType):
    """INTERVAL DAY TO SECOND — epoch-free duration in microseconds, int64
    (reference: spi/type/ (airlift units) IntervalDayTimeType, millis)."""

    name: str = dataclasses.field(init=False, default="interval day to second")

    @property
    def device_dtype(self):
        return jnp.int64


@dataclasses.dataclass(frozen=True)
class IntervalYearMonthType(FixedWidthType):
    """INTERVAL YEAR TO MONTH — whole months, int32."""

    name: str = dataclasses.field(init=False, default="interval year to month")

    @property
    def device_dtype(self):
        return jnp.int32


@dataclasses.dataclass(frozen=True)
class UnknownType(SqlType):
    """Type of NULL literals before coercion (reference: spi UnknownType)."""

    name: str = dataclasses.field(init=False, default="unknown")

    @property
    def device_dtype(self):
        return jnp.bool_


@dataclasses.dataclass(frozen=True)
class ArrayType(SqlType):
    """ARRAY(element). Device representation: dictionary-coded i32 —
    the distinct array VALUES (Python tuples) live in a host-side
    Dictionary, rows carry codes (reference: spi/block/ArrayBlock's
    offsets+elements, re-expressed for static shapes: per-value work
    happens once per distinct array on the host at trace time, row
    work is vectorized gathers — same scheme as strings)."""

    element: SqlType = dataclasses.field(default_factory=UnknownType)
    name: str = dataclasses.field(init=False, default="array")

    @property
    def device_dtype(self):
        return jnp.int32

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    def display(self) -> str:
        return f"array({self.element.display()})"


@dataclasses.dataclass(frozen=True)
class MapType(SqlType):
    """MAP(key, value): dictionary-coded like ARRAY; each distinct map
    value is a Python tuple of (key, value) pairs (reference:
    spi/block/ MapBlock / SingleMapBlock)."""

    key: SqlType = dataclasses.field(default_factory=UnknownType)
    value: SqlType = dataclasses.field(default_factory=UnknownType)
    name: str = dataclasses.field(init=False, default="map")

    @property
    def device_dtype(self):
        return jnp.int32

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    def display(self) -> str:
        return f"map({self.key.display()}, {self.value.display()})"


@dataclasses.dataclass(frozen=True)
class RowType(SqlType):
    """ROW(fields...): dictionary-coded; each distinct row value is a
    Python tuple (reference: spi/block/RowBlock). Field access via
    element_at(row, ordinal)."""

    fields: tuple = ()
    field_names: tuple = ()
    name: str = dataclasses.field(init=False, default="row")

    @property
    def device_dtype(self):
        return jnp.int32

    @property
    def is_dictionary_encoded(self) -> bool:
        return True

    def display(self) -> str:
        inner = ", ".join(f.display() for f in self.fields)
        return f"row({inner})"


@dataclasses.dataclass(frozen=True)
class HllStateType(SqlType):
    """Internal HyperLogLog accumulator state: a tuple-data Block of
    ops/hll.WORDS packed i64 register words per row (reference:
    spi/type/ HyperLogLogType carrying airlift-stats HLL slices; the
    TPU translation keeps registers as fixed-width columns so state
    pages stay pytrees)."""

    name: str = dataclasses.field(init=False, default="hyperloglog")

    @property
    def device_dtype(self):
        return jnp.int64  # per word

    @property
    def is_comparable(self) -> bool:
        return False

    @property
    def is_orderable(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class CollectStateType(SqlType):
    """Internal bounded-collection accumulator state (array_agg /
    map_agg / approx_percentile): Block data is a [cap, K] int64 slot
    matrix; a sibling BIGINT count column says how many slots each
    group uses (reference: operator/aggregation/ArrayAggregation-
    Function's grouped BlockBuilder state). Values encode into int64
    (doubles via the order-preserving arithmetic sign/exponent/mantissa
    pack in exec/executor._collect_encode — NO 64-bit bitcast, which
    an earlier TPU compile service cannot lower; dictionary-coded types by
    code, the dictionary riding the Block); K is the
    array_agg_max_elements session property."""

    element: SqlType = dataclasses.field(default_factory=UnknownType)
    K: int = 1024
    name: str = dataclasses.field(init=False, default="collect_state")

    @property
    def device_dtype(self):
        return jnp.int64

    @property
    def is_comparable(self) -> bool:
        return False

    @property
    def is_orderable(self) -> bool:
        return False

    def display(self) -> str:
        return f"collect_state({self.element.display()}, {self.K})"


# --- singletons (reference: static INSTANCE fields on each Type) ---------
BIGINT = BigintType()
INTEGER = IntegerType()
SMALLINT = SmallintType()
TINYINT = TinyintType()
DOUBLE = DoubleType()
REAL = RealType()
BOOLEAN = BooleanType()
DATE = DateType()
TIMESTAMP = TimestampType()
VARBINARY = VarbinaryType()
UNKNOWN = UnknownType()
VARCHAR = VarcharType()
INTERVAL_DAY_TIME = IntervalDayTimeType()
INTERVAL_YEAR_MONTH = IntervalYearMonthType()
HLL_STATE = HllStateType()

_INTEGRAL = (BigintType, IntegerType, SmallintType, TinyintType)
_FLOATING = (DoubleType, RealType)


def is_integral(t: SqlType) -> bool:
    return isinstance(t, _INTEGRAL)


def is_floating(t: SqlType) -> bool:
    return isinstance(t, _FLOATING)


def is_numeric(t: SqlType) -> bool:
    return is_integral(t) or is_floating(t) or isinstance(t, DecimalType)


def is_string(t: SqlType) -> bool:
    return isinstance(t, (VarcharType, CharType))


def parse_type(text: str) -> SqlType:
    """Parse a type name like ``decimal(12,2)`` or ``varchar`` into a SqlType.

    Reference: presto-main type/TypeRegistry.java parametric type resolution.
    """
    s = text.strip().lower()
    base, args = s, []
    if "(" in s:
        if not s.endswith(")"):
            raise ValueError(f"malformed type: {text!r}")
        base, rest = s.split("(", 1)
        base = base.strip()
        # split on top-level commas only (nested parametric types:
        # map(bigint, array(varchar)))
        args, depth, cur = [], 0, []
        for ch in rest[:-1]:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
            if ch == "," and depth == 0:
                args.append("".join(cur).strip())
                cur = []
            else:
                cur.append(ch)
        if cur and "".join(cur).strip():
            args.append("".join(cur).strip())
    simple = {
        "bigint": BIGINT,
        "integer": INTEGER,
        "int": INTEGER,
        "smallint": SMALLINT,
        "tinyint": TINYINT,
        "double": DOUBLE,
        "double precision": DOUBLE,
        "real": REAL,
        "float": REAL,
        "boolean": BOOLEAN,
        "date": DATE,
        "timestamp": TIMESTAMP,
        "varbinary": VARBINARY,
        "unknown": UNKNOWN,
        "hyperloglog": HLL_STATE,
        "interval day to second": INTERVAL_DAY_TIME,
        "interval year to month": INTERVAL_YEAR_MONTH,
    }
    if base in simple:
        if args:
            raise ValueError(f"type {base} takes no parameters: {text!r}")
        return simple[base]
    if base == "varchar":
        return VarcharType(int(args[0])) if args else VarcharType()
    if base == "char":
        return CharType(int(args[0])) if args else CharType(1)
    if base in ("decimal", "numeric"):
        if len(args) == 2:
            return DecimalType(int(args[0]), int(args[1]))
        if len(args) == 1:
            return DecimalType(int(args[0]), 0)
        return DecimalType(38, 0)
    if base == "array":
        return ArrayType(parse_type(args[0]) if args else UNKNOWN)
    if base == "map":
        return MapType(
            parse_type(args[0]) if args else UNKNOWN,
            parse_type(args[1]) if len(args) > 1 else UNKNOWN,
        )
    if base == "row":
        return RowType(tuple(parse_type(a) for a in args))
    if base in _PLUGIN_TYPES:
        if args:
            raise ValueError(f"type {base} takes no parameters: {text!r}")
        return _PLUGIN_TYPES[base]
    raise ValueError(f"unknown type: {text!r}")


# type plugin SPI (reference: spi/Plugin.getTypes + TypeRegistry.addType):
# plugins contribute named types that then resolve in CAST expressions
# and DDL like any builtin
_PLUGIN_TYPES: dict = {}


def register_type(name: str, t: SqlType) -> None:
    key = name.strip().lower()
    if key in _PLUGIN_TYPES and _PLUGIN_TYPES[key] != t:
        raise ValueError(f"type already registered: {name}")
    if key not in _PLUGIN_TYPES:
        try:
            parse_type(key)
        except ValueError:
            pass
        else:
            # parse_type resolves builtins first, so a shadowing
            # registration would be silently unreachable — reject it
            raise ValueError(f"type name shadows a builtin: {name}")
    _PLUGIN_TYPES[key] = t


def common_super_type(a: SqlType, b: SqlType) -> Optional[SqlType]:
    """Least common type two operands coerce to, or None.

    Reference: presto-main type/TypeCoercion / FunctionRegistry
    getCommonSuperType. Implements the numeric tower
    tinyint < smallint < integer < bigint < decimal < real < double and
    varchar/char widening.
    """
    if a == b:
        return a
    if isinstance(a, UnknownType):
        return b
    if isinstance(b, UnknownType):
        return a
    order = {TinyintType: 0, SmallintType: 1, IntegerType: 2, BigintType: 3}
    if type(a) in order and type(b) in order:
        return a if order[type(a)] >= order[type(b)] else b
    if is_numeric(a) and is_numeric(b):
        if isinstance(a, DoubleType) or isinstance(b, DoubleType):
            return DOUBLE
        if isinstance(a, RealType) or isinstance(b, RealType):
            # decimal + real -> real in Presto
            return REAL
        # at least one decimal; precision capped at 18 — computed decimals
        # are physically scaled i64 (see expr/functions._short_decimal)
        da = _to_decimal(a)
        db = _to_decimal(b)
        scale = max(da.scale, db.scale)
        int_digits = max(da.precision - da.scale, db.precision - db.scale)
        return DecimalType(max(min(18, int_digits + scale), scale, 1), scale)
    if is_string(a) and is_string(b):
        la = a.length
        lb = b.length
        if la is None or lb is None:
            return VarcharType()
        return VarcharType(max(la, lb))
    if isinstance(a, DateType) and isinstance(b, TimestampType):
        return TIMESTAMP
    if isinstance(a, TimestampType) and isinstance(b, DateType):
        return TIMESTAMP
    return None


def _to_decimal(t: SqlType) -> DecimalType:
    if isinstance(t, DecimalType):
        return t
    widths = {
        TinyintType: 3,
        SmallintType: 5,
        IntegerType: 10,
        BigintType: 19,
    }
    return DecimalType(widths[type(t)], 0)
