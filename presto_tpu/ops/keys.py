"""Key encodings: map typed Blocks to uint64 arrays for equality (join /
group-by) and total order (sort / merge).

Reference analog: the reference compares typed values through Type
equalTo/compareTo per position (spi/type/*); on TPU we precompute branch-free
uint64 encodings once per page and then every comparison is integer compare.

Equality encoding: values are equal iff encodings are equal (plus null flags).
Order encoding: encoding order == SQL ascending order for non-null values:
  - signed ints: flip sign bit  (x ^ 0x8000...),
  - floats: IEEE-754 total order trick (flip all bits if negative, else set
    sign bit); -0.0 normalized to +0.0 first so -0.0 == 0.0 (SQL equality);
    NaN sorts above +inf which matches the engine's NaN-is-largest rule,
  - dictionary codes: order via Dictionary.sort_rank (host, static), equality
    via raw codes,
  - booleans: 0/1.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.page import Block

# numpy scalar, not jnp: module-level device buffers embedded as jit
# constants permanently degrade an earlier TPU runtime (see ops/hashing.py)
_SIGN64 = np.uint64(0x8000000000000000)


def _int_order_u64(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64


def _float_order_u64(x: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 total-order u64 key for DOUBLE/REAL values.

    Backend-split, because the TPU backend (a) rejects f64<->u64 bitcasts at
    compile time and (b) *represents* f64 as an (hi, lo) pair of f32s — f32
    exponent range, ~49-bit mantissa; hi = RN32(x), lo = RN32(x - hi), and
    hi + lo reconstructs every storable value exactly (verified on an
    earlier TPU runtime). On TPU the faithful order key is therefore the
    pair key (order32(hi) << 32) | order32(lo): hi is monotone in x, and lo
    breaks ties exactly. On CPU (true f64) we keep the classic bitcast trick.
    Both: -0.0 normalized to +0.0, NaN sorts above +inf (engine's
    NaN-is-largest rule).
    """
    import jax
    import jax.lax as lax

    x64 = x.astype(jnp.float64)
    x64 = jnp.where(x64 == 0.0, 0.0, x64)  # -0.0 -> +0.0
    isnan = jnp.isnan(x64)
    if jax.default_backend() != "tpu":
        bits = lax.bitcast_convert_type(x64, jnp.uint64)
        neg = (bits & _SIGN64) != 0
        out = jnp.where(neg, ~bits, bits | _SIGN64)
        return jnp.where(isnan, jnp.uint64(0xFFFFFFFFFFFFFFFF), out)

    sign32 = jnp.uint32(0x80000000)

    def order32(f):
        f = jnp.where(f == 0.0, jnp.float32(0.0), f)  # -0.0f -> +0.0f
        bits = lax.bitcast_convert_type(f.astype(jnp.float32), jnp.uint32)
        neg = (bits & sign32) != 0
        return jnp.where(neg, ~bits, bits | sign32)

    hi = x64.astype(jnp.float32)
    resid = jnp.where(
        jnp.isfinite(hi), x64 - hi.astype(jnp.float64), 0.0
    )
    lo = resid.astype(jnp.float32)
    key = (order32(hi).astype(jnp.uint64) << 32) | order32(lo).astype(
        jnp.uint64
    )
    return jnp.where(isnan, jnp.uint64(0xFFFFFFFFFFFFFFFF), key)


def equality_encoding(block: Block) -> List[jnp.ndarray]:
    """uint64 array(s) such that rows are SQL-equal iff encodings equal.

    For floats we use the order encoding (normalizes -0.0; NaN==NaN under this
    encoding, documented divergence: SQL `=` on NaN is false, but GROUP BY /
    join on NaN grouping-equal matches the reference's distinct-value
    semantics, which treat NaN as one value).

    Dictionary columns canonicalize codes by *value* through a static host
    lut — dictionaries produced by string transforms (substr/lower/...)
    carry duplicate values, so raw codes are not equality-faithful.
    """
    t = block.type
    if isinstance(block.data, tuple):  # long decimal limbs
        hi, lo = block.data
        return [hi.astype(jnp.uint64), lo.astype(jnp.uint64)]
    if isinstance(t, (T.DoubleType, T.RealType)):
        return [_float_order_u64(block.data)]
    if isinstance(t, T.BooleanType):
        return [block.data.astype(jnp.uint64)]
    if (
        block.dictionary is not None
        and len(block.dictionary)
        and block.dictionary.has_duplicate_values()
    ):
        import numpy as np

        values = block.dictionary.values
        first: dict = {}
        lut = np.empty(len(values), dtype=np.uint64)
        for i, v in enumerate(values):
            lut[i] = first.setdefault(v, i)
        codes = jnp.clip(block.data, 0, len(values) - 1)
        return [jnp.asarray(lut)[codes]]
    return [block.data.astype(jnp.int64).astype(jnp.uint64)]


def order_encoding_parts(
    block: Block,
    *,
    ascending: bool = True,
    nulls_first: bool = False,
) -> List[Tuple[jnp.ndarray, int]]:
    """order_encoding with static bit widths: (u64 key, bits) pairs whose
    MSB-first concatenation orders rows correctly.

    Bit widths come from static knowledge — dictionary size, or the type's
    value range (DATE fits 24 bits, INTEGER 32, ...). Narrow widths let
    pack_sort_keys() fuse several sort keys into one u64 word, which matters
    enormously on TPU: XLA's sort compile time roughly doubles per extra
    operand, so a 5-operand lexsort is minutes while a packed 1-2 operand
    sort is seconds.
    """
    t = block.type
    parts: List[Tuple[jnp.ndarray, int]] = []
    if isinstance(block.data, tuple):  # long decimal limbs
        hi, lo = block.data
        parts = [(_int_order_u64(hi), 64), (lo.astype(jnp.uint64), 64)]
    elif isinstance(t, (T.DoubleType, T.RealType)):
        parts = [(_float_order_u64(block.data), 64)]
    elif isinstance(t, T.BooleanType):
        parts = [(block.data.astype(jnp.uint64), 1)]
    elif t.is_dictionary_encoded and block.dictionary is not None:
        if len(block.dictionary) == 0:
            parts = [(jnp.zeros(block.data.shape, dtype=jnp.uint64), 1)]
        else:
            rank = jnp.asarray(block.dictionary.sort_rank())
            codes = jnp.clip(block.data, 0, len(block.dictionary) - 1)
            bits = max(1, (len(block.dictionary) - 1).bit_length())
            parts = [(rank[codes].astype(jnp.uint64), bits)]
    else:
        bits = 64
        if isinstance(t, T.DateType):
            bits = 24  # Presto DATE range (years 1582..9999) < 2^23 days
        elif isinstance(t, T.IntegerType):
            bits = 32
        elif isinstance(t, T.SmallintType):
            bits = 16
        elif isinstance(t, T.TinyintType):
            bits = 8
        x = block.data.astype(jnp.int64)
        if bits == 64:
            enc = x.astype(jnp.uint64) ^ _SIGN64
        else:
            lo_b, hi_b = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
            enc = (
                jnp.clip(x, lo_b, hi_b) + jnp.int64(1 << (bits - 1))
            ).astype(jnp.uint64)
        parts = [(enc, bits)]

    if not ascending:
        parts = [
            ((~k if b == 64 else (jnp.uint64((1 << b) - 1) - k)), b)
            for k, b in parts
        ]

    null = block.nulls
    if null is None:
        null_key = jnp.zeros(parts[0][0].shape, dtype=jnp.uint64)
    elif nulls_first:
        null_key = jnp.where(null, jnp.uint64(0), jnp.uint64(1))
    else:
        null_key = jnp.where(null, jnp.uint64(1), jnp.uint64(0))
    return [(null_key, 1)] + parts


def pack_sort_keys(
    parts: List[Tuple[jnp.ndarray, int]]
) -> List[jnp.ndarray]:
    """Greedily pack (key, bits) pairs MSB-first into u64 words. Lexicographic
    order of the packed words equals lexicographic order of the unpacked key
    sequence (same static layout for every row)."""
    words: List[jnp.ndarray] = []
    acc = None
    used = 0
    for key, bits in parts:
        if acc is not None and used + bits > 64:
            words.append(acc)
            acc, used = None, 0
        if acc is None:
            acc = key.astype(jnp.uint64)
            used = bits
        else:
            acc = (acc << jnp.uint64(bits)) | key.astype(jnp.uint64)
            used += bits
    if acc is not None:
        words.append(acc)
    return words


def block_key_columns(
    blocks,
) -> Tuple[List[jnp.ndarray], List[Optional[jnp.ndarray]]]:
    """Equality encodings + null masks for a list of key Blocks (flattened:
    a long-decimal key contributes two uint64 columns sharing one null)."""
    cols: List[jnp.ndarray] = []
    nulls: List[Optional[jnp.ndarray]] = []
    for b in blocks:
        enc = equality_encoding(b)
        cols.extend(enc)
        nulls.extend([b.nulls] * len(enc))
    return cols, nulls
