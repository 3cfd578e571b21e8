"""Hashing kernels: per-row hashes, hash combining, order-insensitive
checksums.

Reference:
  - presto-spi spi/type/AbstractLongType.java hashes a long with XxHash64;
  - presto-main operator/InterpretedHashGenerator.java combines channel hashes
    as ``h = h * 31 + channelHash`` (CombineHashFunction);
  - presto-verifier computes order-insensitive result checksums by summing
    row hashes.

We implement xxhash64 for single 8-byte values (bit-exact with the reference's
XxHash64.hash(long)) and use the same 31*h+x combiner, so row hashes and
checksums are comparable with a Java-side harness if one ever runs. All hash
math is uint64 with natural wraparound.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

# numpy scalars, NOT jnp: a module-level jnp constant is a device buffer
# that jit traces embed by reference, and on an earlier TPU runtime any
# executable with an embedded device-buffer constant permanently degrades
# every subsequent kernel launch (~56ms floor, measured). numpy scalars
# fold to HLO literals at trace time instead.
import numpy as _np

_P1 = _np.uint64(0x9E3779B185EBCA87)
_P2 = _np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = _np.uint64(0x165667B19E3779F9)
_P4 = _np.uint64(0x85EBCA77C2B2AE63)
_P5 = _np.uint64(0x27D4EB2F165667C5)


def _rotl(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return (x << jnp.uint64(r)) | (x >> jnp.uint64(64 - r))


_M64 = (1 << 64) - 1


def xxhash64_host(data: bytes, seed: int = 0) -> int:
    """Full xxhash64 over a byte string (host-side scalar; the scalar
    xxhash64() function's implementation — reference:
    io.airlift.slice.XxHash64.hash(Slice))."""
    p1, p2, p3, p4, p5 = (int(_P1), int(_P2), int(_P3), int(_P4),
                          int(_P5))

    def rot(x, r):
        return ((x << r) | (x >> (64 - r))) & _M64

    n = len(data)
    i = 0
    if n >= 32:
        v1 = (seed + p1 + p2) & _M64
        v2 = (seed + p2) & _M64
        v3 = seed & _M64
        v4 = (seed - p1) & _M64

        def rnd(acc, lane):
            acc = (acc + lane * p2) & _M64
            return (rot(acc, 31) * p1) & _M64

        while i + 32 <= n:
            v1 = rnd(v1, int.from_bytes(data[i:i + 8], "little"))
            v2 = rnd(v2, int.from_bytes(data[i + 8:i + 16], "little"))
            v3 = rnd(v3, int.from_bytes(data[i + 16:i + 24], "little"))
            v4 = rnd(v4, int.from_bytes(data[i + 24:i + 32], "little"))
            i += 32
        h = (rot(v1, 1) + rot(v2, 7) + rot(v3, 12) + rot(v4, 18)) & _M64

        def merge(h, v):
            h ^= rnd(0, v)
            return (h * p1 + p4) & _M64

        h = merge(h, v1)
        h = merge(h, v2)
        h = merge(h, v3)
        h = merge(h, v4)
    else:
        h = (seed + p5) & _M64
    h = (h + n) & _M64
    while i + 8 <= n:
        k = (int.from_bytes(data[i:i + 8], "little") * p2) & _M64
        k = (rot(k, 31) * p1) & _M64
        h = ((rot(h ^ k, 27) * p1) + p4) & _M64
        i += 8
    if i + 4 <= n:
        k = (int.from_bytes(data[i:i + 4], "little") * p1) & _M64
        h = ((rot(h ^ k, 23) * p2) + p3) & _M64
        i += 4
    while i < n:
        h = (rot(h ^ ((data[i] * p5) & _M64), 11) * p1) & _M64
        i += 1
    h ^= h >> 33
    h = (h * p2) & _M64
    h ^= h >> 29
    h = (h * p3) & _M64
    h ^= h >> 32
    return h


def xxhash64_u64(value: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """xxhash64 of a single 8-byte little-endian value (vectorized).

    Bit-exact with io.airlift.slice.XxHash64.hash(long) used by the
    reference's type hashes.
    """
    v = value.astype(jnp.uint64)
    acc = jnp.uint64(seed) + _P5 + jnp.uint64(8)
    k1 = v * _P2
    k1 = _rotl(k1, 31)
    k1 = k1 * _P1
    acc = acc ^ k1
    acc = _rotl(acc, 27) * _P1 + _P4
    # avalanche
    acc = acc ^ (acc >> jnp.uint64(33))
    acc = acc * _P2
    acc = acc ^ (acc >> jnp.uint64(29))
    acc = acc * _P3
    acc = acc ^ (acc >> jnp.uint64(32))
    return acc


def combine_hash(h: jnp.ndarray, next_hash: jnp.ndarray) -> jnp.ndarray:
    """Reference: operator/scalar/CombineHashFunction.java: h * 31 + next."""
    return h.astype(jnp.uint64) * jnp.uint64(31) + next_hash.astype(jnp.uint64)


def hash_columns(
    cols_u64: Sequence[jnp.ndarray],
    nulls: Sequence[Optional[jnp.ndarray]],
) -> jnp.ndarray:
    """Row hash over equality-encoded uint64 key columns.

    NULL hashes to 0 (reference: TypeUtils.hashPosition returns NULL_HASH_CODE
    = 0 for nulls).
    """
    h = jnp.zeros(cols_u64[0].shape, dtype=jnp.uint64)
    for col, null in zip(cols_u64, nulls):
        ch = xxhash64_u64(col)
        if null is not None:
            ch = jnp.where(null, jnp.uint64(0), ch)
        h = combine_hash(h, ch)
    return h


def checksum(row_hashes: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Order-insensitive checksum: wrapping uint64 sum of selected row hashes
    (reference: presto-verifier checksum queries)."""
    return jnp.sum(
        jnp.where(valid, row_hashes, jnp.uint64(0)), dtype=jnp.uint64
    )
