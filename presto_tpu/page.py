"""Columnar Page/Block model as JAX pytrees.

Reference: presto-spi spi/Page.java (positionCount + Block[]) and
spi/block/* (LongArrayBlock, VariableWidthBlock, DictionaryBlock,
RunLengthEncodedBlock, ...). The reference moves variable-length Pages between
operators; XLA wants static shapes, so our Page is a **fixed-capacity** batch:

  - every Block array has length ``capacity`` (static, padded),
  - a per-page ``valid: bool[capacity]`` mask is the selection vector
    (reference analog: PageProcessor's selectedPositions),
  - per-block ``nulls: bool[capacity]`` masks SQL NULLs (True = null),
  - strings are DictionaryBlocks: int32 codes + a host-side Dictionary.

Filtering flips bits in ``valid``; physical row compaction happens only at
exchange/output boundaries (presto_tpu.ops.compact). This keeps every operator
a statically-shaped XLA program — the TPU translation of the reference's
"process a Page at a time" discipline.

Pages are registered pytrees: block data and masks are leaves (traced), types
and dictionaries are static aux data (hashable, drive jit specialization).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T


def _round_up(n: int, multiple: int = 8) -> int:
    return ((max(n, 1) + multiple - 1) // multiple) * multiple


class Dictionary:
    """Immutable host-side value dictionary for string/binary blocks.

    Reference: spi/block/DictionaryBlock.java keeps a Block of distinct values
    plus int positions; ours keeps a numpy object array of Python values and is
    hashable by content digest so it can ride in jit static aux data without
    recompiling per identical dictionary.
    """

    __slots__ = ("values", "_index", "_hash")

    def __init__(self, values: Sequence[Any]):
        vals = list(values)
        # element-wise fill: np.array(list_of_equal_length_tuples)
        # would build a 2-D object array, breaking decode/gather for
        # complex-typed (array/map/row tuple) values
        arr = np.empty(len(vals), dtype=object)
        for i, v in enumerate(vals):
            arr[i] = v
        self.values = arr
        self._index = {v: i for i, v in enumerate(vals)}
        self._hash = hash(tuple(vals))

    def __len__(self) -> int:
        return len(self.values)

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Dictionary)
            and self._hash == other._hash
            and len(self.values) == len(other.values)
            and all(a == b for a, b in zip(self.values, other.values))
        )

    def code_of(self, value: Any) -> int:
        """Code for value, or -1 if absent (-1 never matches any row code)."""
        return self._index.get(value, -1)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(codes.shape, dtype=object)
        in_range = (codes >= 0) & (codes < len(self.values))
        out[in_range] = self.values[codes[in_range]]
        out[~in_range] = None
        return out

    def sort_rank(self) -> np.ndarray:
        """rank[code] = position of that value in sorted order — makes code
        comparison order-correct for ORDER BY on dictionary columns."""
        order = np.argsort(self.values, kind="stable")
        rank = np.empty(len(self.values), dtype=np.int32)
        rank[order] = np.arange(len(self.values), dtype=np.int32)
        return rank

    def has_duplicate_values(self) -> bool:
        """Transform-produced dictionaries (substr/lower/...) may map many
        codes to one value; equality on raw codes is then wrong (see
        ops/keys.equality_encoding). Subclasses with unique-by-construction
        values override to False without materializing."""
        return len(self._index) < len(self.values)

    def __repr__(self) -> str:  # pragma: no cover
        head = ", ".join(repr(v) for v in self.values[:4])
        more = "..." if len(self.values) > 4 else ""
        return f"Dictionary([{head}{more}], n={len(self.values)})"


@dataclasses.dataclass
class Block:
    """One column of a Page.

    data: jnp array [capacity] (dtype per SqlType.device_dtype). For long
          decimals (p > 18), a tuple (hi, lo) of int64 arrays.
    nulls: optional bool[capacity], True = SQL NULL. None = no nulls.
    type: SqlType (static aux).
    dictionary: host Dictionary for string/binary types (static aux).
    """

    data: Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]
    type: T.SqlType
    nulls: Optional[jnp.ndarray] = None
    dictionary: Optional[Dictionary] = None

    @property
    def capacity(self) -> int:
        arr = self.data[0] if isinstance(self.data, tuple) else self.data
        return arr.shape[0]

    def nulls_or_false(self) -> jnp.ndarray:
        if self.nulls is None:
            return jnp.zeros((self.capacity,), dtype=jnp.bool_)
        return self.nulls

    def with_data(self, data, nulls="keep") -> "Block":
        return Block(
            data=data,
            type=self.type,
            nulls=self.nulls if nulls == "keep" else nulls,
            dictionary=self.dictionary,
        )

    def take(self, idx, extra_nulls=None) -> "Block":
        """Row-indirection gather: output row j = self row idx[j], with
        ``extra_nulls`` ORed over the gathered null mask.

        The Block-level primitive behind DictionaryBlock-style late
        materialization (exec/latemat.py defers carried join columns as
        row-id indirections and takes the values once, at the first
        value consumer) and ordinary row gathers (ops/compact.
        gather_rows). Callers clamp idx into range; masked-off rows may
        gather garbage that validity/null masks hide."""
        if isinstance(self.data, tuple):
            data = tuple(d[idx] for d in self.data)
        else:
            data = self.data[idx]
        nulls = self.nulls[idx] if self.nulls is not None else None
        if extra_nulls is not None:
            nulls = (
                extra_nulls if nulls is None else (nulls | extra_nulls)
            )
        return Block(
            data=data, type=self.type, nulls=nulls,
            dictionary=self.dictionary,
        )

    def tree_flatten(self):
        children = (self.data, self.nulls)
        aux = (self.type, self.dictionary, self.nulls is None)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        typ, dictionary, _nulls_absent = aux
        data, nulls = children
        return cls(data=data, type=typ, nulls=nulls, dictionary=dictionary)


jax.tree_util.register_pytree_node(
    Block, Block.tree_flatten, Block.tree_unflatten
)


@dataclasses.dataclass
class Page:
    """A columnar batch: blocks + selection mask.

    Reference: spi/Page.java — but positionCount becomes (capacity, valid[]).
    """

    blocks: Tuple[Block, ...]
    valid: jnp.ndarray  # bool[capacity]
    # the count of selected rows as the program that made this page
    # computed it in the same launch (exec/executor._returning_rows): a
    # device int32 array whose SUM is the count (one entry a chip for a
    # page sharded over a mesh), or None. Not a leaf of the pytree, so
    # it never enters a program, and every page derived from this one
    # (with_valid, with_blocks, ...) starts without it.
    rows: Optional[jnp.ndarray] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]

    @property
    def channel_count(self) -> int:
        return len(self.blocks)

    def num_rows(self) -> jnp.ndarray:
        """Traced count of selected rows (reference: getPositionCount)."""
        return jnp.sum(self.valid.astype(jnp.int64))

    def block(self, channel: int) -> Block:
        return self.blocks[channel]

    def with_valid(self, valid: jnp.ndarray) -> "Page":
        return Page(blocks=self.blocks, valid=valid)

    def with_blocks(self, blocks: Sequence[Block]) -> "Page":
        return Page(blocks=tuple(blocks), valid=self.valid)

    def select_channels(self, channels: Sequence[int]) -> "Page":
        return Page(
            blocks=tuple(self.blocks[c] for c in channels), valid=self.valid
        )

    def append_blocks(self, blocks: Sequence[Block]) -> "Page":
        return Page(blocks=self.blocks + tuple(blocks), valid=self.valid)

    def tree_flatten(self):
        return (self.blocks, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        blocks, valid = children
        return cls(blocks=tuple(blocks), valid=valid)

    # ---------------------------------------------------------------- host IO
    @staticmethod
    def from_arrays(
        columns: Sequence[Any],
        types: Sequence[T.SqlType],
        *,
        capacity: Optional[int] = None,
        dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
    ) -> "Page":
        """Build a Page from host data (numpy arrays or Python lists; None =
        NULL). String columns are dictionary-encoded here (ingest boundary —
        reference analog: connector PageSource building Blocks)."""
        if not columns:
            raise ValueError("page needs at least one column")
        n = len(columns[0])
        cap = capacity or _round_up(n)
        dictionaries = dictionaries or [None] * len(columns)
        blocks: List[Block] = []
        for col, typ, dic in zip(columns, types, dictionaries):
            blocks.append(_encode_column(col, typ, cap, dic))
        valid = np.zeros(cap, dtype=bool)
        valid[:n] = True
        # metered h2d boundary (exec/xfer.py, lazy import like
        # to_pylist): page construction from host values is a real
        # device staging the transfer ledger must see
        from presto_tpu.exec import xfer as XF

        return Page(blocks=tuple(blocks),
                    valid=XF.to_device(valid, label="page-build"))

    def to_pylist(self) -> List[tuple]:
        """Materialize selected rows as Python tuples (test/client boundary).

        Reference analog: testing/MaterializedResult.
        """
        # metered d2h boundary (exec/xfer.py; imported lazily — page
        # loads before the exec package during engine import)
        from presto_tpu.exec import xfer as XF

        valid = XF.np_host(self.valid, label="decode-valid")
        rows_idx = np.nonzero(valid)[0]
        cols = []
        for blk in self.blocks:
            cols.append(_decode_block(blk, rows_idx))
        return [tuple(col[i] for col in cols) for i in range(len(rows_idx))]


jax.tree_util.register_pytree_node(Page, Page.tree_flatten, Page.tree_unflatten)


def _encode_column(
    col: Any,
    typ: T.SqlType,
    cap: int,
    dictionary: Optional[Dictionary],
) -> Block:
    vals = list(col) if not isinstance(col, np.ndarray) else col.tolist()
    n = len(vals)
    if n > cap:
        raise ValueError(f"column length {n} exceeds capacity {cap}")
    null_mask = np.array([v is None for v in vals] + [True] * (cap - n))
    has_nulls = bool(null_mask[:n].any())

    if typ.is_dictionary_encoded:
        if dictionary is None:
            distinct = sorted({v for v in vals if v is not None})
            dictionary = Dictionary(distinct)
        codes = np.zeros(cap, dtype=np.int32)
        for i, v in enumerate(vals):
            if v is None:
                continue
            code = dictionary.code_of(v)
            if code < 0:
                raise ValueError(
                    f"value {v!r} not in supplied dictionary"
                )
            codes[i] = code
    # metered h2d boundary (exec/xfer.py): every encoded column stages
    # host values onto the device — the ingest crossing the transfer
    # ledger must see (lazy import; page loads before the exec package)
    from presto_tpu.exec import xfer as XF

    if typ.is_dictionary_encoded:
        return Block(
            data=XF.to_device(codes, label="page-build"),
            type=typ,
            nulls=(XF.to_device(null_mask, label="page-build")
                   if has_nulls else None),
            dictionary=dictionary,
        )

    if isinstance(typ, T.DecimalType) and not typ.is_short:
        hi = np.zeros(cap, dtype=np.int64)
        lo = np.zeros(cap, dtype=np.int64)
        for i, v in enumerate(vals):
            if v is None:
                continue
            u = int(v) & ((1 << 128) - 1)
            lo[i] = np.int64((u & ((1 << 64) - 1)) - (1 << 64) if (u >> 63) & 1 else u & ((1 << 64) - 1))
            hi[i] = np.int64((int(v) >> 64))
        return Block(
            data=(XF.to_device(hi, label="page-build"),
                  XF.to_device(lo, label="page-build")),
            type=typ,
            nulls=(XF.to_device(null_mask, label="page-build")
                   if has_nulls else None),
        )

    np_dtype = typ.numpy_dtype
    arr = np.zeros(cap, dtype=np_dtype)
    for i, v in enumerate(vals):
        if v is not None:
            arr[i] = v
    return Block(
        data=XF.to_device(arr, label="page-build"),
        type=typ,
        nulls=(XF.to_device(null_mask, label="page-build")
               if has_nulls else None),
    )


def _collect_elem_decoder(elem_t, dictionary):
    """int64-encoded collect-state slot -> Python element value
    (inverse of exec/executor._collect_encode)."""
    if dictionary is not None:
        values = dictionary.values

        def dec(v):
            return values[int(np.clip(v, 0, len(values) - 1))]
        return dec
    if isinstance(elem_t, (T.DoubleType, T.RealType)):
        import math

        def dec_float(v):
            v = int(v)
            if v == 0:
                return 0.0
            mag = abs(v)
            e = (mag >> 52) - 1100
            frac = mag & ((1 << 52) - 1)
            out = math.ldexp(0.5 + frac * 2.0**-53, e + 1)
            return -out if v < 0 else out
        return dec_float
    if isinstance(elem_t, T.BooleanType):
        return lambda v: bool(v)
    return lambda v: int(v)


def _decode_block(blk: Block, rows_idx: np.ndarray) -> list:
    from presto_tpu.exec import xfer as XF

    nulls = XF.np_host(blk.nulls) if blk.nulls is not None else None
    if (isinstance(blk.type, (T.ArrayType, T.MapType))
            and isinstance(blk.data, tuple)):
        # collect-state result: (vals2d, elem-null-flags2d, counts) for
        # array_agg; (k2d, v2d, value-null-flags2d, counts) for map_agg
        *mats, counts = blk.data
        mats = [XF.np_host(m)[rows_idx] for m in mats]
        counts = XF.np_host(counts)[rows_idx]
        if isinstance(blk.type, T.ArrayType):
            dec = _collect_elem_decoder(blk.type.element, blk.dictionary)
            vals = [
                tuple(
                    None if nf else dec(v)
                    for v, nf in zip(mats[0][i, : int(c)],
                                     mats[1][i, : int(c)])
                )
                for i, c in enumerate(counts)
            ]
        else:
            kdec = _collect_elem_decoder(blk.type.key, blk.dictionary)
            vdec = _collect_elem_decoder(blk.type.value, None)
            vals = [
                tuple(
                    (kdec(k), None if nf else vdec(v))
                    for k, v, nf in zip(mats[0][i, : int(c)],
                                        mats[1][i, : int(c)],
                                        mats[2][i, : int(c)])
                )
                for i, c in enumerate(counts)
            ]
    elif isinstance(blk.data, tuple):
        hi = XF.np_host(blk.data[0])[rows_idx].astype(object)
        lo = XF.np_host(blk.data[1])[rows_idx].astype(object)
        vals = [(int(h) << 64) | (int(l) & ((1 << 64) - 1)) for h, l in zip(hi, lo)]
    elif blk.dictionary is not None:
        codes = XF.np_host(blk.data)[rows_idx]
        vals = list(blk.dictionary.decode(codes))
    else:
        arr = XF.np_host(blk.data)[rows_idx]
        if arr.dtype == np.bool_:
            vals = [bool(v) for v in arr]
        elif np.issubdtype(arr.dtype, np.integer):
            vals = [int(v) for v in arr]
        else:
            vals = [float(v) for v in arr]
    if nulls is not None:
        sel = nulls[rows_idx]
        vals = [None if is_null else v for v, is_null in zip(vals, sel)]
    return vals
