"""Connector SPI.

Reference: presto-spi spi/connector/* — ConnectorMetadata (schemas),
ConnectorSplitManager (splits), ConnectorPageSourceProvider (pages). The TPU
engine consumes the same three capabilities: describe tables, enumerate row
ranges ("splits"), and produce columnar Pages for a range. Splits are
(start_row, row_count) ranges so a table shards across a device mesh by
simple range partitioning (reference analog: ConnectorSplit streaming to
tasks via SourcePartitionedScheduler).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from presto_tpu import types as T
from presto_tpu.page import Page


@dataclasses.dataclass(frozen=True)
class ColumnSchema:
    name: str
    type: T.SqlType


@dataclasses.dataclass(frozen=True)
class TableSchema:
    name: str
    columns: Tuple[ColumnSchema, ...]

    def column_names(self) -> List[str]:
        return [c.name for c in self.columns]

    def column_index(self, name: str) -> int:
        for i, c in enumerate(self.columns):
            if c.name == name:
                return i
        raise KeyError(f"no column {name!r} in table {self.name!r}")

    def column_type(self, name: str) -> T.SqlType:
        return self.columns[self.column_index(name)].type


@dataclasses.dataclass(frozen=True)
class Split:
    """A row range of a table (reference: spi/ConnectorSplit)."""

    table: str
    start_row: int
    row_count: int


def _launch_on_sink(program, *args):
    """A connector's program called at THE launch point
    (exec/programs.py), counted on the executor that is running a
    query on this thread (none: called uncounted)."""
    from presto_tpu.exec import programs as PG
    from presto_tpu.exec import xfer as XF

    return PG.launch(XF.current_sink(), program, *args)


class GeneratorConnector:
    """Mixin for on-device deterministic generators (tpch/tpcds): column-
    pruned, jit-compiled chunk generation from the global row index.
    Subclasses provide ``_schemas`` (name -> TableSchema), ``_dicts``
    (table -> column -> Dictionary), a ``_gen_cache`` dict, and one
    ``_gen_<table>(start, n) -> _Lazy`` method per table."""

    def page_for_split(self, split: "Split",
                       columns: Optional[Sequence[str]] = None) -> Page:
        schema = self.table_schema(split.table)
        names = tuple(columns) if columns is not None else tuple(
            schema.column_names()
        )
        fn = self._compiled_gen(split.table, split.row_count, names)
        import jax.numpy as jnp

        datas, valid = fn(
            jnp.int64(split.start_row), jnp.int64(split.row_count)
        )
        dicts = self._dicts.get(split.table, {})
        blocks = []
        from presto_tpu.page import Block

        for nm, data in zip(names, datas):
            blocks.append(
                Block(
                    data=data,
                    type=schema.column_type(nm),
                    nulls=None,
                    dictionary=dicts.get(nm),
                )
            )
        return Page(blocks=tuple(blocks), valid=valid)

    def _compiled_gen(self, table: str, n: int, names: tuple):
        """jit-compiled, column-pruned chunk generator over the CANONICAL
        (ladder-bucketed, exec/shapes.py) chunk shape; start_row and the
        real row count are traced, so one compilation serves every chunk
        whose size lands in the same bucket — tail splits no longer mint
        a program shape per (scale factor, page_rows) combination.
        Generated rows past the real count mask out of `valid` (the
        generators are unbounded past the table end; the dist scan
        relies on the same property)."""
        import functools

        import jax.numpy as jnp

        from presto_tpu.exec import programs as PG
        from presto_tpu.exec import shapes as SH

        n_pad = SH.bucket(n)
        key = (table, n_pad, names)
        if key not in self._gen_cache:
            body = self.gen_body(table, n_pad, names)

            def padded(start, count, _body=body, _n=n_pad):
                datas, valid = _body(start)
                in_range = jnp.arange(_n, dtype=jnp.int64) < count
                return datas, valid & in_range

            self._gen_cache[key] = PG.Program("scan_gen", padded)
        return functools.partial(_launch_on_sink, self._gen_cache[key])

    def _lazy_rows(self, table: str, start, n: int):
        """The table's _Lazy over rows [start, start+n). Tables whose
        generation is elementwise in the row index expose
        ``_gen_<table>_at(idx)`` (any int64 index array); the contiguous
        form derives from it. Tables with slot structure (lineitem)
        keep a dedicated ``_gen_<table>(start, n)``."""
        at = getattr(self, f"_gen_{table}_at", None)
        if at is not None:
            import jax.numpy as jnp

            return at(start + jnp.arange(n, dtype=jnp.int64))
        return getattr(self, f"_gen_{table}")(start, n)

    def gen_body(self, table: str, n: int, names: tuple):
        """Traceable chunk generator (Connector.gen_body): pure function of
        the traced start row, safe inside jit or shard_map."""

        def fn(start):
            lazy = self._lazy_rows(table, start, n)
            return (
                tuple(lazy.get(nm) for nm in names),
                lazy.get("__valid__"),
            )

        return fn

    def gen_at(self, table: str, names: Tuple[str, ...]):
        """Traceable random-access generator (Connector.gen_at): pure
        function of an arbitrary int64 row-index array. Exists exactly
        for tables whose columns are elementwise in the row index
        (``_gen_<table>_at``); None otherwise."""
        at = getattr(self, f"_gen_{table}_at", None)
        if at is None:
            return None

        def fn(idx):
            lazy = at(idx)
            return (
                tuple(lazy.get(nm) for nm in names),
                lazy.get("__valid__"),
            )

        return fn

    def host_rows(self, table: str, target_rows: int = 1 << 20):
        """Materialize a table as Python row tuples (oracle loading)."""
        out = []
        for page in self.pages(table, target_rows=target_rows):
            out.extend(page.to_pylist())
        return out

    # ------------------------------------------------- predicate pushdown
    def monotonic_row_bound(self, table: str, column: str):
        """For a column that is non-decreasing in the row index, return
        f(v) = smallest row index whose value >= v (clamped to >= 0);
        None if the column is not monotonic. Lets prune_splits invert a
        value range into a row range — generator tables get TupleDomain
        pushdown for free on their key columns."""
        return None

    def prune_splits(self, table, splits, constraint):
        out = splits
        for col, lo, hi in constraint:
            f = self.monotonic_row_bound(table, col)
            if f is None:
                continue
            row_lo = max(f(lo), 0) if lo is not None else 0
            row_hi = max(f(hi + 1), 0) if hi is not None else None
            out = [
                s for s in out
                if s.start_row + s.row_count > row_lo
                and (row_hi is None or s.start_row < row_hi)
            ]
        return out


class Connector:
    """Reference: spi/connector/Connector + ConnectorMetadata."""

    name: str = "connector"

    def tables(self) -> List[str]:
        raise NotImplementedError

    def table_schema(self, table: str) -> TableSchema:
        raise NotImplementedError

    def row_count(self, table: str) -> int:
        raise NotImplementedError

    def unique_columns(self, table: str) -> frozenset:
        """Columns whose values are unique across the table (primary
        keys). Metadata the engine may exploit — e.g. the Pallas
        unique-key join fast path (reference analog: connector-provided
        table layouts/constraints consulted by the planner)."""
        return frozenset()

    def snapshot_version(self, table: str) -> Optional[str]:
        """Opaque token that changes whenever the table's CONTENT may
        have changed — the result cache (presto_tpu/cache/) folds it
        into every key, so a write makes stale cached results
        structurally unreachable (reference analog: connector-provided
        table versions consulted for materialized-view staleness).

        Default: derived from the row count, which is exact for the
        immutable deterministic generators (content is a pure function
        of (schema, scale), and scale moves the count). Writable
        connectors MUST override with a token that also moves on
        content-preserving-cardinality writes (UPDATE): the memory
        connector bumps an explicit write counter. Return None when
        staleness cannot be proven — scans of this table then never
        cache."""
        try:
            return f"rows:{self.row_count(table)}"
        except Exception:  # noqa: BLE001 - a connector without counts
            return None    # is simply uncacheable, never a query error

    def splits(self, table: str, target_rows: int) -> List[Split]:
        """Chop the table into row-range splits of ~target_rows each."""
        total = self.row_count(table)
        out = []
        start = 0
        while start < total:
            n = min(target_rows, total - start)
            out.append(Split(table, start, n))
            start += n
        return out or [Split(table, 0, 0)]

    def page_for_split(
        self, split: Split, columns: Optional[Sequence[str]] = None
    ) -> Page:
        raise NotImplementedError

    def prune_splits(
        self, table: str, splits: List[Split], constraint
    ) -> List[Split]:
        """Drop splits that provably contain no row satisfying the pushed
        constraint ((column, lo, hi) closed integer ranges — the
        TupleDomain analog, see exec/pushdown.py). Advisory: the engine
        re-applies the full predicate to surviving pages."""
        return splits

    def gen_body(self, table: str, n: int, names: Tuple[str, ...]):
        """Optional traceable chunk generator for SPMD scans: a pure
        function ``start_row -> (tuple of column arrays, valid mask)`` the
        distributed executor can call inside shard_map so each mesh device
        generates its own split on-device. Return None if the connector
        can only produce host pages (the executor then stages host data
        shard by shard).

        Contract (split-batched execution relies on it): the returned
        function must be traceable under jax.vmap and inside
        jax.lax.scan bodies — pure jnp elementwise in the traced start
        row, no host reads, no python control flow on start — so the
        executor can fold a whole batch of splits into one XLA program
        (exec/executor._fused_stream)."""
        return None

    def gen_batch(self, table: str, n: int, names: Tuple[str, ...]):
        """Optional traceable BATCHED chunk generator: a pure function
        ``starts[int64, B] -> (tuple of [B, n] column arrays,
        valid[B, n])`` generating one n-row chunk per start row in a
        single program — the generation half of split-batched
        execution (exec/executor._fused_stream stacks B splits into a
        [B, n] leading dim and vmaps the fused pipeline body over it).
        Default derives from gen_body via jax.vmap, which the gen_body
        traceability contract guarantees is valid; connectors with a
        cheaper closed batched form may override. None when gen_body
        is None."""
        body = self.gen_body(table, n, names)
        if body is None:
            return None
        import jax

        return jax.vmap(body)

    def gen_at(self, table: str, names: Tuple[str, ...]):
        """Optional traceable RANDOM-ACCESS generator: a pure function
        ``row_idx_array -> (tuple of column arrays, valid mask)`` that
        produces the named columns at arbitrary row indices (clipped to
        the table by the caller). With key_inverse this is what makes a
        join against this table build-free: the executor computes build
        row ids from probe keys arithmetically and GENERATES the carried
        columns at those ids — no hash table, no gathers (the reference's
        LookupJoinOperator collapses to pure compute). None if the table
        cannot be generated at scattered indices."""
        return None

    def key_inverse(self, table: str, column: str):
        """Optional traceable inverse of a unique key column: a pure
        function ``vals -> (row_idx int64 array, found bool array)``
        with the contract that for every value v present in the column,
        ``row_idx`` is the exact table row holding v and found is True;
        for any v not present found is False (row_idx may be anything —
        callers clip before generating). The closed-form analog of the
        reference's LookupSource for deterministic generator tables;
        None when no closed form exists (the engine then builds a real
        hash index)."""
        return None

    def key_window_inverse(self, table: str, column: str):
        """Optional traceable WINDOWED inverse: ``(fn, L)`` where
        ``fn(vals) -> (base_idx, found)`` and every table row whose
        column equals v lies in rows [base_idx, base_idx + L). For
        slot-structured fact tables (ticket/order-major layouts) this
        pins a join key to a small static candidate window; the engine
        resolves the exact row by generating the remaining key columns
        at each of the L candidates (exec/executor: windowed generated
        join). The (column,...) keys tested against the window must
        together be unique per table row. None when the column has no
        window structure."""
        return None

    def pages(
        self,
        table: str,
        columns: Optional[Sequence[str]] = None,
        target_rows: int = 1 << 20,
        constraint=None,
    ) -> Iterator[Page]:
        splits = self.splits(table, target_rows)
        if constraint:
            splits = self.prune_splits(table, splits, constraint)
        for split in splits:
            if split.row_count:
                yield self.page_for_split(split, columns)
