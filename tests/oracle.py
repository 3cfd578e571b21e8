"""sqlite3-based SQL oracle for engine correctness tests.

Reference test pattern: presto-tests tests/H2QueryRunner — TPC-H correctness
suites compare engine output against an embedded relational database over
the same data. We load the deterministic TPC-H pages into sqlite with
engine-internal encodings (decimals as unscaled ints, dates as epoch days)
so integer math is exact on both sides.
"""

import hashlib
import os
import sqlite3
import tempfile
from typing import Dict, Iterable, List, Optional

from presto_tpu import types as T
from presto_tpu.connectors.base import Connector

# Disk cache for loaded oracle databases: decoding the deterministic
# generator pages into sqlite is pure (connector class, scale, tables)
# — and slow enough that the bench oracle phase never finished inside
# its 240s reserve. Loaded DBs persist as sqlite
# files keyed by the load's content fingerprint; cache hits open the
# file READ-ONLY (uri mode=ro), so a test that tried to mutate a
# shared oracle fails loudly instead of poisoning later runs.
# Point PRESTO_TPU_ORACLE_CACHE_DIR elsewhere, or at "" to disable.
_CACHE_DIR = os.environ.get(
    "PRESTO_TPU_ORACLE_CACHE_DIR", "/tmp/presto_tpu_oracle_cache"
)


def _sqlite_type(t: T.SqlType) -> str:
    if T.is_string(t):
        return "TEXT"
    if T.is_floating(t):
        return "REAL"
    return "INTEGER"


def _cache_key(connector, tables, target_rows: int) -> Optional[str]:
    """Content fingerprint of one oracle load, or None when the load
    is not cacheable. Only the bare deterministic generator connectors
    cache: wrappers (split filtering, caching, memory tables) produce
    host_rows that depend on wrapper state the key cannot see."""
    if not _CACHE_DIR:
        return None
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.connectors.tpch import TpchConnector

    if type(connector) not in (TpchConnector, TpcdsConnector):
        return None
    h = hashlib.sha1()
    h.update(type(connector).__name__.encode())
    h.update(repr(getattr(connector, "scale", None)).encode())
    h.update(repr(int(target_rows)).encode())
    for table in tables:
        schema = connector.table_schema(table)
        h.update(table.encode())
        h.update(repr(
            [(c.name, str(c.type)) for c in schema.columns]
        ).encode())
        # row_count rides in the key so a generator change that moves
        # cardinality invalidates; value changes at equal cardinality
        # need a cache wipe (the dir is /tmp — cheap and explicit)
        h.update(repr(connector.row_count(table)).encode())
    return h.hexdigest()


def load_sqlite(
    connector: Connector,
    tables: Iterable[str],
    target_rows: int = 1 << 20,
) -> sqlite3.Connection:
    tables = list(tables)
    key = _cache_key(connector, tables, target_rows)
    path = os.path.join(_CACHE_DIR, f"oracle_{key}.db") if key else None
    if path and os.path.exists(path):
        return sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    if path:
        os.makedirs(_CACHE_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=_CACHE_DIR, suffix=".db.building"
        )
        os.close(fd)
        db = sqlite3.connect(tmp)
    else:
        tmp = None
        db = sqlite3.connect(":memory:")
    try:
        for table in tables:
            schema = connector.table_schema(table)
            cols = ", ".join(
                f"{c.name} {_sqlite_type(c.type)}"
                for c in schema.columns
            )
            db.execute(f"CREATE TABLE {table} ({cols})")
            placeholders = ", ".join("?" for _ in schema.columns)
            rows = connector.host_rows(table, target_rows=target_rows)
            db.executemany(
                f"INSERT INTO {table} VALUES ({placeholders})", rows
            )
        db.commit()
    except BaseException:
        # the load is the slow phase — an interrupted build must not
        # orphan a partial .db.building file in the shared cache dir
        if tmp is not None:
            db.close()
            try:
                os.unlink(tmp)
            except OSError:
                pass
        raise
    if tmp is not None:
        # atomic publish: concurrent pytest processes building the
        # same key race harmlessly (last rename wins, both complete)
        db.close()
        os.replace(tmp, path)
        return sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    return db


def rows_match(engine_rows: List[tuple], oracle_rows: List[tuple],
               float_cols: Optional[set] = None, tol: float = 1e-9) -> None:
    """Order-sensitive row comparison with exact ints and tolerant floats."""
    assert len(engine_rows) == len(oracle_rows), (
        f"row count mismatch: engine {len(engine_rows)} vs oracle "
        f"{len(oracle_rows)}\nengine head: {engine_rows[:3]}\n"
        f"oracle head: {oracle_rows[:3]}"
    )
    float_cols = float_cols or set()
    for i, (er, orow) in enumerate(zip(engine_rows, oracle_rows)):
        assert len(er) == len(orow), f"row {i} arity mismatch"
        for j, (ev, ov) in enumerate(zip(er, orow)):
            if j in float_cols and ev is not None and ov is not None:
                assert abs(float(ev) - float(ov)) <= tol * max(
                    1.0, abs(float(ov))
                ), f"row {i} col {j}: {ev} != {ov}"
            else:
                assert ev == ov, f"row {i} col {j}: {ev!r} != {ov!r}"
