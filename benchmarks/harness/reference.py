"""The plain references, the answer cache and the comparison that
decides ``correct``.

A reference is independent of the engine's operators: it reads the rows
the connector generates (pulled to the host page by page, a different
path from the served one, which generates inside the fused step) and
reduces them with NumPy integer arithmetic or with sqlite. What it
cannot be independent of is the data itself: TPC-H fixes the rows, and
the only generator of *this* table is the connector's (PERF.md, Open
questions).

Each template has a file ``references/<template>.py`` of one of two
kinds:

``KIND = "columns"``: ``TABLE``, ``COLUMNS``, ``start(params, control)``,
``update(state, cols)``, ``finish(state, dictionaries)``. The harness
makes ONE pass over the table for every variant of every such template
on a catalog.

``KIND = "sqlite"``: ``TABLES`` (table -> columns), ``INDEXES``
("table(column)" strings, built once for every template) and
``oracle_sql(params, control)``. The harness loads the union of the
columns once per catalog.

``control=True`` is the control of "How correct is decided": the same
reference with one guarantee broken (float32 accumulation; a dropped
grace partition). It has to come out as not equal.

Answers are in the engine's encoding: decimals as unscaled ints, dates
as epoch days, strings as str.
"""

from __future__ import annotations

import datetime
import decimal
import hashlib
import inspect
import json
import os
import re
import sqlite3
import time
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from . import manifest

EPOCH = datetime.date(1970, 1, 1)
FLOAT_REL_TOL = 1e-9
_DECIMAL_RE = re.compile(r"decimal\((\d+),\s*(\d+)\)")
_FLOAT_TYPES = ("double", "real")
PASS_ROWS = 1 << 20   # rows per page pulled to the host


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def engine_encoding(columns: Sequence[Dict], rows: Iterable[Sequence]
                    ) -> List[tuple]:
    """Wire rows (decimals as strings, dates ISO) -> the engine's
    encoding, which the references answer in."""
    kinds = []
    for col in columns:
        m = _DECIMAL_RE.match(col["type"])
        kinds.append(int(m.group(2)) if m else col["type"])
    out = []
    for row in rows:
        vals = []
        for kind, v in zip(kinds, row):
            if v is None:
                vals.append(None)
            elif isinstance(kind, int):
                vals.append(int(decimal.Decimal(v).scaleb(kind)))
            elif kind == "date":
                vals.append(days(v))
            elif kind in _FLOAT_TYPES:
                vals.append(float(v))
            else:
                vals.append(v)
        out.append(tuple(vals))
    return out


def mismatch(got: Sequence[tuple], want: Sequence[Sequence]) -> str:
    """'' where the served rows equal the reference's, else what differs
    first. Ordered; exact on integers, unscaled decimals, dates and
    strings; FLOAT_REL_TOL relative on floats."""
    if len(got) != len(want):
        return f"{len(got)} rows served, reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: {len(g)} columns, reference has {len(w)}"
        for j, (gv, wv) in enumerate(zip(g, w)):
            if isinstance(wv, float) and gv is not None:
                if abs(float(gv) - wv) > FLOAT_REL_TOL * max(
                        1.0, abs(wv)):
                    return f"row {i} col {j}: {gv!r} != {wv!r}"
            elif isinstance(gv, float) or gv != wv:
                return f"row {i} col {j}: {gv!r} != {wv!r}"
    return ""


# ------------------------------------------------------------ data pass
def host_pages(conn, table: str, columns: Sequence[str]):
    """The connector's generated rows of ``columns``, page by page, as
    (arrays of the valid rows, dictionaries of the string columns)."""
    for split in conn.splits(table, target_rows=PASS_ROWS):
        page = conn.page_for_split(split, tuple(columns))
        valid = np.asarray(page.valid)
        cols, dicts = {}, {}
        for name, block in zip(columns, page.blocks):
            cols[name] = np.asarray(block.data)[valid]
            if block.dictionary is not None:
                dicts[name] = block.dictionary.values
        yield cols, dicts


def _columns_answers(conn, jobs: List[Tuple[str, object, Dict, bool]]
                     ) -> Dict[str, List[tuple]]:
    """One pass per table for every (key, module, params, control)."""
    out = {}
    by_table: Dict[str, list] = {}
    for job in jobs:
        by_table.setdefault(job[1].TABLE, []).append(job)
    for table, tjobs in by_table.items():
        columns = sorted({c for j in tjobs for c in j[1].COLUMNS})
        states = [j[1].start(j[2], j[3]) for j in tjobs]
        dicts = {}
        for cols, dicts in host_pages(conn, table, columns):
            cols = {k: v.astype(np.int64) for k, v in cols.items()}
            for (_k, mod, _p, _c), state in zip(tjobs, states):
                mod.update(state, cols)
        for (key, mod, _p, _c), state in zip(tjobs, states):
            out[key] = mod.finish(state, dicts)
    return out


def load_sqlite(conn, tables: Dict[str, Sequence[str]]
                ) -> sqlite3.Connection:
    """The named columns of the named tables in an in-memory sqlite,
    decimals as unscaled ints, dates as epoch days, strings decoded."""
    db = sqlite3.connect(":memory:")
    for table, columns in tables.items():
        columns = list(columns)
        db.execute(f"CREATE TABLE {table} ({', '.join(columns)})")
        marks = ", ".join("?" for _ in columns)
        for cols, dicts in host_pages(conn, table, columns):
            arrays = [
                dicts[c][cols[c]].tolist() if c in dicts
                else cols[c].tolist() for c in columns]
            db.executemany(
                f"INSERT INTO {table} VALUES ({marks})", zip(*arrays))
    db.commit()
    return db


def _sqlite_answers(conn, jobs) -> Dict[str, List[tuple]]:
    tables: Dict[str, set] = {}
    indexes = set()
    for _key, mod, _params, _control in jobs:
        indexes.update(mod.INDEXES)
        for table, columns in mod.TABLES.items():
            tables.setdefault(table, set()).update(columns)
    db = load_sqlite(conn, {t: sorted(c) for t, c in tables.items()})
    try:
        for i, index in enumerate(sorted(indexes)):
            db.execute(f"CREATE INDEX ix{i} ON {index}")
        return {key: [tuple(r) for r in db.execute(
                    mod.oracle_sql(params, control)).fetchall()]
                for key, mod, params, control in jobs}
    finally:
        db.close()


# ---------------------------------------------------------------- cache
def _cache_key(stmt, catalog_props: Dict, mod, control: bool) -> str:
    h = hashlib.sha256()
    for part in (stmt.sql, json.dumps(catalog_props, sort_keys=True),
                 inspect.getsource(mod),
                 inspect.getsource(inspect.getmodule(_cache_key)),
                 "control" if control else "exact"):
        h.update(part.encode())
        h.update(b"\0")
    return h.hexdigest()[:32]


def answers(statements, catalogs: Dict, catalog_props: Dict[str, Dict],
            cache_dir: str, control: bool = False, log=print
            ) -> Dict[str, List[tuple]]:
    """statement key -> reference rows, for every statement given.
    Answers found in ``cache_dir`` are read; the rest are computed in
    one pass per catalog and table, and written there."""
    os.makedirs(cache_dir, exist_ok=True)
    out: Dict[str, List[tuple]] = {}
    todo: Dict[str, list] = {}      # catalog -> jobs
    paths = {}
    for st in statements:
        mod = manifest.load_module("references", st.template)
        path = os.path.join(cache_dir, _cache_key(
            st, catalog_props[st.catalog], mod, control) + ".json")
        if os.path.exists(path):
            out[st.key] = [tuple(r) for r in
                           manifest.load_json(path)["rows"]]
        else:
            paths[st.key] = (path, st)
            todo.setdefault(st.catalog, []).append(
                (st.key, mod, st.params, control))
    for catalog, jobs in todo.items():
        t0 = time.perf_counter()
        conn = catalogs[catalog]
        got = {}
        for kind, fn in (("columns", _columns_answers),
                         ("sqlite", _sqlite_answers)):
            kjobs = [j for j in jobs if j[1].KIND == kind]
            if kjobs:
                got.update(fn(conn, kjobs))
        for key, rows in got.items():
            path, st = paths[key]
            tmp = path + ".writing"
            with open(tmp, "w") as f:
                json.dump({"statement": key, "sql": st.sql,
                           "catalog": catalog_props[st.catalog],
                           "control": control, "rows": rows}, f)
            os.replace(tmp, path)
            out[key] = rows
        log(phase="reference", catalog=catalog, control=control,
            statements=sorted(got), computed_s=time.perf_counter() - t0)
    return out
