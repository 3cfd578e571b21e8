"""Bytes a stored-table scan would have to read: rows times the stored
width of the touched columns. With the generated scan these bytes are
never read from HBM, so the roofline share built on them is what a chip
needs to stream the stored columns over the device time spent: a floor
for any stored-table implementation, not an efficiency of today's."""

from __future__ import annotations

import re
from typing import Sequence

_FIXED = {"bigint": 8, "integer": 4, "int": 4, "date": 4, "double": 8,
          "real": 4, "boolean": 1, "smallint": 2, "tinyint": 1}
_DECIMAL = re.compile(r"decimal\((\d+),\s*\d+\)")


def column_bytes(type_name: str) -> int:
    """Stored bytes per value of a column of this SQL type: strings are
    dictionary codes (4), decimals of up to 18 digits one int64 (8),
    longer ones two."""
    name = type_name.lower()
    if name in _FIXED:
        return _FIXED[name]
    if name.startswith(("varchar", "char")):
        return 4
    m = _DECIMAL.match(name)
    if m:
        return 8 if int(m.group(1)) <= 18 else 16
    raise KeyError(f"no stored width known for SQL type {type_name!r}")


def scan_bytes(conn, table: str, columns: Sequence[str]) -> int:
    schema = conn.table_schema(table)
    width = sum(column_bytes(str(schema.column_type(c))) for c in columns)
    return conn.row_count(table) * width
