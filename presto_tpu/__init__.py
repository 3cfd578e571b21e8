"""presto-tpu: a TPU-native distributed SQL query engine.

Built from scratch in JAX/XLA/Pallas with the capabilities of the reference
engine (skyahead/presto, a prestodb/presto fork): coordinator-planned SQL over
columnar operator pipelines compiled to XLA stage programs on a device mesh.

Architecture (TPU-first, not a port):
  - Columnar batches are fixed-capacity ``Page``s of ``Block``s registered as
    JAX pytrees (reference: presto-spi spi/Page.java, spi/block/*), with
    validity masks instead of dynamic row counts so every operator is a
    statically-shaped XLA program.
  - Expressions lower from a RowExpression-style IR straight to jax.jit
    (reference: presto-main sql/gen/ExpressionCompiler.java generates JVM
    bytecode; XLA is our bytecode).
  - Group-by/join/sort are vectorized array programs (segmented reductions,
    sort + searchsorted probes, lax.top_k) rather than pointer-chasing hash
    tables (reference: presto-main operator/GroupByHash.java, JoinHash).
  - Distribution is SPMD over a jax.sharding.Mesh: hash repartition is
    lax.all_to_all over ICI, broadcast joins are all_gather, final gathers are
    psum/gather (reference: HTTP shuffle via operator/ExchangeClient.java).

x64 note: SQL BIGINT/DOUBLE semantics require 64-bit; we enable jax x64 at
import. Hot paths downcast to i32/bf16 where value ranges allow.
"""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

from presto_tpu.types import (  # noqa: E402
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    REAL,
    SMALLINT,
    TINYINT,
    UNKNOWN,
    VARBINARY,
    CharType,
    DecimalType,
    SqlType,
    VarcharType,
)
from presto_tpu.page import Block, Dictionary, Page  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "BIGINT",
    "BOOLEAN",
    "DATE",
    "DOUBLE",
    "INTEGER",
    "REAL",
    "SMALLINT",
    "TINYINT",
    "UNKNOWN",
    "VARBINARY",
    "Block",
    "CharType",
    "DecimalType",
    "Dictionary",
    "Page",
    "SqlType",
    "VarcharType",
]
