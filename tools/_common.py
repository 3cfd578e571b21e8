"""Shared setup for the bench tools: one place for the sys.path hack,
the persistent compile cache, and session-property application (mirrors
LocalRunner.execute's session->executor wiring so a tool driving the
executor directly behaves like the engine would)."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def configure_jax():
    import jax

    from presto_tpu import compilecache

    # min_compile_secs=0: cache EVERY program — retry-ladder rungs and
    # small per-page kernels matter as much as the big fused programs.
    # compilecache decides the directory (JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    compilecache.enable_persistent_cache()
    return jax


def make_runner(suite: str, sf: float, props=(), cached: bool = False):
    """LocalRunner over the named generator suite with k=v session
    properties applied to both the session and the live executor.
    cached=True wraps the connector in the device-resident page cache
    (scan = HBM read after the first streaming, the memory-connector
    analog) for generate-vs-query attribution."""
    from presto_tpu.connectors.cached import CachingConnector
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runner import LocalRunner

    cls = TpchConnector if suite == "tpch" else TpcdsConnector
    conn = cls(scale=sf)
    if cached:
        conn = CachingConnector(conn)
    runner = LocalRunner({suite: conn}, default_catalog=suite)
    for kv in props:
        k, v = kv.split("=", 1)
        runner.session.set(k, v)
    # session -> executor for direct executor drivers (bisect_rung
    # times ex.pages without execute())
    runner.apply_session()
    return runner


def queries(suite: str):
    if suite == "tpch":
        from tests.tpch_queries import QUERIES

        return QUERIES
    from tests.tpcds_queries import QUERIES

    return QUERIES
