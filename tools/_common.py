"""Shared setup for the tools that plan statements in process: a
runner with session properties applied (mirrors LocalRunner.execute's
session->executor wiring so a tool driving the executor directly
behaves like the engine would) and the test corpora's statements."""


def make_runner(suite: str, sf: float, props=()):
    """LocalRunner over the named generator suite with k=v session
    properties applied to both the session and the live executor."""
    from presto_tpu.connectors.tpcds import TpcdsConnector
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runner import LocalRunner

    cls = TpchConnector if suite == "tpch" else TpcdsConnector
    runner = LocalRunner({suite: cls(scale=sf)}, default_catalog=suite)
    for kv in props:
        k, v = kv.split("=", 1)
        runner.session.set(k, v)
    runner.apply_session()
    return runner


def queries(suite: str):
    if suite == "tpch":
        from tests.tpch_queries import QUERIES

        return QUERIES
    from tests.tpcds_queries import QUERIES

    return QUERIES
