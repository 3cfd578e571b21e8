"""A chain without a riding stored join runs the programs it ran before
ISSUE 45, to the lowered text.

``Executor._ride_stored_joins`` reads a fused chain's links and moves
a stored join into the build that carries its key. A chain that holds
no such pair of links takes no new branch: Q3 / Q5 over the generated
catalog (generated joins), Q1 / Q6 over stored ``lineitem`` (no join),
and a star of stored joins (every join keyed by the probe table's own
columns) hand their launches the arguments and lower to the text they
did at PR 44. ``data/chain_programs_pr44.json`` holds that tree's
digests: {case: [[label, sha256 of the lowered text], ...]} for every
program a statement launches, in launch order, under both drivers of
the fused scan.

A later PR that means to change one of these programs writes the file
anew from its own tree and says so (``python
tests/test_chain_programs_pinned.py`` from the root of a checkout):
the test then holds the next PR to that."""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks.harness import manifest  # noqa: E402
from presto_tpu import config  # noqa: E402
from presto_tpu.connectors.cached import ResidentConnector  # noqa: E402
from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.exec import programs as PG  # noqa: E402
from presto_tpu.runner import LocalRunner  # noqa: E402

PINNED = os.path.join(HERE, "data", "chain_programs_pr44.json")
SF = 0.01
PAGE_ROWS = 16384
DRIVERS = {"one_split": "auto", "batched": 4}
_SQL = {st.key: st.sql
        for cell in ("scan_sf10_solo", "join_sf1_solo")
        for st in manifest.load_cell(cell).every}
STAR = ("select count(*), sum(l_extendedprice), sum(o_totalprice), "
        "sum(s_acctbal) from lineitem, orders, supplier "
        "where l_orderkey = o_orderkey and l_suppkey = s_suppkey")


def _generated():
    return TpchConnector(SF)


def _stored_lineitem():
    return ResidentConnector(TpchConnector(SF), tables=["lineitem"])


def _stored_schema():
    return config._builtin_factories()["resident"]({
        "resident.inner": "tpch", "tpch.scale-factor": str(SF),
        "resident.tables": "*"})


# case -> (catalog, sql, the label its chain's program must carry)
CASES = {
    "generated/q3": (_generated, _SQL["q3_sf1#0"], "fused"),
    "generated/q5": (_generated, _SQL["q5_sf1#0"], "fused"),
    "stored_lineitem/q1": (_stored_lineitem, _SQL["q1_sf10#0"], "stored"),
    "stored_lineitem/q6": (_stored_lineitem, _SQL["q6_sf10#0"], "stored"),
    "stored_schema/star": (_stored_schema, STAR, "stored_probe"),
}
KEYS = sorted(f"{case}/{driver}" for case in CASES for driver in DRIVERS)


def lowered(key: str):
    """[label, digest of the lowered text] of every program the case's
    statement launches, at each program's first launch, in order."""
    case, driver = key.rsplit("/", 1)
    make, sql, _label = CASES[case]
    runner = LocalRunner({"tpch": make()}, default_catalog="tpch",
                         page_rows=PAGE_ROWS)
    runner.session.set("fused_partial_agg_enabled", "true")
    runner.session.set("split_batch_size", DRIVERS[driver])
    seen, out = set(), []
    launch = PG.launch

    def lowering(sink, program, *args, **kwargs):
        if id(program) not in seen:
            seen.add(id(program))
            text = program.jitted.lower(*args, **kwargs).as_text()
            out.append([program.label,
                        hashlib.sha256(text.encode()).hexdigest()])
        return launch(sink, program, *args, **kwargs)

    PG.launch = lowering
    try:
        runner.execute(sql)
    finally:
        PG.launch = launch
    return out


@pytest.mark.parametrize("key", KEYS)
def test_a_chain_without_a_rider_lowers_to_the_pinned_text(key):
    with open(PINNED) as f:
        pinned = json.load(f)
    got = lowered(key)
    case, driver = key.rsplit("/", 1)
    label = CASES[case][2] + ("_batch" if driver == "batched" else "")
    assert label in [lb for lb, _ in got], got
    assert got == pinned[key]


if __name__ == "__main__":
    with open(PINNED, "w") as f:
        json.dump({key: lowered(key) for key in KEYS}, f, indent=1)
        f.write("\n")
    print("wrote", PINNED)
