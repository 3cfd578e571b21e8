"""Shape canonicalization + compilation-reuse layer tests.

The contract (exec/shapes.py + compilecache.py): every dynamic
capacity quantizes onto one power-of-two bucket ladder and jit-cache
keys name canonical program content, so nearby planner estimates,
boosted retries, and repeated runs REUSE compiled programs instead of
minting fresh shapes — `programs_compiled` stays flat on a warmed run.
"""

import dataclasses

import numpy as np
import pytest

from presto_tpu import compilecache as CC
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import plan as P
from presto_tpu.exec import shapes as SH
from presto_tpu.exec.executor import Executor


# ------------------------------------------------------------- ladder
def test_bucket_ladder_properties():
    assert SH.bucket(0) == SH.LADDER_MIN
    assert SH.bucket(8) == 8
    assert SH.bucket(9) == 16
    assert SH.bucket(1000) == 1024
    assert SH.bucket(1024) == 1024
    for n in (1, 7, 100, 4097, 1 << 20):
        b = SH.bucket(n)
        assert b >= n and b & (b - 1) == 0
    # next_bucket is STRICTLY above its argument (the retry re-entry
    # rung), and still on the ladder
    assert SH.next_bucket(8) == 16
    assert SH.next_bucket(9) == 16
    assert SH.next_bucket(16) == 32
    # boosted sizes stay on the ladder: bucket(est * boost) for a
    # pow2 boost is bucket(est) shifted — no off-ladder shapes
    for est in (100, 1000, 5000):
        assert (SH.bucket(est * SH.BOOST_STEP)
                == SH.bucket(est) * SH.BOOST_STEP)
    assert SH.next_boost(1) == SH.BOOST_STEP
    # chunk sizes land on the ladder (2x expected occupancy, floored)
    assert SH.chunk_bucket(1 << 20, 16) == (1 << 20) // 8
    assert SH.chunk_bucket(100, 64) == 1024


# ------------------------------------------- canonical page shapes
@pytest.fixture(scope="module")
def conn():
    return TpchConnector(scale=0.01)


def test_tail_splits_pad_to_bucketed_shapes(conn):
    # orders is a DENSE generator table: valid rows == table rows, so
    # padding is observable exactly (lineitem is slot-structured)
    total = conn.row_count("orders")
    pages = list(conn.pages(
        "orders", ["o_orderkey", "o_custkey"], target_rows=1 << 12
    ))
    # every page's shape is a ladder bucket (the tail split pads up
    # instead of minting an arbitrary program shape downstream)
    for p in pages:
        assert p.capacity == SH.bucket(p.capacity)
    # padded slots are invalid: row accounting is exact
    valid_rows = sum(int(np.asarray(p.valid).sum()) for p in pages)
    assert valid_rows == total
    # the tail split (total % 4096 = 2712 rows) shares the 4096 bucket
    # with the full splits: ONE program shape for the whole table
    assert {p.capacity for p in pages} == {1 << 12}


def _agg_plan(capacity: int) -> P.Output:
    scan = P.TableScan(
        catalog="tpch", table="lineitem",
        columns=("l_returnflag", "l_quantity"),
    )
    agg = P.Aggregation(
        source=scan,
        group_channels=(0,),
        aggregates=(
            P.AggSpec(function="sum", channel=1),
            P.AggSpec(function="count_star"),
        ),
        capacity=capacity,
    )
    return P.Output(source=agg, names=("flag", "s", "c"))


def _rows_sorted(rows):
    return sorted((str(r[0]), round(float(r[1]), 6), int(r[2]))
                  for r in rows)


def test_nearby_capacity_estimates_share_programs(conn):
    """Two plans differing only in the capacity estimate (same bucket)
    produce identical canonical shapes: the second run compiles
    NOTHING and re-traces nothing (jit-cache keys exclude the
    estimate; static caps quantize through the ladder)."""
    ex = Executor({"tpch": conn})
    _, rows1 = ex.execute(_agg_plan(1000))
    base = CC.snapshot()
    _, rows2 = ex.execute(_agg_plan(1010))  # same SH.bucket -> 1024
    d = CC.delta(base)
    assert ex.programs_compiled == 0
    assert d["programs_compiled"] == 0
    # no persistent-cache lookups either: nothing was even re-traced
    assert d["persistent_cache_misses"] == 0
    assert _rows_sorted(rows1) == _rows_sorted(rows2)


def test_overflow_retry_reuses_cached_programs(conn):
    """A capacity-overflow retry climbs the SHARED ladder: re-running
    the same overflowing query compiles zero fresh shapes (every
    boosted rung's programs were cached by the first run)."""
    # l_quantity has 50 distinct values; capacity 8 under-estimates,
    # so the query climbs the boost ladder before succeeding
    plan = P.Output(
        source=P.Aggregation(
            source=P.TableScan(
                catalog="tpch", table="lineitem",
                columns=("l_quantity", "l_orderkey"),
            ),
            group_channels=(0,),
            aggregates=(P.AggSpec(function="count_star"),),
            capacity=8,
        ),
        names=("q", "c"),
    )
    ex = Executor({"tpch": conn})
    _, rows1 = ex.execute(plan)
    assert len(rows1) == 50  # the retry actually happened and finished
    base = CC.snapshot()
    _, rows2 = ex.execute(plan)
    d = CC.delta(base)
    assert ex.programs_compiled == 0
    assert d["programs_compiled"] == 0
    assert sorted(rows1) == sorted(rows2)


def test_oracle_parity_under_bucketed_capacities(conn):
    """Bucketed capacities + padded tail pages change program shapes,
    never results: engine group-by matches a host-side oracle."""
    ex = Executor({"tpch": conn}, page_rows=1 << 14)  # forces tail pads
    _, rows = ex.execute(_agg_plan(1000))
    oracle = {}
    for page in conn.pages("lineitem", ["l_returnflag", "l_quantity"]):
        for flag, qty in page.to_pylist():
            s, c = oracle.get(flag, (0.0, 0))
            oracle[flag] = (s + float(qty), c + 1)
    want = sorted(
        (str(k), round(v[0], 6), v[1]) for k, v in oracle.items()
    )
    assert _rows_sorted(rows) == want


# ------------------------------------------------- cache/session wiring
@pytest.fixture
def restore_cache_placement():
    """enable_persistent_cache is process-global: put the suite's own
    placement back after a test re-points it."""
    yield
    CC.enable_persistent_cache()


def test_compile_cache_session_property(tmp_path, monkeypatch,
                                        restore_cache_placement):
    from presto_tpu.runner import LocalRunner

    monkeypatch.delenv(CC.ENV_CACHE_DIR, raising=False)
    runner = LocalRunner(
        {"tpch": TpchConnector(scale=0.001)}, default_catalog="tpch"
    )
    cache_dir = str(tmp_path / "cc")
    runner.session.set("compile_cache_dir", cache_dir)
    runner.apply_session()
    assert CC.cache_dir() == cache_dir
    # prewarm compiles the program set; a second prewarm finds
    # everything cached in-process
    runner.prewarm("select count(*) from lineitem")
    out = runner.prewarm("select count(*) from lineitem")
    assert out["programs_compiled"] == 0
    assert out["cache_dir"] == cache_dir


def _jax_cache_dir_setting():
    """jax's own configured cache directory (what
    enable_persistent_cache may or may not have written)."""
    import jax

    (name,) = [k for k in jax.config.values
               if k.endswith("compilation_cache_dir")]
    return jax.config.values[name]


@pytest.mark.parametrize("env_set,via", [
    (True, "argument"), (True, "session"), (True, "etc"),
    (False, "default"), (False, "argument"),
])
def test_compile_cache_placement(env_set, via, tmp_path, monkeypatch,
                                 restore_cache_placement):
    """ONE place decides where the cache lives: where
    JAX_COMPILATION_CACHE_DIR is set the cache is there — a caller's
    path, the session property and the etc key are ignored and jax's
    own setting is not written; where it is unset the default is the
    fixed <checkout>/.jax_cache and a caller's path still wins."""
    import os

    env_dir = str(tmp_path / "from_env")
    mine = str(tmp_path / "mine")
    if env_set:
        monkeypatch.setenv(CC.ENV_CACHE_DIR, env_dir)
    else:
        monkeypatch.delenv(CC.ENV_CACHE_DIR, raising=False)
    before = _jax_cache_dir_setting()

    if via == "session":
        from presto_tpu.runner import LocalRunner

        runner = LocalRunner(
            {"tpch": TpchConnector(scale=0.001)},
            default_catalog="tpch")
        runner.session.set("compile_cache_dir", mine)
        runner.apply_session()
    elif via == "etc":
        from presto_tpu.config import server_from_etc

        etc = tmp_path / "etc"
        (etc / "catalog").mkdir(parents=True)
        (etc / "config.properties").write_text(
            f"compile-cache.dir={mine}\n")
        (etc / "catalog" / "tpch.properties").write_text(
            "connector.name=tpch\ntpch.scale-factor=0.001\n")
        server_from_etc(str(etc), port=0)
    elif via == "argument":
        CC.enable_persistent_cache(mine)
    else:
        CC.enable_persistent_cache()

    if env_set:
        assert CC.cache_dir() == env_dir
        assert _jax_cache_dir_setting() == before  # nothing written
        assert not os.path.exists(mine)
    elif via == "default":
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert CC.cache_dir() == os.path.join(checkout, ".jax_cache")
        assert CC.cache_dir() == CC.DEFAULT_CACHE_DIR
        assert _jax_cache_dir_setting() == CC.DEFAULT_CACHE_DIR
    else:
        assert CC.cache_dir() == mine
        assert _jax_cache_dir_setting() == mine


def test_tpu_budget_needs_the_device_memory_limit(monkeypatch):
    """On a TPU whose runtime reports no memory limit, auto budget
    resolution is an error that names the device — never a guessed
    HBM size."""
    from presto_tpu.exec import membudget as MB

    monkeypatch.setattr(MB, "device_hbm_bytes", lambda: None)
    with pytest.raises(RuntimeError, match="bytes_limit") as err:
        MB.resolve_budget(0, "tpu")
    import jax

    assert jax.local_devices()[0].device_kind in str(err.value)
    # an explicit setting never asks the device
    assert MB.resolve_budget(1 << 30, "tpu") == 1 << 30
    monkeypatch.setattr(MB, "device_hbm_bytes", lambda: 16 << 30)
    assert MB.resolve_budget(0, "tpu") == (16 << 30) * 7 // 8


def test_pallas_join_policy_by_backend(monkeypatch):
    """pallas_join_enabled: auto allows the dim probe on a TPU only;
    true allows it everywhere, interpreted off a TPU (the test path)
    and compiled on one; builds above DIM_MAX_BUILD have no layout
    (the sort join's). Nothing raises."""
    import jax

    from presto_tpu.exec.executor import Executor
    from presto_tpu.ops import pallas_join as PJ

    assert PJ.plan_layout(1000) == ("dim", 16)
    assert PJ.plan_layout(PJ.DIM_MAX_BUILD) == ("dim", PJ.DIM_TILES_MAX)
    assert PJ.plan_layout(PJ.DIM_MAX_BUILD + 1) is None

    ex = Executor({"tpch": TpchConnector(scale=0.001)})
    on_cpu = {"off": False, "auto": False, "force": True}
    for mode, allowed in on_cpu.items():
        ex.pallas_join = mode
        assert ex._pallas_mode_allows() is allowed, mode
    assert Executor._pallas_interpret() is True

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_tpu = {"off": False, "auto": True, "force": True}
    for mode, allowed in on_tpu.items():
        ex.pallas_join = mode
        assert ex._pallas_mode_allows() is allowed, mode
    assert Executor._pallas_interpret() is False  # lowers for real


def test_explain_analyze_reports_compile_counters(conn):
    from presto_tpu.runner import LocalRunner

    runner = LocalRunner({"tpch": conn}, default_catalog="tpch")
    res = runner.execute(
        "explain analyze select count(*) from lineitem"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "programs_compiled=" in text
    assert "compile_wall_s=" in text
