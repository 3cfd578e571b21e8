#!/usr/bin/env python3
"""Chip smoke: one process on the TPU serves TPC-H through /v1/statement.

The quickest proof that the served path still starts on the chip. One
process holds the chip: it starts the coordinator in-process with
``presto_tpu.config.server_from_etc`` (what ``python -m presto_tpu.cli
--serve --etc-dir`` calls), talks to it over real HTTP on localhost with
``presto_tpu.client.StatementClient`` and computes the references in the
same process afterwards. It starts no child process.

    python chip_smoke.py            # one chip: tiny + SF1 + SF10 phases
    python chip_smoke.py --chips 4  # the mesh path only, on four chips

The first phase is set-up: every statement's program set is compiled
into the persistent cache, each on a thread of its own (one cold TPU
compile of one statement takes minutes and is single-threaded; the
seven in a row take longer than the whole run may), while the main
thread loads the sqlite oracles. The served phases then load their
programs from that cache. Every number printed says which run it is.

Every phase is fatal: an assertion or an engine error ends the run with
a non-zero exit code and no final line. Without a TPU (``jax.devices()``
reports another platform) the script exits non-zero before any phase.
Each earlier output line is one JSON object; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The compile cache is wherever ``presto_tpu.compilecache`` puts it:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
The script's own files (the etc/ directory it serves from) go under
``chiprun_out/chip_smoke/`` of the checkout.
"""

from __future__ import annotations

import argparse
import collections
import datetime
import decimal
import json
import os
import re
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# etc/ written from these constants, so the run needs committed files
# only. Server defaults otherwise (no session property is overridden;
# page-rows is the server's own default, written out because the
# compile phase below must build the same programs).
PAGE_ROWS = 1 << 18
CONFIG_PROPERTIES = {"default-catalog": "tpch", "page-rows": str(PAGE_ROWS)}
CATALOGS = {
    "tpch": {"connector.name": "tpch", "tpch.scale-factor": "10"},
    "tpch_sf1": {"connector.name": "tpch", "tpch.scale-factor": "1"},
    "tiny": {"connector.name": "tpch", "tpch.scale-factor": "0.01"},
}

EPOCH = datetime.date(1970, 1, 1)
# the TPU-`auto` paths whose engagement the SF10 phase reports
AUTO_PATH_COUNTERS = (
    "fused_partial_aggs", "splits_per_launch", "pallas_joins_used",
    "buffers_donated",
)
_DECIMAL_RE = re.compile(r"decimal\((\d+),\s*(\d+)\)")


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def write_etc(etc_dir: str) -> None:
    os.makedirs(os.path.join(etc_dir, "catalog"), exist_ok=True)

    def dump(path, props):
        with open(path, "w") as f:
            f.writelines(f"{k}={v}\n" for k, v in props.items())

    dump(os.path.join(etc_dir, "config.properties"), CONFIG_PROPERTIES)
    for name, props in CATALOGS.items():
        dump(os.path.join(etc_dir, "catalog", f"{name}.properties"), props)


def http_json(url: str):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.loads(resp.read().decode())


def engine_encoding(result) -> list:
    """Wire rows -> the engine-internal encoding the sqlite oracle uses
    (tests/oracle.py): decimals as unscaled ints, dates as epoch days."""
    kinds = []
    for col in result.columns:
        m = _DECIMAL_RE.match(col["type"])
        kinds.append(int(m.group(2)) if m else col["type"])
    out = []
    for row in result.rows:
        vals = []
        for kind, v in zip(kinds, row):
            if v is None:
                vals.append(None)
            elif isinstance(kind, int):
                vals.append(int(decimal.Decimal(v).scaleb(kind)))
            elif kind == "date":
                vals.append(
                    (datetime.date.fromisoformat(v) - EPOCH).days)
            else:
                vals.append(v)
        out.append(tuple(vals))
    return out


class Served:
    """The in-process coordinator plus one HTTP client per catalog."""

    def __init__(self, server):
        self.server = server
        self.url = f"http://127.0.0.1:{server.start()}"

    def run(self, catalog: str, sql: str):
        """Submit through /v1/statement, follow nextUri to the end.
        Returns (result, wall seconds, attempts) — attempts are the
        query's `attempt` spans from /v1/query/{id} (boost, outcome)."""
        from presto_tpu.client import StatementClient

        client = StatementClient(self.url, catalog=catalog)
        t0 = time.perf_counter()
        result = client.execute(sql)
        wall = time.perf_counter() - t0
        if result.error or result.state != "FINISHED":
            raise RuntimeError(
                f"{catalog}: query {result.query_id} {result.state}: "
                f"{result.error}")
        info = http_json(f"{self.url}/v1/query/{result.query_id}")
        attempts = [
            dict(span["attrs"], name=span["name"])
            for stage in info.get("stages", ())
            for task in stage.get("tasks", ())
            for span in task.get("spans", ())
            if span["kind"] == "attempt"
        ]
        return result, wall, attempts

    def counters(self, catalog: str, sql: str) -> dict:
        """EXPLAIN ANALYZE's trailing Counters line for one more run of
        the statement: the per-query execution counters as a user of
        the server sees them."""
        result, _wall, _att = self.run(catalog, "explain analyze " + sql)
        line = [r[0] for r in result.rows
                if r[0].startswith("Counters:")][-1]
        out = {}
        for kv in line[len("Counters:"):].split(","):
            k, v = kv.strip().split("=", 1)
            out[k] = int(v) if v.lstrip("-").isdigit() else float(v)
        return out


def assert_no_fault(tag: str, attempts: list) -> int:
    """No attempt ended in a device fault; returns the settled boost."""
    faults = [a for a in attempts if a.get("outcome") == "device-fault"]
    assert not faults, f"{tag}: device-fault retry: {attempts}"
    assert attempts and attempts[-1].get("outcome") == "ok", (
        f"{tag}: last attempt not ok: {attempts}")
    return int(attempts[-1].get("boost", 1))


# ------------------------------------------------------------ references
def check_against_sqlite(served, catalog, qnums, db, tag):
    """Each statement through /v1/statement equals sqlite over the same
    generated rows (tests/oracle.py, tests/test_sql_tpch.compare)."""
    from tests.test_sql_tpch import ENGINE_SQL, ORACLE, compare

    for q in qnums:
        result, wall, attempts = served.run(catalog, ENGINE_SQL[q])
        boost = assert_no_fault(f"{tag} q{q}", attempts)
        got = engine_encoding(result)
        oracle_sql, modes = ORACLE[q]
        compare(q, got, db.execute(oracle_sql).fetchall(), modes)
        emit(phase=tag, query=f"q{q}", wall_s=wall, rows=len(got),
             capacity_boost=boost, attempts=len(attempts),
             equal_to="sqlite", ok=True)


def numpy_reference(conn):
    """TPC-H Q1 and Q6 over the connector's generated lineitem columns,
    pulled to the host page by page and reduced with NumPy — plain
    integer arithmetic on the unscaled decimals, independent of the
    engine's operators. Returns (q1 rows, q6 value) in the engine
    encoding (unscaled ints)."""
    import numpy as np

    cols = REFERENCE_COLUMNS
    q1_cut = days(1998, 12, 1) - 90
    q6_lo, q6_hi = days(1994, 1, 1), days(1995, 1, 1)
    dicts = conn._dicts["lineitem"]
    n_ls = len(dicts["l_linestatus"])
    # per group: qty, base, disc_price, charge, discount, count
    acc = collections.defaultdict(lambda: [0] * 6)
    q6 = 0
    n_rows = 0
    for split in conn.splits("lineitem", target_rows=1 << 20):
        page = conn.page_for_split(split, cols)
        valid = np.asarray(page.valid)
        rf, ls, qty, ext, disc, tax, ship = (
            np.asarray(b.data)[valid].astype(np.int64)
            for b in page.blocks)
        n_rows += int(valid.sum())
        m6 = ((ship >= q6_lo) & (ship < q6_hi) & (disc >= 5)
              & (disc <= 7) & (qty < 2400))
        q6 += int((ext[m6] * disc[m6]).sum())
        m1 = ship <= q1_cut
        gid = (rf * n_ls + ls)[m1]
        disc_price = ext[m1] * (100 - disc[m1])
        parts = (qty[m1], ext[m1], disc_price,
                 disc_price * (100 + tax[m1]), disc[m1],
                 np.ones_like(gid))
        for g in np.unique(gid):
            sel = gid == g
            a = acc[int(g)]
            for i, p in enumerate(parts):
                a[i] += int(p[sel].sum())  # Python ints: no overflow
    rows = []
    for g in sorted(acc):
        qty, base, dp, ch, dsc, cnt = acc[g]

        def avg(total):  # decimal average, round half up
            return (2 * total + cnt) // (2 * cnt)

        rows.append((
            str(dicts["l_returnflag"].values[g // n_ls]),
            str(dicts["l_linestatus"].values[g % n_ls]),
            qty, base, dp, ch, avg(qty), avg(base), avg(dsc), cnt,
        ))
    rows.sort()
    return rows, q6, n_rows


# ---------------------------------------------------------------- phases
TINY_QUERIES = (1, 6, 3, 5)
# the tables those four read (part and partsupp are not among them)
TINY_TABLES = ("region", "nation", "supplier", "customer", "orders",
               "lineitem")
SF1_TABLES = ("customer", "orders", "lineitem")
REFERENCE_COLUMNS = (
    "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
    "l_discount", "l_tax", "l_shipdate")


def compile_phase(catalogs) -> dict:
    """Set-up: run every statement once on a LocalRunner of its own
    (LocalRunner.prewarm), all at the same time, so their program sets
    compile side by side into the persistent cache; the main thread
    meanwhile loads the sqlite oracles. Returns the loaded oracles."""
    import threading

    from presto_tpu.runner import LocalRunner
    from tests.oracle import load_sqlite
    from tests.test_sql_tpch import ENGINE_SQL
    from tests.tpch_queries import QUERIES

    statements = [("tiny", q, ENGINE_SQL[q]) for q in TINY_QUERIES]
    statements += [("tpch_sf1", 3, ENGINE_SQL[3]),
                   ("tpch", 6, QUERIES[6]), ("tpch", 1, QUERIES[1])]
    failures = []

    def prewarm(catalog, q, sql):
        try:
            runner = LocalRunner(catalogs, default_catalog=catalog,
                                 page_rows=PAGE_ROWS)
            # what the server sets for every query it runs
            runner.session.set("query_trace_enabled", True)
            out = runner.prewarm(sql)
            emit(phase="compile", catalog=catalog, query=f"q{q}",
                 thread_wall_s=out["wall_s"],
                 persistent_cache_hits=out["program_cache_hits"],
                 note="process-wide compile counters overlap across "
                      "threads; see the phase total")
        except BaseException as e:  # noqa: BLE001 - re-raised below
            failures.append((catalog, q, e))

    def first_reference_page():
        try:
            conn = catalogs["tpch"]
            split = conn.splits("lineitem", target_rows=1 << 20)[0]
            conn.page_for_split(split, REFERENCE_COLUMNS)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            failures.append(("tpch", "reference page", e))

    from presto_tpu import compilecache

    base = compilecache.snapshot()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=prewarm, args=st, daemon=True)
               for st in statements]
    threads.append(threading.Thread(target=first_reference_page,
                                    daemon=True))
    for t in threads:
        t.start()
    oracles = {}
    for name, tables in (("tiny", TINY_TABLES),
                         ("tpch_sf1", SF1_TABLES)):
        t1 = time.perf_counter()
        oracles[name] = load_sqlite(catalogs[name], tables)
        emit(phase="compile", oracle=name, tables=list(tables),
             load_s=time.perf_counter() - t1)
    for t in threads:
        t.join()
    if failures:
        raise RuntimeError(f"compile phase failed: {failures}") \
            from failures[0][2]
    cc = compilecache.delta(base)
    emit(phase="compile", statements=len(statements),
         wall_s=time.perf_counter() - t0,
         programs_compiled=cc["programs_compiled"],
         program_cache_hits=cc["program_cache_hits"],
         compile_wall_summed_s=cc["compile_wall_s"])
    return oracles


def one_chip(served, catalogs) -> None:
    from presto_tpu import compilecache
    from tests.tpch_queries import QUERIES

    info = http_json(f"{served.url}/v1/info")
    emit(phase="info", info=info, cache_dir=compilecache.cache_dir())
    assert info["backend"] == "tpu", info

    oracles = compile_phase(catalogs)
    check_against_sqlite(
        served, "tiny", TINY_QUERIES, oracles["tiny"], "tiny_sf0.01")
    check_against_sqlite(
        served, "tpch_sf1", (3,), oracles["tpch_sf1"], "tpch_sf1")

    got = {}
    for q in (6, 1):
        tag = f"tpch_sf10 q{q}"
        runs = []
        # "cold": first run in the served path of this process; its
        # programs load from the persistent cache the compile phase
        # filled (or compile here, and are counted, if that missed)
        for label in ("cold", "warm"):
            base = compilecache.snapshot()
            result, wall, attempts = served.run("tpch", QUERIES[q])
            cc = compilecache.delta(base)
            boost = assert_no_fault(f"{tag} {label}", attempts)
            runs.append(engine_encoding(result))
            emit(phase="tpch_sf10", query=f"q{q}", run=label,
                 wall_s=wall, capacity_boost=boost,
                 attempts=len(attempts),
                 programs_compiled=cc["programs_compiled"],
                 program_cache_hits=cc["program_cache_hits"],
                 compile_wall_s=cc["compile_wall_s"])
        assert cc["programs_compiled"] == 0, (
            f"{tag}: warm run compiled {cc['programs_compiled']} "
            "programs")
        assert runs[0] == runs[1], f"{tag}: cold and warm rows differ"
        got[q] = runs[1]
        # one more warm run under EXPLAIN ANALYZE for the counters
        ctr = served.counters("tpch", QUERIES[q])
        emit(phase="tpch_sf10", query=f"q{q}", run="warm-analyze",
             counters={k: ctr[k] for k in (
                 "programs_compiled", "split_batch_fallbacks",
                 "device_oom_retries", "capacity_boost_retries",
                 "program_launches", "splits_scanned",
                 "peak_device_bytes", "d2h_bytes", "h2d_bytes",
                 *AUTO_PATH_COUNTERS)},
             auto_paths_engaged=[
                 k for k in AUTO_PATH_COUNTERS if ctr[k] > (
                     1 if k == "splits_per_launch" else 0)])
        assert ctr["programs_compiled"] == 0, (tag, ctr)
        assert ctr["split_batch_fallbacks"] == 0, (tag, ctr)
        assert ctr["device_oom_retries"] == 0, (tag, ctr)

    t0 = time.perf_counter()
    q1_ref, q6_ref, n_rows = numpy_reference(catalogs["tpch"])
    emit(phase="tpch_sf10", reference="numpy", lineitem_rows=n_rows,
         reference_s=time.perf_counter() - t0)
    assert got[6] == [(q6_ref,)], (got[6], q6_ref)
    assert got[1] == q1_ref, f"q1:\nengine {got[1]}\nnumpy  {q1_ref}"
    emit(phase="tpch_sf10", equal_to="numpy", queries=["q6", "q1"],
         q1_groups=len(q1_ref), ok=True)


class _DeviceSampler:
    """Samples, while a query runs, which devices hold live buffers."""

    def __init__(self):
        import threading

        self.bytes_by_device = collections.Counter()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        import jax

        while not self._stop.is_set():
            now = collections.Counter()
            for arr in jax.live_arrays():
                try:
                    for sh in arr.addressable_shards:
                        now[sh.device.id] += sh.data.nbytes
                except RuntimeError:
                    continue  # deleted between listing and reading
            for dev, n in now.items():
                self.bytes_by_device[dev] = max(
                    self.bytes_by_device[dev], n)
            self._stop.wait(0.005)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def four_chips(etc_dir: str) -> None:
    """The mesh path only: a server over make_mesh(4), Q1/Q3/Q5 at SF1
    through /v1/statement, each equal as a multiset of rows to the same
    statement on a one-device runner in this process."""
    import jax

    from presto_tpu import compilecache
    from presto_tpu.config import server_from_etc
    from presto_tpu.dist.executor import make_mesh
    from presto_tpu.exec import plan as P
    from presto_tpu.runner import LocalRunner
    from tests.tpch_queries import QUERIES

    import threading

    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_mesh(4)
    served = Served(server_from_etc(etc_dir, port=0, mesh=mesh))
    conn = served.server.catalogs["tpch_sf1"]

    # what the mesh is compared with: the same statements on one-device
    # runners (device 0, no collectives), each on a thread of its own so
    # their cold compiles overlap the mesh queries' instead of adding
    # to them. The mesh queries themselves run one after another:
    # collective programs are launched from one thread only.
    reference = {}

    def one_device(q):
        try:
            t0 = time.perf_counter()
            rows = LocalRunner({"tpch": conn}).execute(QUERIES[q]).rows
            reference[q] = (rows, time.perf_counter() - t0)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            reference[q] = e

    ref_threads = [threading.Thread(target=one_device, args=(q,),
                                    daemon=True) for q in (1, 3, 5)]
    for t in ref_threads:
        t.start()
    try:
        info = http_json(f"{served.url}/v1/info")
        emit(phase="info", info=info,
             cache_dir=compilecache.cache_dir())
        assert info["backend"] == "tpu", info
        got = {}
        for q in (1, 3, 5):
            with _DeviceSampler() as sampler:
                result, wall, attempts = served.run(
                    "tpch_sf1", QUERIES[q])
            boost = assert_no_fault(f"mesh q{q}", attempts)
            got[q] = engine_encoding(result)
            held = {str(d.id): sampler.bytes_by_device[d.id]
                    for d in jax.devices()}
            emit(phase="mesh_sf1", query=f"q{q}", wall_s=wall,
                 rows=len(got[q]), capacity_boost=boost,
                 peak_live_bytes_by_device=held)
            assert all(n > 0 for n in held.values()), (
                f"mesh q{q}: a device held no live buffer: {held}")
    finally:
        served.server.stop()

    # the repartition program the mesh executor compiles for Q3's
    # sharded exchange really is a collective on this backend
    mesh_runner = LocalRunner({"tpch": conn}, mesh=mesh)
    ex = mesh_runner.executor

    def repartitions(node):
        if (isinstance(node, P.Exchange) and node.kind == "repartition"
                and ex.dist(node.source) == "sharded"):
            yield node
        for c in node.children():
            yield from repartitions(c)

    plan = mesh_runner.plan(QUERIES[3])
    node = next(repartitions(plan))
    page = next(ex.pages(node.source))
    ex._repartition_fn(node.keys)  # made, and kept under its label
    (program,) = [prog for key, prog in ex._jit_cache.items()
                  if key[0] == "d_repartition"]
    hlo = program.jitted.lower(page).compile().as_text()
    n_a2a = hlo.count("all-to-all")
    emit(phase="mesh_sf1", repartition_program="q3",
         all_to_all_ops=n_a2a, page_rows=page.capacity)
    assert n_a2a > 0, "no all-to-all in the compiled repartition"

    for t in ref_threads:
        t.join()
    for q in (1, 3, 5):
        if isinstance(reference[q], BaseException):
            raise reference[q]
        ref, ref_wall = reference[q]
        assert collections.Counter(map(repr, got[q])) == \
            collections.Counter(map(repr, map(tuple, ref))), (
                f"mesh q{q} differs from one device:\n{got[q][:3]}\n"
                f"{ref[:3]}")
        emit(phase="mesh_sf1", query=f"q{q}", equal_to="one-device",
             one_device_thread_wall_s=ref_wall, ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the mesh path only, on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev.platform!r}); this script only runs on the chip",
              file=sys.stderr)
        return 1
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1

    # references load sqlite in memory: nothing is written outside the
    # checkout (tests/oracle.py would otherwise cache under /tmp)
    os.environ["PRESTO_TPU_ORACLE_CACHE_DIR"] = ""
    sys.path.insert(0, HERE)
    import jaxlib

    from presto_tpu.config import server_from_etc

    etc_dir = os.path.join(OUT_DIR, "etc")
    write_etc(etc_dir)
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except Exception:  # noqa: BLE001 - version string is informative
        libtpu_version = "unknown"
    stats = dev.memory_stats() or {}
    t_start = time.perf_counter()
    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, device_kind=dev.device_kind,
         devices=len(jax.devices()),
         bytes_limit=stats.get("bytes_limit"), etc_dir=etc_dir,
         config=CONFIG_PROPERTIES, catalogs=CATALOGS)
    if args.chips == 4:
        four_chips(etc_dir)
    else:
        served = Served(server_from_etc(etc_dir, port=0))
        try:
            one_chip(served, served.server.catalogs)
        finally:
            served.server.stop()
    emit(phase="end", total_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
