"""Starts the system under test and warms it: the coordinator in this
process, from an etc/ directory written out of the cell's configuration
file, driven over real HTTP on localhost.

From the program this module takes ``presto_tpu.config`` (the etc/
loader), ``presto_tpu.runner.LocalRunner`` (to compile a statement's
program set off the serving thread), ``presto_tpu.client`` and
``presto_tpu.compilecache`` (compile counts). It sets no attribute of
any of them.

A checkout's first run compiles in a **child process** that ends before
this one touches the chip (``compile_in_child``), so the serving process
only ever loads programs from the persistent cache: the state every
later run is in. A process that had compiled the mixed cell's programs
itself and then served them died of a segmentation fault in the Q1
statements' first served executions (PERF.md, PR 24).
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import threading
import time
import urllib.request
from typing import Dict, List

REHEARSE_SCALE_FACTOR = "0.01"
# etc keys the server's constructor consumes; every other registered key
# is a session default, which the compile phase's runners get too
_SERVER_KEYS = ("page-rows", "query.max-memory-bytes")
_METRIC_LINE = re.compile(r"^presto_tpu_(\w+?)(?:_total)? (-?[\d.e+-]+)$")


def http_text(url: str) -> str:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.read().decode()


def http_json(url: str):
    return json.loads(http_text(url))


def write_etc(etc_dir: str, config: Dict, rehearse: bool) -> Dict:
    """etc/config.properties and etc/catalog/*.properties from the
    configuration file. Returns the catalogs' properties as written (a
    rehearsal shrinks every scale factor)."""
    os.makedirs(os.path.join(etc_dir, "catalog"), exist_ok=True)

    def dump(path, props):
        with open(path, "w") as f:
            f.writelines(f"{k}={v}\n" for k, v in props.items())

    dump(os.path.join(etc_dir, "config.properties"),
         config["config_properties"])
    catalogs = {}
    for name, props in config["catalogs"].items():
        props = dict(props)
        if rehearse:
            for k in props:
                if k.endswith("scale-factor"):
                    props[k] = REHEARSE_SCALE_FACTOR
        catalogs[name] = props
        dump(os.path.join(etc_dir, "catalog", f"{name}.properties"),
             props)
    return catalogs


class Served:
    """The in-process coordinator and its URL."""

    def __init__(self, etc_dir: str, chips: int):
        from presto_tpu.config import server_from_etc

        kw = {}
        if chips > 1:
            from presto_tpu.dist.executor import make_mesh

            kw["mesh"] = make_mesh(chips)
        self.server = server_from_etc(etc_dir, port=0, **kw)
        self.url = f"http://127.0.0.1:{self.server.start()}"
        self.catalogs = self.server.catalogs

    def client(self, catalog: str):
        from presto_tpu.client import StatementClient

        return StatementClient(self.url, catalog=catalog)

    def query_info(self, query_id: str) -> Dict:
        return http_json(f"{self.url}/v1/query/{query_id}")

    def metrics(self) -> Dict[str, float]:
        """/metrics as name -> value, without the presto_tpu_ prefix and
        the _total suffix (histogram buckets and labelled lines are
        left out)."""
        out = {}
        for line in http_text(f"{self.url}/metrics").splitlines():
            m = _METRIC_LINE.match(line)
            if m:
                out[m.group(1)] = float(m.group(2))
        return out

    def stop(self) -> None:
        self.server.stop()


def compile_statements(catalogs: Dict, cell, statements: List, log
                       ) -> None:
    """Every statement's program set compiled (or loaded from the
    persistent cache) on a runner of its own. The runners get what the
    server gives every query it runs: tracing on, the deployment's
    session defaults and, for a cell on several chips, the mesh, so that
    what compiles here is what the server will load. On one chip every
    statement has a thread of its own, all at once: one cold TPU compile
    of one statement takes minutes and is single-threaded."""
    from presto_tpu.config import ETC_SESSION_KEYS
    from presto_tpu.runner import LocalRunner

    mesh = {}
    if cell.chips > 1:
        from presto_tpu.dist.executor import make_mesh

        mesh["mesh"] = make_mesh(cell.chips)
    props = cell.config["config_properties"]
    page_rows = int(props.get("page-rows", str(1 << 18)))
    session = {"query_trace_enabled": True}
    for etc_key, prop in ETC_SESSION_KEYS.items():
        if etc_key in props and etc_key not in _SERVER_KEYS:
            session[prop] = props[etc_key]

    def prewarm(st):
        runner = LocalRunner(catalogs, default_catalog=st.catalog,
                             page_rows=page_rows, **mesh)
        for k, v in session.items():
            runner.session.set(k, v)
        out = runner.prewarm(st.sql)
        log(phase="compile", statement=st.key,
            thread_wall_s=out["wall_s"])

    if cell.chips > 1:
        # prewarm executes the statement, and programs with collectives
        # launched from two threads have no common dispatch order across
        # the devices (DistExecutor._fenced puts them in one on the CPU
        # only): over a mesh, one statement after another on one thread
        for st in statements:
            prewarm(st)
    else:
        _run_all(prewarm, statements, "compile phase")


def _run_all(fn, statements, what: str) -> None:
    """fn(statement) for every statement, each on a thread of its own."""
    failures = []

    def guarded(st):
        try:
            fn(st)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            failures.append((st.key, e))

    threads = [threading.Thread(target=guarded, args=(st,), daemon=True)
               for st in statements]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise RuntimeError(f"{what} failed: {failures}") from failures[0][1]


def _program_hash(cell) -> "hashlib._Hash":
    """A hash of the program's source, JAX's version, the configuration
    as served and the chips it is served over (a mesh's programs are not
    one chip's): what a compiled program depends on besides the
    statement."""
    import hashlib

    import jax

    from presto_tpu import compilecache

    program = os.path.dirname(os.path.abspath(compilecache.__file__))
    h = hashlib.sha256(jax.__version__.encode())
    for root, dirs, files in sorted(os.walk(program)):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                with open(os.path.join(root, name), "rb") as f:
                    h.update(f.read())
    h.update(json.dumps(cell.config["config_properties"],
                        sort_keys=True).encode())
    h.update(json.dumps(cell.config["catalogs"],
                        sort_keys=True).encode())
    h.update(f"chips={cell.chips}".encode())
    return h


def _warm_markers(cell, statements: List, rehearse: bool
                  ) -> Dict[str, str]:
    """statement key -> a file in the persistent cache's directory that
    says: this source of the program, serving this statement under this
    configuration over this many chips, has compiled into this cache
    before. The file travels
    with the cache, and a changed program or statement misses it. The
    directory is the one ``server_from_etc`` will enable: the same call
    is made here, ahead of it, which touches no device."""
    from presto_tpu import compilecache

    compilecache.enable_persistent_cache(
        cell.config["config_properties"].get("compile-cache.dir"))
    base = _program_hash(cell)
    base.update(b"rehearsal" if rehearse else b"chip")
    out = {}
    for st in statements:
        h = base.copy()
        h.update(f"{st.catalog}\0{st.sql}".encode())
        out[st.key] = os.path.join(
            compilecache.cache_dir(),
            f"perfbench-warm-{h.hexdigest()[:24]}")
    return out


def uncompiled(cell, rehearse: bool) -> List:
    """Those of the cell's statements that the persistent cache is not
    known to hold."""
    markers = _warm_markers(cell, cell.every, rehearse)
    return [st for st in cell.every
            if not os.path.exists(markers[st.key])]


def compile_phase(etc_dir: str, cell, rehearse: bool, log) -> None:
    """The child's work: compile what the cache is not known to hold, on
    runners over the catalogs as served (and the mesh, where the cell
    has one), and leave the markers."""
    from presto_tpu import compilecache
    from presto_tpu.config import load_catalogs

    unknown = uncompiled(cell, rehearse)
    base = compilecache.snapshot()
    t0 = time.perf_counter()
    compile_statements(load_catalogs(etc_dir), cell, unknown, log)
    cc = compilecache.delta(base)
    log(phase="compile", compiled_off_server=[st.key for st in unknown],
        wall_s=time.perf_counter() - t0,
        programs_compiled=cc["programs_compiled"],
        program_cache_hits=cc["program_cache_hits"])
    markers = _warm_markers(cell, unknown, rehearse)
    for st in unknown:
        with open(markers[st.key], "w") as f:
            f.write("warm\n")


def compile_in_child(command: List[str], log) -> None:
    """Runs the compile phase as a process of its own and waits for its
    end. Called before this process has touched a device: one process
    holds the chip at a time. Whatever ends this process ends the child
    first. A child that a signal ended is started once more: the TPU
    compiler has died of a segmentation fault with eight statements
    compiling side by side (PERF.md, PR 28), what had compiled by then
    is in the persistent cache, and the second child goes on from
    there."""
    def on_sigterm(signum, _frame):
        raise SystemExit(128 + signum)

    main = threading.current_thread() is threading.main_thread()
    old = signal.signal(signal.SIGTERM, on_sigterm) if main else None
    try:
        for attempt in (1, 2):
            t0 = time.perf_counter()
            # subprocess.run kills the child and waits for it on any
            # exception, the SystemExit above among them
            rc = subprocess.run(
                command, stdin=subprocess.DEVNULL).returncode
            log(phase="compile_child", rc=rc, attempt=attempt,
                wall_s=time.perf_counter() - t0)
            if rc >= 0:
                break
    finally:
        if main:
            signal.signal(signal.SIGTERM, old)
    if rc != 0:
        raise SystemExit(
            f"the compile phase's process ended with code {rc}; its "
            "standard error is above")


def warm(served: Served, statements: List, side_by_side: bool, log
         ) -> Dict:
    """Set-up after the server is up, every program in the persistent
    cache: each of this run's statements is served once, which loads its
    programs into the server's own jit cache. Returns the compile counts
    of it. The concurrent server is sent them all at once
    (``side_by_side``). The serial path runs one statement at a time
    whatever it is sent, so it is sent them one after another, in the
    order given: which statement loads first decides where its programs'
    buffers lie on the device, and with it the device time of every
    later run of a statement (Q3 at SF1: 793 or 810 ms for the whole
    run, PERF.md, PR 28); sent all at once, the order was a race between
    two threads."""
    from presto_tpu import compilecache

    base = compilecache.snapshot()
    t0 = time.perf_counter()

    def serve_once(st):
        t1 = time.perf_counter()
        result = served.client(st.catalog).execute(st.sql)
        if result.error or result.state != "FINISHED":
            raise RuntimeError(
                f"warm-up of {st.key} {result.state}: {result.error}")
        log(phase="warm", statement=st.key,
            wall_s=time.perf_counter() - t1)

    if side_by_side:
        _run_all(serve_once, statements, "warm-up")
    else:
        for st in statements:
            serve_once(st)
    cc = compilecache.delta(base)
    log(phase="warm", wall_s=time.perf_counter() - t0,
        programs_compiled=cc["programs_compiled"],
        program_cache_hits=cc["program_cache_hits"])
    return cc
