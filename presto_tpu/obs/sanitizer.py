"""Runtime lock sanitizer + THE engine concurrency registry (ISSUE 11).

Reference: the concurrency tooling the Java original leans on —
`@GuardedBy` annotations checked by error-prone, `synchronized` audits
in review, and ThreadSanitizer-style CI jobs racing the coordinator's
state machines deliberately. The Python rebuild gets the same two
layers: `tools/concheck.py` is the static side (lock inventory,
acquisition-order graph, blocking-under-lock); THIS module is the
dynamic side — an opt-in instrumented lock that records what actually
happens at runtime:

  - per-thread held-lock sets and every observed acquisition ordering
    (lock A held while acquiring lock B);
  - lock-order INVERSIONS observed live (A-then-B somewhere,
    B-then-A somewhere else — the classic two-thread deadlock shape),
    recorded with both sites;
  - re-entrant acquisition of a non-reentrant lock (a guaranteed
    self-deadlock: the sanitizer raises instead of hanging CI);
  - writes to a class's declared `_shared_attrs` without any of the
    object's registered locks held (the `tools/lint` locks-rule
    contract, enforced against real interleavings instead of the AST).

Zero-cost when off: `make_lock`/`make_condition` return plain
`threading` primitives and `register_owner` is a no-op boolean check,
so the serving path pays nothing. Armed (env
`PRESTO_TPU_LOCK_SANITIZER=1`, the tier-1 conftest,
`tools/chaos.py --sanitize`), every engine lock is a
`_SanitizedLock` and every registered owner's class is swapped for an
instrumented subclass whose `__setattr__` checks the lock contract.
Violations accumulate in a process-wide list (they never raise except
for the guaranteed-deadlock case) — harnesses assert `violations()`
is empty after racing the engine.

Granularity caveats, documented not hidden: ordering is tracked by
lock NAME (one name per class attribute — two instances of the same
lock rank are not ordered against each other), and `__setattr__`
instrumentation sees attribute REBINDS only (`self._entries[k] = v`
mutates a dict in place and is invisible here — the static locks rule
covers subscript writes).

The two registries below are the `QUERY_COUNTERS`/`SPAN_KINDS`
discipline applied to concurrency: every lock/Condition the engine
creates and every `threading.Thread` target it spawns is declared
here with help text, and `tools/concheck.py` fails when a site is
undeclared or an entry is stale.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------
# THE concurrency registry. Keys are canonical site names: the dotted
# module path under presto_tpu/ plus the owning class (if any) and the
# attribute — exactly the literal each make_lock()/make_condition()
# call site passes, cross-checked by tools/concheck.py.

LOCK_REGISTRY: Dict[str, str] = {
    "cache.store.ResultCache._lock":
        "the process-shared result-cache store: entry map, byte "
        "accounting, LRU order, tallies",
    "cache.store._shared_lock":
        "creation of THE per-process shared ResultCache instance",
    "cache.persist.ManifestStore._lock":
        "the generation-numbered manifest's in-memory entry map + "
        "pending-append queue (shared by the result-cache warm tier "
        "and the coordinator checkpoint journal) — append/compaction "
        "file I/O runs OUTSIDE it on a drain loop (take batch under "
        "lock marking the writer busy, write outside, re-check)",
    "dist.checkpoint.CheckpointJournal._lock":
        "the coordinator checkpoint journal's per-query record map "
        "(protocol threads noting client tokens vs scheduler threads "
        "recording stage barriers on the same query) — durable "
        "publishes go through the ManifestStore OUTSIDE this lock",
    "dist.cacheprobe.RemoteCacheIndex._lock":
        "per-worker bloom summaries of cached fragment keys: "
        "heartbeat threads write (update_from_info), scheduler "
        "dispatch threads read (might_contain) — pure bytes ops, "
        "probes themselves go over connpool OUTSIDE the lock",
    "connectors.cached.ResidentConnector._lock":
        "the device-resident table store: the table map, and a "
        "table's LOAD, which runs under it on purpose so that "
        "concurrent first touches load once and the rest wait",
    "connectors.stream.StreamConnector._cv":
        "the append-log table map + offset advance; appends "
        "notify_all so tailing long-pollers (wait_for_offset) wake",
    "dist.connpool.ConnectionPool._lock":
        "the per-destination keep-alive connection free-lists + "
        "reuse/failover tallies (take/put are pure list ops — every "
        "connect, send, and read happens OUTSIDE the lock)",
    "compilecache._lock":
        "process-wide XLA compile/cache counters fed by jax.monitoring "
        "listeners",
    "obs.histo.Histogram._lock":
        "latency-histogram buckets (observe vs scrape)",
    "obs.profile.ProfileStore._instances_lock":
        "the per-directory ProfileStore instance map (class-level)",
    "obs.profile.ProfileStore._lock":
        "one profile store's in-memory profile cache",
    "obs.trace.QueryTrace._lock":
        "one query's span list (scheduler dispatch loop vs status "
        "polls record concurrently)",
    "server.heartbeat.HeartbeatFailureDetector._lock":
        "peer-health map shared between the ping loop and query-path "
        "readers",
    "server.http_server.MemoryArbiter._cv":
        "HBM-footprint admission: used/active accounting + waiters",
    "server.launch_batcher.LaunchBatcher._cv":
        "the cross-query batch point: pending gather-groups keyed by "
        "jit-key family; leaders gather under a bounded window, "
        "followers park for the published per-slot results — the "
        "shared device dispatch itself runs OUTSIDE this lock",
    "server.http_server.QueryManager._exec_lock":
        "the serial-path device lock (one query on the chip when no "
        "memory arbiter is configured)",
    "server.http_server.QueryManager._lock":
        "query registry + completion tallies shared between HTTP "
        "handler threads and per-query executor threads",
    "server.resource_groups.ResourceGroupManager._lock":
        "admission queues/slots/memory per resource-group path "
        "(Condition-fronted: acquire blocks on it)",
    "server.worker._runtimes_lock":
        "the same-process placement registry (uri -> TaskRuntime) the "
        "mesh-local exchange fast path reads",
    "server.worker.TaskRuntime._fault_lock":
        "fault-injection overlay + the drop/kill call counters",
    "server.worker.TaskRuntime._tasks_lock":
        "the task registry (create/expire/cancel vs data-plane "
        "lookups)",
    "server.worker._Task.lock":
        "one task's result buffers and lifecycle flags (executor "
        "thread vs fetch/status/cancel handlers)",
    "server.http_server.TailCursor._cv":
        "one tailing cursor's emitted rows / token spans / poll "
        "serialization flag (concurrent protocol GETs on one "
        "cursor); the poll's query execution runs UNLOCKED behind "
        "the _polling flag",
    "streaming.ivm.IvmRegistry._lock":
        "the materialized-view registry (register/lookup by name "
        "and by statement shape fingerprint)",
    "streaming.ivm.MaterializedView._cv":
        "one view's persisted state/watermark/last-result "
        "publication + refresh serialization flag; the refresh "
        "itself (delta scan, fold, finalize) runs UNLOCKED behind "
        "_refreshing so concurrent tailers coalesce",
    "streaming.ivm._shared_lock":
        "creation of THE per-process shared IvmRegistry instance",
}

THREAD_REGISTRY: Dict[str, str] = {
    "server.heartbeat:self._loop":
        "background peer-ping loop (daemon; stops via Event)",
    "server.http_server:self._run":
        "one thread per submitted query: admission -> execute -> "
        "completion",
    "server.http_server:self._httpd.serve_forever":
        "the coordinator's HTTP accept loop",
    "server.worker:self._run_task":
        "one thread per task: fragment execution into the spool/page "
        "buffers",
    "server.http_server:self._reattach_run":
        "one thread per journaled query on a restarted coordinator: "
        "recover via dist.checkpoint.reattach_query, verify the "
        "delivered-page digests, settle FINISHED/FAILED",
    "server.worker:self._httpd.serve_forever":
        "the worker's HTTP accept loop",
}

# ---------------------------------------------------------------------
# arming

_armed = os.environ.get("PRESTO_TPU_LOCK_SANITIZER", "") in (
    "1", "true", "on")
_THIS_FILE = os.path.abspath(__file__)
_THREADING_FILE = os.path.abspath(threading.__file__)

_tls = threading.local()
_meta = threading.Lock()  # raw on purpose: the instrumentation's own
_order: Dict[Tuple[str, str], str] = {}     # (held, acquired) -> site
_violations: List[str] = []
_subclasses: Dict[type, type] = {}


def arm() -> None:
    """Instrument locks created FROM NOW ON (creation-time choice:
    already-created plain locks stay plain)."""
    global _armed
    _armed = True


def disarm() -> None:
    global _armed
    _armed = False


def is_armed() -> bool:
    return _armed


def reset() -> None:
    """Clear recorded violations and orderings (test isolation)."""
    with _meta:
        _violations.clear()
        _order.clear()


def violations() -> List[str]:
    with _meta:
        return list(_violations)


def violation_count() -> int:
    with _meta:
        return len(_violations)


def order_edges() -> Dict[Tuple[str, str], str]:
    """Observed (held, acquired) orderings with their first site."""
    with _meta:
        return dict(_order)


def report() -> str:
    """Human-readable violation dump (harness failure output)."""
    v = violations()
    if not v:
        return "# lock sanitizer: 0 violations"
    return "# lock sanitizer: {} violation(s)\n".format(len(v)) + \
        "\n".join(f"  - {x}" for x in v)


# ---------------------------------------------------------------------
# internals

def _held() -> List["_SanitizedLock"]:
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    return held


def _site() -> str:
    """First caller frame outside this module and threading.py (the
    Condition wrapper calls acquire/release from threading.py)."""
    f = sys._getframe(1)
    for _ in range(12):
        if f is None:
            break
        fn = f.f_code.co_filename
        if fn not in (_THIS_FILE, _THREADING_FILE):
            return f"{os.path.basename(fn)}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


def _violation(msg: str) -> None:
    with _meta:
        _violations.append(msg)


class _SanitizedLock:
    """Duck-typed non-reentrant lock recording held-sets/orderings.
    Works as a `threading.Condition` backing lock: Condition lifts
    acquire/release/_is_owned, so wait() keeps the held-set honest."""

    __slots__ = ("name", "_raw")

    def __init__(self, name: str):
        self.name = name
        self._raw = threading.Lock()

    def acquire(self, blocking: bool = True, timeout: float = -1):
        held = _held()
        for h in held:
            if h is self:
                msg = (f"re-entrant acquire of non-reentrant lock "
                       f"{self.name} at {_site()} — guaranteed "
                       f"self-deadlock")
                _violation(msg)
                raise RuntimeError(msg)
        if timeout == -1:
            got = self._raw.acquire(blocking)
        else:
            got = self._raw.acquire(blocking, timeout)
        if got:
            if held:
                site = _site()
                with _meta:
                    for h in held:
                        if h.name == self.name:
                            continue
                        pair = (h.name, self.name)
                        inverse = (self.name, h.name)
                        if inverse in _order and pair not in _order:
                            _violations.append(
                                f"lock-order inversion: {self.name} "
                                f"acquired while holding {h.name} at "
                                f"{site}, but the opposite order was "
                                f"observed at {_order[inverse]}")
                        _order.setdefault(pair, site)
            held.append(self)
        return got

    def release(self) -> None:
        held = _held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is self:
                del held[i]
                break
        self._raw.release()

    def __enter__(self) -> "_SanitizedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._raw.locked()

    def _is_owned(self) -> bool:
        # lifted by threading.Condition (beats its acquire(0) probe)
        return any(h is self for h in _held())

    held_by_me = _is_owned


# ---------------------------------------------------------------------
# the factory surface engine modules create their locks through

def make_lock(name: str):
    """A lock for the canonical site ``name`` (a LOCK_REGISTRY key —
    tools/concheck.py cross-checks the literal against the site)."""
    if _armed:
        return _SanitizedLock(name)
    return threading.Lock()


def make_condition(name: Optional[str] = None, lock=None):
    """A Condition; pass ``lock=`` to front an existing engine lock
    (the ResourceGroupManager shape — holding the Condition IS holding
    the lock, so the held-set stays unified), else a dedicated backing
    lock is created under ``name``."""
    if lock is None:
        assert name is not None, "make_condition needs a name or a lock"
        lock = make_lock(name)
    return threading.Condition(lock)


def _resolve_lock(obj, attr: str) -> Optional[_SanitizedLock]:
    x = getattr(obj, attr, None)
    if isinstance(x, _SanitizedLock):
        return x
    if isinstance(x, threading.Condition) and \
            isinstance(x._lock, _SanitizedLock):
        return x._lock
    return None


def _subclass_for(cls: type, lock_attrs: Tuple[str, ...]) -> type:
    sub = _subclasses.get(cls)
    if sub is not None:
        return sub
    shared = frozenset(getattr(cls, "_shared_attrs", ()) or ())

    def __setattr__(self, name, value):
        if name in shared:
            locks = [_resolve_lock(self, a) for a in lock_attrs]
            locks = [lk for lk in locks if lk is not None]
            if locks and not any(lk._is_owned() for lk in locks):
                _violation(
                    f"unlocked shared-attr write: "
                    f"{cls.__module__}.{cls.__name__}.{name} written "
                    f"without {'/'.join(lk.name for lk in locks)} "
                    f"held at {_site()}")
        object.__setattr__(self, name, value)

    sub = type(cls.__name__, (cls,), {
        "__setattr__": __setattr__,
        "_san_instrumented": True,
        "__module__": cls.__module__,
    })
    _subclasses[cls] = sub
    return sub


def register_owner(obj, lock_attrs=("_lock",)):
    """Called at the end of a lock-owning __init__: when armed, swap
    the instance's class for an instrumented subclass that checks every
    `_shared_attrs` rebind happens under one of ``lock_attrs``. No-op
    (one bool check) when off."""
    if not _armed:
        return obj
    cls = type(obj)
    if getattr(cls, "_san_instrumented", False):
        return obj
    if not getattr(cls, "_shared_attrs", None):
        return obj
    if not any(_resolve_lock(obj, a) for a in lock_attrs):
        return obj  # plain locks (created before arming): uncheckable
    try:
        obj.__class__ = _subclass_for(cls, tuple(lock_attrs))
    except TypeError:
        pass  # __slots__/extension classes cannot be swapped; skip
    return obj
