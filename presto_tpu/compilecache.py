"""Compilation-reuse layer: persistent XLA compile cache + counters.

Reference: Presto amortizes per-query codegen with compiled-artifact
caches (ExpressionCompiler's LRU, the coordinator reusing plans across
queries). The JAX-native analog is jax's persistent compilation cache:
programs compile once per canonical shape PER MACHINE, not per process
— repeated benchmark runs, repeated tier-1 runs, and worker restarts all
reload compiled executables from disk instead of re-invoking XLA (on
an earlier TPU toolchain a partitioned-join program set costs 40+ min
fresh; warm it is seconds). The other half of the bargain — making the
cache actually hit — is the shared shape ladder in exec/shapes.py.

Observability: jax.monitoring hooks below count real XLA backend
compiles (`programs_compiled`, `compile_wall_s`) and persistent-cache
hits/misses (`program_cache_hits` / `persistent_cache_misses`)
process-wide; the executor snapshots them around each query and
EXPLAIN ANALYZE reports the deltas (/metrics the process totals). A
persistent-cache HIT does not count as a compile —
`programs_compiled == 0` on a warmed run is the contract.

Program load, split (ISSUE 25): what a program's first call costs in a
process has four parts, and the hooks below keep a process total of
each — `program_trace_wall_s` (Python tracing to a jaxpr),
`program_lower_wall_s` (jaxpr to an MLIR module), which no cache saves,
and `program_retrieval_wall_s` (reading and loading an executable from
the persistent cache) beside `compile_wall_s` (a real XLA compile). A
jitted function traced while another is being traced (most of `jnp` is
jitted) reports its own trace event inside the outer one's interval:
the total counts the union, per thread, not the sum.

Counters are process-global (jax compiles are); concurrent queries in
one process attribute each other's compiles to whichever query's
window they land in — same caveat as every process-wide metric.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from presto_tpu.obs.sanitizer import make_lock

# NOTE on jax's event semantics (re-verified on jax 0.9.0: a cold and
# then a warm process on one directory counted programs_compiled 2
# then 0, program_cache_hits 0 then 2): the backend_compile_duration
# event wraps compile_or_get_cached, so it fires once per compiled-
# program REQUEST — including persistent-cache HITS, where its
# duration is the (small) retrieval time. Real compiles are therefore
# requests minus hits, and real compile wall is total request wall
# minus the hits' retrieval wall.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAXPR_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_lock = make_lock("compilecache._lock")
_raw: Dict[str, float] = {
    "requests": 0,
    "request_wall_s": 0.0,
    "hits": 0,
    "retrieval_wall_s": 0.0,
    "misses": 0,
    "traces": 0,
    "trace_wall_s": 0.0,
    "lowerings": 0,
    "lower_wall_s": 0.0,
}
# per thread, the trace events not yet seen inside an outer one:
# (start, duration) in completion order, the newest few only
_tls = threading.local()
_MAX_OPEN_TRACES = 256
_installed = False
_cache_dir: Optional[str] = None


def _outermost_part(duration: float) -> float:
    """The part of a trace event's duration that no event already
    counted on this thread lies inside: an inner jitted function's
    event ends before the outer's and began after it, so the outer
    one takes back what its inner ones added."""
    start = time.perf_counter() - duration
    stack = _tls.__dict__.setdefault("traces", [])
    inner = 0.0
    while stack and stack[-1][0] >= start:
        inner += stack.pop()[1]
    stack.append((start, duration))
    del stack[:-_MAX_OPEN_TRACES]
    return max(duration - inner, 0.0)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _JAXPR_TRACE:
        part = _outermost_part(duration)
        with _lock:
            _raw["traces"] += 1
            _raw["trace_wall_s"] += part
    elif event == _JAXPR_TO_MLIR:
        with _lock:
            _raw["lowerings"] += 1
            _raw["lower_wall_s"] += duration
    elif event == _BACKEND_COMPILE:
        with _lock:
            _raw["requests"] += 1
            _raw["request_wall_s"] += duration
    elif event == _CACHE_RETRIEVAL:
        with _lock:
            _raw["retrieval_wall_s"] += duration


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT:
        with _lock:
            _raw["hits"] += 1
    elif event == _CACHE_MISS:
        with _lock:
            _raw["misses"] += 1


def install() -> None:
    """Register the monitoring listeners once per process. Idempotent;
    counters work with or without the persistent cache enabled."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def snapshot() -> Dict[str, float]:
    """Current process-wide compile counters (install()s on first use):
    programs_compiled = real XLA compiles (requests minus persistent-
    cache hits), compile_wall_s = their summed wall (request wall minus
    the hits' retrieval wall)."""
    install()
    with _lock:
        return {
            "programs_compiled": int(_raw["requests"] - _raw["hits"]),
            "compile_wall_s": max(
                _raw["request_wall_s"] - _raw["retrieval_wall_s"], 0.0
            ),
            "program_cache_hits": int(_raw["hits"]),
            "persistent_cache_misses": int(_raw["misses"]),
            "programs_traced": int(_raw["traces"]),
            "program_trace_wall_s": _raw["trace_wall_s"],
            "programs_lowered": int(_raw["lowerings"]),
            "program_lower_wall_s": _raw["lower_wall_s"],
            "program_retrieval_wall_s": _raw["retrieval_wall_s"],
        }


_WALLS = ("compile_wall_s", "program_trace_wall_s",
          "program_lower_wall_s", "program_retrieval_wall_s")


def delta(since: Dict[str, float]) -> Dict[str, float]:
    """Counter deltas since a snapshot(), rounding the walls."""
    cur = snapshot()
    out = {k: cur[k] - since.get(k, 0) for k in cur}
    for k in _WALLS:
        out[k] = round(max(out[k], 0.0), 3)
    return out


def cache_dir() -> Optional[str]:
    """The enabled persistent-cache directory, or None."""
    return _cache_dir


# JAX's own variable: whoever runs the program (the driver, the chip
# tool, an operator) places the cache from outside with it.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# The one default, for the server, the tools and the tests alike: a
# fixed path inside the checkout (the path is part of the cache key,
# so a directory that moves never hits).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def enable_persistent_cache(
    path: Optional[str] = None, min_compile_secs: float = 0.0
) -> str:
    """Turn on jax's persistent compilation cache and register the
    counters; THE one place that decides where the cache lives. Where
    JAX_COMPILATION_CACHE_DIR is set the cache is there — jax reads the
    variable itself, so no directory is configured here and ``path``
    (a caller's argument, the compile_cache_dir session property, the
    compile-cache.dir etc key) is ignored. Otherwise ``path``, else
    DEFAULT_CACHE_DIR, created if missing. min_compile_secs=0 caches
    every program — the engine's programs are numerous and a retry
    rung only pays off if its shape was cached too. Idempotent;
    re-pointing at a different dir is allowed (last call wins)."""
    global _cache_dir
    install()
    import jax

    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        path = env
    else:
        path = os.path.abspath(path or DEFAULT_CACHE_DIR)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _cache_dir = path
    return path
