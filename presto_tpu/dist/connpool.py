"""Keep-alive HTTP connection pool for the shuffle plane (ISSUE 16).

Reference: presto-main operator/HttpPageBufferClient rides an async
HTTP client with pooled keep-alive connections; our DCN plane opened
a fresh TCP connection per request (urlopen) for every page fetch,
status poll, ack, and release. This module gives `dist/dcn.py`,
`dist/spool.py`, and `dist/scheduler.py` one shared per-destination
pool with urlopen-compatible semantics:

  - `request(url, ...)` returns a response object with `.status`,
    `.headers`, `.read(n)`, usable as a context manager — and raises
    `urllib.error.HTTPError` on >= 400 (with `.code`/`.headers`/
    `.read()` intact) and `urllib.error.URLError` on transport
    failure, so every existing except-clause and retry ladder on the
    fetch plane (PR-5/7 recovery semantics) behaves exactly as it
    did with urlopen.
  - Lock discipline (tools/concheck.py): the pool lock guards ONLY
    the free-list take/put and the reuse tallies. Connects, sends,
    reads, and closes all happen outside it.
  - Loud fallback: a request that fails on a REUSED connection (the
    peer closed a keep-alive socket between requests) retries once
    on a fresh connection and counts/logs the failover — never a
    silent extra retry burned from the caller's bounded ladder.
    POSTs never ride a reused connection at all: a replayed task
    submit on a half-dead socket could double-create a task, and
    submits are rare next to fetches.

Reused-connection requests are metered onto the thread-bound
transfer sink's `exchange_fetch_reused_conns` registry counter
(exec/counters.py) plus module process totals for /metrics.
"""

from __future__ import annotations

import http.client
import io
import logging
import urllib.error
from typing import Dict, List, Optional, Tuple
from urllib.parse import urlsplit

from presto_tpu.exec import xfer as XF
from presto_tpu.obs.sanitizer import make_lock, register_owner

_LOG = logging.getLogger("presto_tpu.dist.connpool")

# process-lifetime totals (the dist/serde.py `_TOTALS` pattern)
_TOTALS = {"exchange_fetch_reused_conns": 0, "exchange_pool_failovers": 0}

# bound the response bytes close() will drain to recycle a
# connection; anything larger just closes the socket
_DRAIN_LIMIT = 1 << 16


class _PooledResponse:
    """One in-flight response bound to its pooled connection. Reading
    to EOF (or closing with only a small remainder) returns the
    connection to the pool; anything irregular closes it."""

    def __init__(self, pool: "ConnectionPool", key, conn, resp):
        self._pool = pool
        self._key = key
        self._conn = conn
        self._resp = resp
        self._released = False

    @property
    def status(self) -> int:
        return self._resp.status

    @property
    def headers(self):
        return self._resp.headers

    def read(self, amt: Optional[int] = None) -> bytes:
        return self._resp.read() if amt is None else self._resp.read(amt)

    def close(self) -> None:
        if self._released:
            return
        self._released = True
        reusable = False
        try:
            if not self._resp.isclosed():
                left = self._resp.length
                if left is not None and left <= _DRAIN_LIMIT:
                    self._resp.read()
            reusable = self._resp.isclosed() and not self._resp.will_close
        except (OSError, http.client.HTTPException):
            reusable = False
        if reusable:
            self._pool._give(self._key, self._conn)
        else:
            self._conn.close()

    def __enter__(self) -> "_PooledResponse":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ConnectionPool:
    """Per-destination keep-alive connection free-lists."""

    # tally rebinds happen under _lock (obs/sanitizer.py owner check)
    _shared_attrs = ("reused_total", "failover_total")

    def __init__(self, max_per_dest: int = 4):
        self.max_per_dest = max_per_dest
        self._conns: Dict[Tuple[str, str], List] = {}
        self.reused_total = 0
        self.failover_total = 0
        self._lock = make_lock("dist.connpool.ConnectionPool._lock")
        register_owner(self, lock_attrs=("_lock",))

    # ------------------------------------------------------ free list
    def _take(self, key):
        with self._lock:
            lst = self._conns.get(key)
            if lst:
                return lst.pop()
        return None

    def _give(self, key, conn) -> None:
        with self._lock:
            lst = self._conns.setdefault(key, [])
            if len(lst) < self.max_per_dest:
                lst.append(conn)
                return
        conn.close()  # over cap: closed OUTSIDE the lock

    def _count_reuse(self) -> None:
        with self._lock:
            self.reused_total += 1
        _TOTALS["exchange_fetch_reused_conns"] += 1
        sink = XF.current_sink()
        count = getattr(sink, "count_reused_conn", None)
        if count is not None:
            count()

    def _count_failover(self, key, err) -> None:
        with self._lock:
            self.failover_total += 1
        _TOTALS["exchange_pool_failovers"] += 1
        _LOG.warning(
            "pooled connection to %s://%s failed (%s); retrying once "
            "on a fresh connection", key[0], key[1], err)

    # -------------------------------------------------------- request
    def request(self, url: str, *, method: str = "GET",
                data: Optional[bytes] = None, headers=(),
                timeout: float = 60.0) -> _PooledResponse:
        split = urlsplit(url)
        key = (split.scheme or "http", split.netloc)
        path = split.path or "/"
        if split.query:
            path += "?" + split.query
        hdrs = dict(headers)
        # a replayed POST on a half-dead keep-alive socket could
        # reach the server twice — submits always open fresh
        conn = self._take(key) if data is None else None
        reused = conn is not None
        while True:
            fresh = conn is None
            if fresh:
                cls = (http.client.HTTPSConnection
                       if key[0] == "https" else http.client.HTTPConnection)
                conn = cls(key[1], timeout=timeout)
            try:
                if conn.sock is not None:
                    conn.sock.settimeout(timeout)
                else:
                    conn.timeout = timeout
                conn.request(method, path, body=data, headers=hdrs)
                resp = conn.getresponse()
                break
            except (http.client.HTTPException, ConnectionError,
                    OSError) as e:
                conn.close()
                conn = None
                if reused and fresh is False:
                    # loud fallback: stale keep-alive, not a peer
                    # failure — retry once without burning one of the
                    # caller's bounded transport retries
                    self._count_failover(key, e)
                    reused = False
                    continue
                raise urllib.error.URLError(e) from e
        if reused:
            self._count_reuse()
        if resp.status >= 400:
            # urlopen contract: error statuses raise, with code/
            # headers/body intact for X-Task-Error and 410 handling
            try:
                body = resp.read()
                reusable = resp.isclosed() and not resp.will_close
            except (OSError, http.client.HTTPException):
                body, reusable = b"", False
            if reusable:
                self._give(key, conn)
            else:
                conn.close()
            raise urllib.error.HTTPError(
                url, resp.status, resp.reason, resp.headers,
                io.BytesIO(body))
        return _PooledResponse(self, key, conn, resp)

    def close_all(self) -> None:
        with self._lock:
            doomed = [c for lst in self._conns.values() for c in lst]
            self._conns.clear()
        for c in doomed:  # socket closes OUTSIDE the lock
            c.close()


_POOL = ConnectionPool()


def request(url: str, *, method: str = "GET",
            data: Optional[bytes] = None, headers=(),
            timeout: float = 60.0) -> _PooledResponse:
    """Issue one HTTP request through THE process-shared pool."""
    return _POOL.request(url, method=method, data=data,
                         headers=headers, timeout=timeout)


def pool_totals() -> dict:
    """Process-lifetime reuse/failover totals, for the /metrics
    overlay."""
    return dict(_TOTALS)


def reset_pool() -> None:
    """Close every idle pooled connection (tests, shutdown)."""
    _POOL.close_all()
