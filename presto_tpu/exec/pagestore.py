"""Materialized, restreamable intermediate pages.

Reference: presto-main operator/PagesIndex.java (append-only page store
shared across probe passes) and spiller/FileSingleStreamSpiller.java
(serialized pages staged out of memory and read back per merge pass).

The TPU translation has two tiers:

- tier="device": the page list stays resident in HBM. Restreaming is
  free and involves no transfers; every individual page remains small
  (page-capacity granularity), which matters because the XLA:TPU
  runtime on this host faults kernels touching >=~4M-row buffers — a
  page LIST sidesteps that while a single concatenated buffer would
  not.
- tier="host": pages are pulled to host RAM as numpy pytrees
  (jax.device_get) and re-staged with device_put on each stream() —
  the HBM->host-RAM spill of SURVEY §6.4. This is what lets a
  partitioned operator consume an intermediate larger than device
  memory without recomputing the subplan that produced it.
- tier="disk": each page's array leaves write to one .npz file in a
  per-store temp directory (the treedef and static aux — types,
  dictionaries — are tiny and stay in RAM); stream() re-reads and
  re-stages. The FileSingleStreamSpiller analog proper: at SF100 a
  partitioned join's materialized side can exceed host RAM (SURVEY
  §6.4 sizes SF100 lineitem at ~80 GB raw). Files are deleted on
  close()/GC.

Stores are owned by the Executor per query attempt (capacity-boost
retries invalidate them — cached pages may embed overflowed results).
Tier selection is governed: beyond the explicit host/disk spill
thresholds, the device-memory budget (exec/membudget.py) routes any
materialization that cannot stay HBM-resident to the host tier, and
past several budgets' worth to the disk tier — the overflow home that
lets SF100-scale partitioned state exceed both HBM and host RAM.

Shape contract (exec/shapes.py): stores preserve page shapes exactly
across tiers — a restreamed page re-enters the very programs its
first pass compiled. Callers size everything that feeds a store
(grace-partition chunks, compacted build pieces, fold accumulators)
through the shared bucket ladder, so spilled intermediates never
reintroduce off-ladder shapes on the restream path.
"""

from __future__ import annotations

import atexit
import os
import shutil
import tempfile
from typing import Iterator, List, Optional, Set

import jax
import numpy as np

from presto_tpu.exec import xfer as XF
from presto_tpu.page import Page

# Spill directories created by THIS process, removed on close() and —
# as a backstop for paths that bypass close() (a killed query thread, a
# store leaked past interpreter teardown ordering) — swept at process
# exit. Dir names embed the owning pid (presto_tpu_spill_<pid>_...) so
# sweep_stale_spill_dirs can reclaim leftovers of DEAD processes
# without ever touching a live sibling's spill.
_LIVE_DIRS: Set[str] = set()
_SWEPT_ROOTS: Set[str] = set()


@atexit.register
def _exit_sweep() -> None:  # pragma: no cover - interpreter teardown
    for d in list(_LIVE_DIRS):
        shutil.rmtree(d, ignore_errors=True)
    _LIVE_DIRS.clear()


def sweep_stale_spill_dirs(root: Optional[str] = None) -> int:
    """Remove presto_tpu_spill_* dirs under ``root`` (default: the
    system temp dir) whose embedded owner pid is no longer alive —
    leftovers of crashed/killed engine processes. Returns the number of
    directories removed. Live processes' dirs (including ours) are
    never touched."""
    root = root or tempfile.gettempdir()
    removed = 0
    try:
        entries = os.listdir(root)
    except OSError:
        return 0
    for name in entries:
        if not name.startswith("presto_tpu_spill_"):
            continue
        pid_part = name[len("presto_tpu_spill_"):].split("_", 1)[0]
        if not pid_part.isdigit():
            continue  # pre-pid-tagged layout: ownership unknowable
        pid = int(pid_part)
        if pid == os.getpid():
            continue
        try:
            os.kill(pid, 0)
            continue  # owner alive
        except ProcessLookupError:
            pass
        except OSError:
            continue  # owned by another user / undeterminable
        shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        removed += 1
    return removed


class PageStore:
    """Append-once, stream-many page materialization."""

    def __init__(self, tier: str = "device",
                 spill_dir: Optional[str] = None):
        assert tier in ("device", "host", "disk"), tier
        self.tier = tier
        self._pages: List = []
        self.bytes = 0
        self.page_count = 0
        self._dir: Optional[str] = None
        if tier == "disk":
            root = spill_dir or None
            # opportunistic stale-dir sweep, once per root per process
            key = root or tempfile.gettempdir()
            if key not in _SWEPT_ROOTS:
                _SWEPT_ROOTS.add(key)
                sweep_stale_spill_dirs(key)
            self._dir = tempfile.mkdtemp(
                prefix=f"presto_tpu_spill_{os.getpid()}_", dir=root
            )
            _LIVE_DIRS.add(self._dir)

    def put(self, page: Page) -> None:
        from presto_tpu.exec.executor import page_bytes

        self.bytes += page_bytes(page)
        self.page_count += 1
        if self.tier == "host":
            # one bounded D2H transfer per page; an earlier TPU runtime
            # degrades post-D2H kernel launches, so callers only pick
            # the host tier when the intermediate cannot stay resident
            self._pages.append(XF.to_host(page, label="spill-host"))
        elif self.tier == "disk":
            host = XF.to_host(page, label="spill-disk")
            leaves, treedef = jax.tree_util.tree_flatten(host)
            path = os.path.join(self._dir, f"p{self.page_count}.npz")
            np.savez(path, **{f"a{i}": leaf
                              for i, leaf in enumerate(leaves)})
            self._pages.append((path, treedef, len(leaves)))
        else:
            self._pages.append(page)

    def put_host(self, host_page) -> None:
        """Append an ALREADY-HOST page pytree with no device-sync API
        in the path (put() routes through xfer.to_host, which concheck
        treats as the device sync it is). The result-cache demotion
        plane runs under the store's lock — concheck's
        blocking-under-lock rule is why this exists: moving
        host_pages() output between tiers must never touch the device."""
        from presto_tpu.exec.executor import page_bytes

        self.bytes += page_bytes(host_page)
        self.page_count += 1
        if self.tier == "disk":
            leaves, treedef = jax.tree_util.tree_flatten(host_page)
            path = os.path.join(self._dir, f"p{self.page_count}.npz")
            np.savez(path, **{f"a{i}": leaf
                              for i, leaf in enumerate(leaves)})
            self._pages.append((path, treedef, len(leaves)))
        else:
            self._pages.append(host_page)

    # ---------------------------------------------------- byte plane
    # The spooled-exchange tier (dist/scheduler.py) stores SERIALIZED
    # pages — the worker's wire blobs — through the same host/disk
    # tiers and spill-dir lifecycle as page pytrees: host tier keeps
    # the bytes resident, disk tier writes one file per blob into the
    # pid-tagged spill dir (swept on close/exit like every spill file).
    # A store holds pages OR blobs, never both.

    def put_bytes(self, blob: bytes) -> None:
        self.bytes += len(blob)
        self.page_count += 1
        if self.tier == "disk":
            path = os.path.join(self._dir, f"b{self.page_count}.bin")
            with open(path, "wb") as f:
                f.write(blob)
            self._pages.append(path)
        else:  # device/host: resident bytes (there is no device blob)
            self._pages.append(blob)

    def blob_at(self, i: int) -> bytes:
        """Random access for token-indexed spool fetch (the consumer's
        at-least-once protocol re-reads arbitrary tokens)."""
        entry = self._pages[i]
        if isinstance(entry, str):
            with open(entry, "rb") as f:
                return f.read()
        return entry

    def host_pages(self) -> List:
        """Host-side page pytrees WITHOUT device staging — the result
        cache's replay/demotion plane: demoting a host-tier store to a
        disk-tier one must not round-trip every page through the
        device (stream() device_puts), and cache replay wants a safe
        host snapshot it can stage lazily. Host tier returns the
        retained pytrees; disk tier loads its spill files; device tier
        returns the device pages as-is (callers on that tier want
        them resident anyway)."""
        if self.tier == "disk":
            out = []
            for path, treedef, n in self._pages:
                with np.load(path) as z:
                    leaves = [z[f"a{i}"] for i in range(n)]
                out.append(
                    jax.tree_util.tree_unflatten(treedef, leaves)
                )
            return out
        return list(self._pages)

    def stream(self) -> Iterator[Page]:
        if self.tier == "host":
            for p in self._pages:
                yield XF.to_device(p, label="restream")
        elif self.tier == "disk":
            for path, treedef, n in self._pages:
                with np.load(path) as z:
                    leaves = [z[f"a{i}"] for i in range(n)]
                yield XF.to_device(
                    jax.tree_util.tree_unflatten(treedef, leaves),
                    label="restream",
                )
        else:
            yield from self._pages

    def close(self) -> None:
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            _LIVE_DIRS.discard(self._dir)
            self._dir = None
        self._pages = []

    def __del__(self):  # best-effort file cleanup
        try:
            self.close()
        except Exception:  # noqa: BLE001 - __del__ must never raise
            pass
