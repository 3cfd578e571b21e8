"""Spooled-exchange data plane helpers for the stage-DAG scheduler.

Reference: presto-main operator/PartitionedOutputOperator.java (the
producer half of a hash-repartition exchange: route each row to a
partition buffer by hash(keys) % P) and operator/ExchangeClient.java
(the consumer half: token-acked page fetch from every producer task).
The Project-Tardigrade twist: partition buffers are SPOOLED — they
outlive the producing task's execution on the worker (PageStore
host/disk tiers, exec/pagestore.py), so a lost downstream task replays
from its upstream spools instead of failing the query.

Two partitioning tiers (ISSUE 13). The HOST tier below is numpy on
already-device_get pages: the split happens at the serialization
boundary where the page has left the device anyway (SURVEY §6.8: HTTP
shapes survive only at the pod boundary). The DEVICE tier
(`device_partition_pages`) computes the SAME splitmix64 value-hash as
a jitted kernel and compacts each partition to a ladder-bucket
capacity on device — pages never cross to host at the exchange, and
the worker spool holds device Pages that materialize to host bytes
LAZILY (`spool_blob`) only when a replay or a DCN-remote consumer
actually fetches over HTTP. Parity between the tiers is test-pinned
per key type incl. the NULL sentinel (tests/test_device_exchange.py);
skew still rides the boosted-retry ladder — a partition overflowing
its bucket raises the deferred flag.

Client split (deliberate, not drift): `fetch_spool_blobs` below is the
WORKER-side exchange client — plain token-dedupe fetch between stage
tasks. The COORDINATOR's drain of final stages keeps using
`dcn.DcnRunner._fetch_pages`, which layers the PR-5 resume machinery
(rolling sha256 of consumed bytes + byte-identical prefix verification
after a replay) that worker-to-worker ingest does not need — a
re-dispatched consumer restarts its stream from token 0. Both speak
the same `/v1/task/{id}/results/{token}?part=p` protocol.

Hash discipline: partitioning needs only SELF-consistency across the
two sides of one exchange (co-partitioned join sides / all producers
of one aggregation exchange), not agreement with the device kernels'
hash. Keys hash from VALUE encodings — int64 bit-views, IEEE-754
bit-views with -0.0/NaN normalization, dictionary VALUES (not codes) —
mixed with a splitmix64 finalizer and the reference's 31*h+x combiner,
so equal SQL values land in the same partition regardless of which
producer task emitted them. NULL keys hash to a fixed sentinel (every
null row lands on a deterministic partition — inner join keys never
match NULL, and NULL group keys co-locate).
"""

from __future__ import annotations

import functools
import json
import struct
import time
import urllib.error
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.exec import shapes as SH
from presto_tpu.exec import xfer as XF
from presto_tpu.ops.compact import compact_indices, scatter_column
from presto_tpu.ops.hashing import xxhash64_host
from presto_tpu.page import Block, Page

_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_NULL_SENTINEL = np.uint64(0x9E3779B185EBCA87)
_NAN_KEY = np.uint64(0xFFFFFFFFFFFFFFFF)
_C31 = np.uint64(31)


def _mix64(h: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (vectorized, natural uint64 wraparound)."""
    with np.errstate(over="ignore"):
        h = (h ^ (h >> np.uint64(30))) * _MIX1
        h = (h ^ (h >> np.uint64(27))) * _MIX2
        return h ^ (h >> np.uint64(31))


@functools.lru_cache(maxsize=64)
def _dict_value_hashes(dictionary) -> np.ndarray:
    """Per-code value hashes of one Dictionary, memoized — dictionaries
    are shared across every page of a scan, and Dictionary hashes by
    CONTENT, so the Python-level hashing loop runs once per distinct
    dictionary instead of once per page per key channel."""
    return np.array(
        [xxhash64_host(repr(v).encode()) for v in dictionary.values],
        dtype=np.uint64,
    )


def _block_value_u64(blk: Block) -> np.ndarray:
    """Per-row uint64 VALUE encoding of one key block (host numpy)."""
    data = blk.data
    if isinstance(data, tuple):
        # long decimal (hi, lo): combine the two words
        arrs = [XF.np_host(d) for d in data]
        if any(a.ndim != 1 for a in arrs):
            raise TypeError(
                "collect-state blocks cannot be exchange partition keys"
            )
        h = np.zeros(arrs[0].shape[0], dtype=np.uint64)
        with np.errstate(over="ignore"):
            for a in arrs:
                h = h * _C31 + a.astype(np.int64).view(np.uint64)
        return h
    arr = XF.np_host(data)
    if blk.dictionary is not None:
        # hash the dictionary VALUES, not the table-local codes —
        # producer tasks with different dictionaries stay consistent
        vh = _dict_value_hashes(blk.dictionary)
        if len(vh) == 0:
            return np.zeros(arr.shape[0], dtype=np.uint64)
        codes = np.clip(arr.astype(np.int64), 0, len(vh) - 1)
        return vh[codes]
    if arr.dtype == np.bool_:
        return arr.astype(np.uint64)
    if np.issubdtype(arr.dtype, np.floating):
        f = arr.astype(np.float64)
        f = np.where(f == 0.0, 0.0, f)  # -0.0 == +0.0 (SQL equality)
        bits = f.view(np.uint64)
        return np.where(np.isnan(f), _NAN_KEY, bits)
    return arr.astype(np.int64).view(np.uint64)


def row_hash_u64(page: Page, keys: Sequence[int]) -> np.ndarray:
    """Per-row partition hash over the key channels (31*h + mix(col),
    the reference's CombineHashFunction shape over splitmix-dispersed
    column encodings)."""
    cap = XF.np_host(page.valid).shape[0]
    h = np.zeros(cap, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for k in keys:
            blk = page.block(k)
            col = _mix64(_block_value_u64(blk))
            if blk.nulls is not None:
                col = np.where(XF.np_host(blk.nulls), _NULL_SENTINEL,
                               col)
            h = h * _C31 + col
    return _mix64(h)


def take_rows_host(page: Page, idx: np.ndarray) -> Page:
    """Compact the given row indices of a HOST page into a fresh page
    whose capacity sits on the shapes.py bucket ladder (restreamed
    exchange pages must not mint off-ladder program shapes
    downstream)."""
    n = len(idx)
    cap = SH.bucket(max(n, 1))
    pad = np.zeros(cap, dtype=np.int64)
    pad[:n] = idx
    blocks: List[Block] = []
    for blk in page.blocks:
        if isinstance(blk.data, tuple):
            data = tuple(XF.np_host(d)[pad] for d in blk.data)
        else:
            data = XF.np_host(blk.data)[pad]
        nulls = (XF.np_host(blk.nulls)[pad]
                 if blk.nulls is not None else None)
        blocks.append(Block(data=data, type=blk.type, nulls=nulls,
                            dictionary=blk.dictionary))
    valid = np.zeros(cap, dtype=bool)
    valid[:n] = True
    return Page(blocks=tuple(blocks), valid=valid)


def partition_host_page(
    page: Page, keys: Sequence[int], nparts: int
) -> List[Tuple[int, Page]]:
    """Split one host page into per-partition compacted pages.
    Partitions with zero rows are skipped (deterministically — replay
    regenerates the same skips, so token sequences stay stable)."""
    valid = XF.np_host(page.valid)
    if nparts <= 1:
        return [(0, page)] if valid.any() else []
    part = (row_hash_u64(page, keys) % np.uint64(nparts)).astype(
        np.int64)
    out: List[Tuple[int, Page]] = []
    for p in range(nparts):
        idx = np.nonzero(valid & (part == p))[0]
        if len(idx):
            out.append((p, take_rows_host(page, idx)))
    return out


# ------------------------------------------------- device partitioning
def _mix64_dev(h: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer traced in jnp — bit-identical to the host
    `_mix64` (uint64 multiplies wrap in XLA exactly like numpy's)."""
    h = (h ^ (h >> jnp.uint64(30))) * jnp.uint64(_MIX1)
    h = (h ^ (h >> jnp.uint64(27))) * jnp.uint64(_MIX2)
    return h ^ (h >> jnp.uint64(31))


def _block_value_u64_dev(blk: Block, vh) -> jnp.ndarray:
    """Traced mirror of `_block_value_u64`: per-row uint64 VALUE
    encoding of one key block. `vh` is the block's staged dictionary
    value-hash LUT (device uint64 array) or None."""
    data = blk.data
    if isinstance(data, tuple):
        # long decimal (hi, lo): int64 -> uint64 astype wraps two's-
        # complement, the same bits .view reinterprets on the host
        h = jnp.zeros(data[0].shape[0], dtype=jnp.uint64)
        for a in data:
            h = h * jnp.uint64(_C31) + a.astype(jnp.int64).astype(
                jnp.uint64)
        return h
    if vh is not None:
        if vh.shape[0] == 0:
            return jnp.zeros(data.shape[0], dtype=jnp.uint64)
        codes = jnp.clip(data.astype(jnp.int64), 0, vh.shape[0] - 1)
        return vh[codes]
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64)
    if jnp.issubdtype(data.dtype, jnp.floating):
        f = data.astype(jnp.float64)
        f = jnp.where(f == 0.0, 0.0, f)  # -0.0 == +0.0 (SQL equality)
        bits = jax.lax.bitcast_convert_type(f, jnp.uint64)
        return jnp.where(jnp.isnan(f), jnp.uint64(_NAN_KEY), bits)
    return data.astype(jnp.int64).astype(jnp.uint64)


def device_row_hash_u64(page: Page, keys: Sequence[int],
                        dict_luts=()) -> jnp.ndarray:
    """Traced mirror of `row_hash_u64`: 31*h + mix(col) over splitmix-
    dispersed column encodings, NULL keys to the fixed sentinel.
    `dict_luts` aligns with `keys` (device LUT or None per key)."""
    luts = tuple(dict_luts) or (None,) * len(keys)
    h = jnp.zeros(page.valid.shape[0], dtype=jnp.uint64)
    for k, vh in zip(keys, luts):
        blk = page.block(k)
        col = _mix64_dev(_block_value_u64_dev(blk, vh))
        if blk.nulls is not None:
            col = jnp.where(blk.nulls, jnp.uint64(_NULL_SENTINEL), col)
        h = h * jnp.uint64(_C31) + col
    return _mix64_dev(h)


def device_partition_pages(
    ex, page: Page, keys: Sequence[int], nparts: int,
    with_counts: bool = False,
) -> List[Tuple[int, Page]]:
    """Device-tier `partition_host_page`: ONE jitted program computes
    every partition assignment and compacts all `nparts` output pages
    to their ladder-bucket capacity without the page ever crossing to
    host (ISSUE 13 — the d2h/h2d exchange pair deletes).
    Every partition is emitted (empties carry all-False validity) so a
    replayed task regenerates an identical page sequence. The
    OR-reduced per-partition overflow flag joins the executor's
    deferred ladder: skew degrades to a boosted retry, exactly like
    the host tier's take_rows_host bucket.

    ``with_counts=True`` (the spool-stats plane, ISSUE 15) also
    returns the exact per-partition row counts — computed INSIDE the
    same program (the compaction already counts them) and pulled as
    one nparts-long vector through the metered choke point, so the
    stats cost is a handful of d2h bytes per page, never a second
    kernel or a whole-mask pull. Return shape then is
    ``(pairs, counts_np)``."""
    cap_in = page.valid.shape[0]
    if nparts <= 1:
        if with_counts:
            v = page.valid
            n = (int(XF.np_host(page.num_rows(), label="spool-stats"))
                 if isinstance(v, jax.Array)
                 else int(XF.np_host(v).sum()))
            return [(0, page)], np.asarray([n], dtype=np.int64)
        return [(0, page)]
    # host-resident input (a cache replay at the fragment root) stages
    # through the metered choke point; device pages pass through free
    page = XF.to_device(page, label="spool-stage")
    dicts = tuple(page.block(k).dictionary for k in keys)
    luts = tuple(
        XF.to_device(_dict_value_hashes(d), label="dict-hash")
        if d is not None else None
        for d in dicts
    )
    boost = ex._capacity_boost
    cap = SH.exchange_partition_cap(cap_in, nparts, boost)

    def body(pg: Page, *vhs):
        vh_by_key = iter(vhs)
        full = tuple(next(vh_by_key) if d is not None else None
                     for d in dicts)
        h = device_row_hash_u64(pg, keys, full)
        part = (h % jnp.uint64(nparts)).astype(jnp.int32)
        outs = []
        nums = []
        overflow = jnp.asarray(False)
        for p in range(nparts):
            mask = pg.valid & (part == p)
            targets, out_valid, num = compact_indices(mask, cap)
            blocks = []
            for blk in pg.blocks:
                if isinstance(blk.data, tuple):
                    data = tuple(scatter_column(d, targets, cap)
                                 for d in blk.data)
                else:
                    data = scatter_column(blk.data, targets, cap)
                nulls = (scatter_column(blk.nulls, targets, cap)
                         if blk.nulls is not None else None)
                blocks.append(blk.with_data(data, nulls=nulls))
            outs.append(Page(blocks=tuple(blocks), valid=out_valid))
            nums.append(num)
            overflow = overflow | (num > cap)
        if with_counts:
            return tuple(outs), jnp.stack(nums), overflow
        return tuple(outs), overflow

    fn = ex._jit(
        ("dev_repart", tuple(keys), nparts, cap, cap_in, dicts,
         with_counts),
        body,
    )
    out = fn(page, *[v for v in luts if v is not None])
    if with_counts:
        outs, nums, overflow = out
        ex._pending_overflow.append(overflow)
        counts = XF.np_host(nums, label="spool-stats").astype(np.int64)
        # counts are EXACT published rows: an overflowing partition
        # never publishes (the deferred flag re-runs the attempt and
        # on_attempt resets the spool), so clamping to the landing cap
        # only guards the transient pre-retry value
        return list(enumerate(outs)), np.minimum(counts, cap)
    outs, overflow = out
    ex._pending_overflow.append(overflow)
    return list(enumerate(outs))


def spool_blob(page: Page) -> bytes:
    """Materialize one spooled page to wire bytes — THE lazy host
    materialization of the device-resident spool tier. Called only
    when host bytes are actually needed (an HTTP fetch from a
    DCN-remote consumer or a replay, or spool budget demotion); the
    d2h is metered at the choke point. Deterministic, so a re-fetch
    or a replayed prefix serializes byte-identically."""
    from presto_tpu.dist import serde

    return serde.serialize_page(XF.to_host(page, label="spool-blob"))


# ------------------------------------------------------------ client
class SourceTaskFailed(RuntimeError):
    """The upstream task itself failed (X-Task-Error): deterministic,
    re-dispatching the CONSUMER alone will not help."""


class SourceLost(RuntimeError):
    """An upstream task's spool is unreachable (node death): the
    scheduler must replay the upstream task before the consumer can
    make progress. The message carries the placement for diagnosis."""

    def __init__(self, uri: str, task_id: str, cause: str):
        super().__init__(
            f"[source-lost {uri} {task_id}] {cause}")
        self.uri = uri
        self.task_id = task_id


# the default bounded in-flight-bytes window for one streaming fetch
# response (ISSUE 16): the server packs consecutive page frames into
# one response only up to this many bytes, and the client decodes
# frame-at-a-time off the socket — so consumer host memory per edge
# stays O(window), not O(partition), while fetch overlaps decode.
FETCH_WINDOW_BYTES = 4 << 20


def pack_frames(blobs: Sequence[bytes]) -> bytes:
    """Server-side framing of a streamed results response: each page
    blob rides as `<q len | bytes>` so the consumer can decode pages
    incrementally off the socket (dedupe-by-token still holds — the
    token advances one per frame on both ends)."""
    out = bytearray()
    for b in blobs:
        out.extend(struct.pack("<q", len(b)))
        out.extend(b)
    return bytes(out)


def _read_exact(r, n: int, *, eof_ok: bool = False) -> Optional[bytes]:
    """Read exactly n bytes from a response; None at a clean EOF when
    `eof_ok` (frame boundary). A mid-frame EOF raises ConnectionError
    — the transport-retry ladders treat it like any broken fetch."""
    chunks = []
    got = 0
    while got < n:
        c = r.read(n - got)
        if not c:
            if eof_ok and got == 0:
                return None
            raise ConnectionError(
                f"truncated page frame: got {got} of {n} bytes")
        chunks.append(c)
        got += len(c)
    return b"".join(chunks)


def iter_response_frames(r) -> Iterator[bytes]:
    """Incremental client half of `pack_frames`: yield each page blob
    as it comes off the socket, holding at most ONE frame in memory."""
    while True:
        head = _read_exact(r, 8, eof_ok=True)
        if head is None:
            return
        (ln,) = struct.unpack("<q", head)
        if ln < 0:
            raise ConnectionError(f"corrupt page-frame length {ln}")
        yield _read_exact(r, ln)


def fetch_spool_blobs(
    uri: str,
    task_id: str,
    part: int,
    *,
    start_token: int = 0,
    retries: int = 3,
    backoff_s: float = 0.1,
    timeout: float = 60.0,
    deadline: Optional[float] = None,
    window_bytes: Optional[int] = None,
) -> Iterator[bytes]:
    """Token-acked streaming fetch of one spool partition
    (at-least-once + dedupe-by-token, the HttpPageBufferClient
    protocol with the partition dimension added). Each request drains
    up to `window_bytes` of consecutive page frames on a pooled
    keep-alive connection (dist/connpool.py); the token advances one
    per yielded frame, so a mid-stream transport failure resumes at
    the first unconsumed page. Raises SourceTaskFailed on
    X-Task-Error, SourceLost after bounded transport retries."""
    from presto_tpu.dist import connpool as CONNPOOL

    token = start_token
    window = FETCH_WINDOW_BYTES if window_bytes is None \
        else int(window_bytes)
    while True:
        attempt = 0
        while True:
            if deadline is not None and time.monotonic() > deadline:
                from presto_tpu.exec.executor import (
                    QueryDeadlineExceeded,
                )

                raise QueryDeadlineExceeded(
                    "query exceeded query_max_run_time in a spool "
                    "fetch"
                )
            try:
                with CONNPOOL.request(
                    f"{uri}/v1/task/{task_id}/results/{token}"
                    f"?part={part}&max={window}", timeout=timeout,
                ) as r:
                    if r.status == 204:
                        if r.headers.get("X-Done") == "1":
                            return
                        break  # long-poll timeout; re-ask same token
                    for body in iter_response_frames(r):
                        token += 1
                        yield body
                    break
            except urllib.error.HTTPError as e:
                if e.headers.get("X-Task-Error"):
                    try:
                        msg = json.loads(e.read().decode()).get(
                            "error", "")
                    except (ValueError, OSError):
                        msg = str(e)
                    raise SourceTaskFailed(
                        f"upstream task {task_id} on {uri} FAILED: "
                        f"{msg}"
                    ) from e
                if e.code == 410:
                    # the partition was acked/released: deterministic
                    # and permanent — retrying or replaying the
                    # (healthy) producer node would not bring the
                    # spool back
                    raise SourceTaskFailed(
                        f"spool partition {part} of task {task_id} on "
                        f"{uri} was already released (acked) — the "
                        f"scheduler consumed it before this fetch"
                    ) from e
                attempt += 1
                if attempt > retries:
                    raise SourceLost(uri, task_id, str(e)) from e
                time.sleep(backoff_s * attempt)
            except (urllib.error.URLError, ConnectionError,
                    OSError) as e:
                attempt += 1
                if attempt > retries:
                    raise SourceLost(uri, task_id, str(e)) from e
                time.sleep(backoff_s * attempt)


def local_source_pages(uri: str, task_id: str,
                       part: int) -> Optional[Iterator[Page]]:
    """Mesh-local exchange fast path (ISSUE 13): when `uri` names a
    task runtime in THIS process and the task has finished, return an
    iterator over its spooled partition Pages — no HTTP, no serde for
    lazy entries, and no h2d re-stage for device-resident spools.
    None = not local (or not yet done): the caller falls back to the
    metered HTTP fetch, which also provides the long-poll wait and
    the fault-injection surface.

    Race discipline: the released/done checks AND the entry-list
    snapshot happen under the task lock, so a concurrent ack/release
    can never yield a silently-empty stream (the HTTP path's 410
    contract); pages then materialize ONE AT A TIME outside the lock
    — blob entries whose store was closed mid-iteration raise
    SourceTaskFailed loudly, and lazy Page entries stay valid by
    reference regardless of release."""
    from presto_tpu.server.worker import local_runtime

    rt = local_runtime(uri)
    if rt is None:
        return None
    task = rt.get_task(task_id)
    if task is None:
        return None
    with task.lock:
        done, err = task.done, task.error
        spool = task.spool
        released = task.part_released(part)
        entries = (
            list(spool.parts[part]._entries)
            if (done and not err and not released and spool is not None
                and part < len(spool.parts))
            else []
        )
    if err:
        raise SourceTaskFailed(
            f"upstream task {task_id} on {uri} FAILED: {err}")
    if released:
        raise SourceTaskFailed(
            f"spool partition {part} of task {task_id} on {uri} was "
            f"already released (acked) — the scheduler consumed it "
            f"before this fetch")
    if not done or spool is None:
        return None

    def gen() -> Iterator[Page]:
        from presto_tpu.dist import serde

        for entry in entries:
            if entry[0] == "page":
                yield entry[1]
                continue
            store, i = entry
            try:
                blob = store.blob_at(i)
            except (OSError, IndexError) as e:
                raise SourceTaskFailed(
                    f"spool partition {part} of task {task_id} on "
                    f"{uri} was released (acked) during a mesh-local "
                    f"read") from e
            yield serde.deserialize_page(blob)

    return gen()


def iter_source_pages(
    spec: dict,
    *,
    retries: int = 3,
    backoff_s: float = 0.1,
    deadline: Optional[float] = None,
    on_local=None,
):
    """Worker-side exchange ingest: yield deserialized pages of one
    RemoteSource edge — partition `spec['partition']` of every
    producer task, in payload order (deterministic, so a re-dispatched
    consumer regenerates an identical stream from identical spools).
    Same-process producers serve their spooled Pages directly
    (`local_source_pages`; `on_local` fires once per edge task so the
    consumer's executor can count mesh_local_exchanges).

    An adaptive BROADCAST READ of a repartitioned spool (ISSUE 15)
    passes ``spec['partitions']`` — an explicit partition list; the
    consumer drains every listed partition of every producer task
    (their union is the full producer output, so a join build flipped
    to broadcast after its producer already spooled P hash partitions
    reads exactly the rows a broadcast spool would have held)."""
    from presto_tpu.dist import serde

    parts = [int(p) for p in (spec.get("partitions")
                              or (spec.get("partition", 0),))]
    for t in spec["tasks"]:
        for part in parts:
            pages = local_source_pages(t["uri"], t["taskId"], part)
            if pages is not None:
                if on_local is not None:
                    on_local()
                yield from pages
                continue
            for blob in fetch_spool_blobs(
                t["uri"], t["taskId"], part, retries=retries,
                backoff_s=backoff_s, deadline=deadline,
            ):
                yield serde.deserialize_page(blob)


def ack_spool(uri: str, task_id: str, part: int,
              timeout: float = 5.0) -> bool:
    """Release one consumed spool partition on the producer (the ack
    half of the fetch/ack protocol). Best-effort: a dead producer has
    nothing left to free."""
    from presto_tpu.dist import connpool as CONNPOOL

    try:
        with CONNPOOL.request(
            f"{uri}/v1/task/{task_id}/spool/{part}", method="DELETE",
            timeout=timeout,
        ) as r:
            r.read()
        return True
    except (urllib.error.URLError, OSError, TimeoutError):
        return False
