"""DistExecutor: interpret a fragmented plan over a jax device Mesh.

Reference: the worker-side execution of exchanges — operator/
PartitionedOutputOperator.java (hash rows -> partition -> serialize ->
HTTP buffer) and operator/ExchangeOperator.java (fetch + deserialize) —
plus LocalExecutionPlanner wiring. TPU-native redesign: a "page" is ONE
global jax.Array per column, sharded row-wise across the mesh
(NamedSharding over axis "d"), and every exchange is an XLA collective
compiled into the neighboring kernel via shard_map:

    repartition -> per-shard bucketing + lax.all_to_all
    broadcast   -> lax.all_gather(tiled) to a replicated page
    gather      -> same collective; semantically the SINGLE partitioning
                   (every device holds the full stream and runs the final
                   stage redundantly — replicated compute is free compared
                   to leaving devices idle)

Shard-local operators reuse the single-device kernels unchanged inside
shard_map bodies — the Driver loop compiled away, the shuffle compiled in.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from presto_tpu import types as T
from presto_tpu.exec import agg_states as S
from presto_tpu.exec import plan as P
from presto_tpu.exec import programs as PG
from presto_tpu.exec import xfer as XF
from presto_tpu.exec.executor import (
    Executor,
    _apply_steps,
    _compact_with_flag,
    _final_agg_page,
    _final_global_agg,
    _merge_compact_flag,
    _merge_leading,
    _next_pow2,
    _null_blocks,
    _partial_agg_page,
    _partial_global_agg,
    _probe_join_page,
    _returning_rows,
    _row_bytes,
    _semi_join_page,
    _topn_merge,
)
from presto_tpu.ops import hashing as H
from presto_tpu.ops import keys as K
from presto_tpu.ops.compact import compact_indices, concat_all, scatter_column
from presto_tpu.ops.sort import sort_page
from presto_tpu.page import Block, Page

SHARDED = "sharded"
REPLICATED = "replicated"


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    # xfercheck: raw-ok - object array of device HANDLES; no bytes cross
    return Mesh(np.array(devs[:n]), ("d",))


class DistExecutor(Executor):
    """Executes plans produced by dist.fragmenter.add_exchanges.

    Page distribution is tracked statically per node ("sharded" over the
    mesh vs "replicated"); replicated subtrees run the inherited single-
    stream code paths (XLA replicates the compute across devices), sharded
    nodes run shard_map-wrapped kernels.
    """

    # an eager program between launches is the pace of a mesh
    # statement, so every program made here returns its page's row
    # count with the page (Executor._jit; _mesh_jit for the shard_map
    # programs): pages() dispatches nothing for the query trace
    launch_counts_rows = True

    def __init__(self, catalogs, mesh: Mesh, **kw):
        super().__init__(catalogs, **kw)
        self.mesh = mesh
        self.D = int(mesh.devices.size)
        self._dist_cache: Dict[int, str] = {}
        # hash_partition_count session property: devices that RECEIVE
        # repartitioned rows (0 = whole mesh). Routing and residue
        # filters share _route_devices so both sides of a partitioned
        # stage agree on the partition function.
        self.hash_partitions = 0

    def _route_devices(self) -> int:
        """Devices used for repartitioned stages (reference:
        hash_partition_count): hash routing targets devices
        0..P-1; the whole mesh still executes the programs."""
        hp = int(self.hash_partitions or 0)
        return min(hp, self.D) if hp > 0 else self.D

    # ------------------------------------------------- memory governor
    def _budget(self) -> int:
        """Mesh-wide device-memory budget: the per-chip share (the
        inherited resolution — one chip's HBM minus headroom, or the
        session's device_memory_budget per chip) times the mesh size.
        The dominant governed buffers — partitioned join builds,
        repartitioned aggregation state — are sharded row-wise across
        the mesh, so the mesh collectively holds D chips' shares.
        Replicated (broadcast) builds are bounded separately by the
        stats-driven broadcast decision, which uses the PER-CHIP share
        (runner._session_dist_options -> fragmenter.broadcast_bytes)."""
        return super()._budget() * self.D

    # -------------------------------------------- collective dispatch
    @staticmethod
    def _fenced(fn):
        """Serialize collective programs on the CPU backend.

        The in-process CPU runtime schedules enqueued executables by
        DATAFLOW READINESS, not dispatch order: two in-flight programs
        that both contain cross-device collectives can start in different
        orders on different virtual devices — device 0 enters program B's
        all-reduce rendezvous while devices 1..7 wait in program A's, and
        the rendezvous aborts after its timeout (seen as rc=134 on
        TPC-DS Q17: its windowed generated-join `psum` interleaved
        with the dim-join pipeline's gathers, "Expected 8 threads to
        join the rendezvous, but only 1 arrived"). Blocking on each
        collective program's outputs before the next one can be
        dispatched enforces ONE consistent execution order across all
        devices. TPU per-device queues execute strictly in dispatch
        order, so the fence is CPU-only and costs hardware nothing — the
        deferred-sync discipline (Executor.__init__) is a TPU-runtime
        concern and unaffected."""
        if jax.default_backend() != "cpu":
            return fn

        def fenced(*args):
            out = fn(*args)
            # xfercheck: raw-ok - sync fence (no copy): pins collective
            jax.block_until_ready(out)  # rendezvous order on CPU
            return out

        return fenced

    def _mesh_jit(self, key, body, in_specs=(PS("d"),),
                  out_specs=PS("d"), fenced=False):
        """THE one place a mesh program is made: ``body`` under
        shard_map over this mesh is the function Executor._jit jits
        under the key's ``d_*`` label, so every call goes through
        exec/programs.launch like a one-device program's (counted,
        timed and annotated on the calling executor). The default
        specs are a shard-local page -> page map; ``fenced`` marks a
        body that holds a cross-device collective (see _fenced).

        The program also returns the row count of the page it makes
        (the body's output is a page or a tuple that begins with one),
        counted in the body: a chip's own sum(valid) under the page's
        spec, so one entry a chip of a sharded page, one of a
        replicated page, and no collective; the host adds them after
        the run (Executor._resolve_row_counts)."""
        page_spec = out_specs[0] if isinstance(out_specs, tuple) \
            else out_specs
        fn = self._jit(key, make=lambda: jax.shard_map(
            _returning_rows(body), mesh=self.mesh, in_specs=in_specs,
            out_specs=(out_specs, page_spec), check_vma=False),
            returns_rows=True)
        return self._fenced(fn) if fenced else fn

    # ---------------------------------------------------------- dist tags
    def dist(self, node: P.PhysicalNode) -> str:
        # keyed by id() with the node itself retained: a bare id key goes
        # stale when a garbage-collected plan node's address is reused by
        # a later plan (observed as flaky distributed-vs-single mismatches)
        key = id(node)
        hit = self._dist_cache.get(key)
        if hit is None or hit[0] is not node:
            self._dist_cache[key] = (node, self._compute_dist(node))
        return self._dist_cache[key][1]

    def _compute_dist(self, node) -> str:
        if isinstance(node, P.TableScan):
            return SHARDED
        if isinstance(node, P.Values):
            return REPLICATED
        if isinstance(node, P.Exchange):
            return SHARDED if node.kind == "repartition" else REPLICATED
        if isinstance(node, (P.HashJoin, P.CrossJoin)):
            return self.dist(node.left)
        if isinstance(node, P.Union):
            return self.dist(node.sources[0])
        children = node.children()
        return self.dist(children[0]) if children else REPLICATED

    # --------------------------------------------- cache residency
    def _cache_subtree_ok(self, node: P.PhysicalNode) -> bool:
        """Mesh-path cache residency (ISSUE 15 satellite, ROADMAP
        item 6 remainder): only REPLICATED subtrees may become cache
        points — their pages are ordinary replicated arrays a host
        replay can reproduce, so a mesh query with an uncacheable
        root (volatile filter, system branch) still caches its
        expensive gathered interior instead of nothing at all.
        Sharded subtrees' pages are mesh-sharded global arrays; a
        host replay could not rebuild their shard layout."""
        return self.dist(node) == REPLICATED

    def _sink_chain_ids(self, node) -> frozenset:
        """Mesh host-sink chain: besides the Output pass-through, a
        gather/broadcast Exchange over an already-REPLICATED source
        is a verbatim pass-through on this executor
        (_exec_exchange yields self.pages(source) unchanged), so a
        cache point below it can serve host pages straight to result
        decode — the mesh-local handoff applied to replays, with
        ZERO h2d/d2h crossings on the hit (transfer-ledger pinned in
        tests/test_result_cache.py)."""
        ids = {id(node)}
        while True:
            if isinstance(node, P.Output):
                node = node.source
            elif (isinstance(node, P.Exchange)
                    and node.kind in ("gather", "broadcast")
                    and self.dist(node.source) == REPLICATED):
                node = node.source
            else:
                break
            ids.add(id(node))
        return frozenset(ids)

    def _stage_replay(self, page: Page) -> Page:
        """Replayed host pages commit as mesh-REPLICATED arrays (not
        device-0 singletons): consumers above a replicated cache
        point may be shard_map programs with replicated in_specs
        (residue repartition, broadcast joins) that require a
        consistent placement across every mesh device."""
        return XF.to_device(
            page, spec=NamedSharding(self.mesh, PS()),
            label="cache-replay")

    # ------------------------------------------------------------- pages
    def _pages_impl(self, node: P.PhysicalNode) -> Iterator[Page]:
        if isinstance(node, P.Exchange):
            yield from self._exec_exchange(node)
            return
        if self.dist(node) == REPLICATED and all(
            self.dist(c) == REPLICATED for c in node.children()
        ):
            yield from super()._pages_impl(node)
            return
        if isinstance(node, (P.Filter, P.Project, P.HashJoin)):
            fused = self._fused_rounds(node)
            if fused is not None:
                yield from fused
                return
        if isinstance(node, P.TableScan):
            yield from self._scan_sharded(node)
            return
        if isinstance(node, P.Filter):
            from presto_tpu.expr.eval import evaluate_filter

            fn = self._mesh_jit(
                ("d_filter", node.predicate),
                lambda page, _pred=node.predicate: evaluate_filter(
                    _pred, page, jnp
                ),
            )
            for page in self.pages(node.source):
                yield fn(page)
            return
        if isinstance(node, P.Project):
            from presto_tpu.exec.executor import _project_page

            fn = self._mesh_jit(
                ("d_project", node.exprs),
                functools.partial(_project_page, node.exprs),
            )
            for page in self.pages(node.source):
                yield fn(page)
            return
        if isinstance(node, P.Aggregation):
            yield from self._dist_aggregation(node)
            return
        if isinstance(node, P.TopN):
            yield from self._dist_topn(node)
            return
        if isinstance(node, P.HashJoin):
            yield from self._dist_join(node)
            return
        if isinstance(node, P.CrossJoin):
            yield from self._dist_cross_join(node)
            return
        if isinstance(node, P.UniqueId):
            yield from self._dist_unique_id(node)
            return
        if isinstance(node, P.Unnest):
            from presto_tpu.exec.executor import _unnest_page

            for page in self.pages(node.source):
                dic = page.block(node.array_channel).dictionary
                fn = self._mesh_jit(
                    ("d_unnest", node.array_channel, node.element_type,
                     node.with_ordinality, dic),
                    functools.partial(
                        _unnest_page, node.array_channel,
                        node.element_type, node.with_ordinality,
                    ),
                )
                yield fn(page)
            return
        if isinstance(node, P.GroupId):
            from presto_tpu.exec.executor import _group_id_page

            fns = [
                self._mesh_jit(
                    ("d_groupid", node.key_channels, mask, si),
                    functools.partial(_group_id_page,
                                      node.key_channels, mask, si),
                )
                for si, mask in enumerate(node.set_masks)
            ]
            for page in self.pages(node.source):
                for fn in fns:
                    yield fn(page)
            return
        if isinstance(node, P.Union):
            for src in node.sources:
                yield from self.pages(src)
            return
        if isinstance(node, P.Output):
            yield from self.pages(node.source)
            return
        raise TypeError(
            f"DistExecutor: node {type(node).__name__} requires a "
            f"replicated input (fragmenter should have inserted a gather)"
        )

    # ----------------------------------------------------------- helpers
    def _lazy_probe_ok(self, node: P.PhysicalNode) -> bool:
        """Late materialization only along fully-replicated probe
        spines: sharded subtrees route through shard_map paths that
        speak materialized Pages (their exchanges/collectives cannot
        carry a host-side indirection descriptor)."""
        return (
            super()._lazy_probe_ok(node)
            and self.dist(node) == REPLICATED
            and all(
                self.dist(c) == REPLICATED for c in node.children()
            )
        )

    # -------------------------------------------------------------- scan
    def _scan_sharded(self, node: P.TableScan) -> Iterator[Page]:
        gen = self._round_generator(node)
        if gen is None:
            conn = self.catalogs[node.catalog]
            yield from self._scan_staged(node, conn, tuple(node.columns))
            return
        n, gen_split, make_page, _n_rounds, launches = gen
        fn = self._mesh_jit(
            ("d_scan", node.catalog, node.table, tuple(node.columns), n),
            lambda start_arr: make_page(*gen_split(start_arr[0])))
        for start_arr, _rounds in launches():
            yield fn(start_arr)

    def _round_generator(self, node: P.TableScan):
        """A sharded scan of an on-device generator as rounds of D
        splits, one a chip: ``(n, gen_split, make_page, n_rounds,
        launches)``: the slots a chip generates a round, the
        shard-local generator (a split's start -> columns, valid), the
        page of its output, the scan's rounds, and ``launches(bmax)``,
        the iterator of ``(split starts, rounds)`` a launch, the
        starts sharded over the mesh (it counts the real splits; the
        tail round is padded). A launch of one round (every launch at
        the default ``bmax`` 1) takes an ``int64[D]``, a chip's start;
        one of B > 1 rounds an ``int64[D, B]``, a chip's B starts in
        scan order, the round-robin of the one-round launches (chip d
        has splits d, d + D, ...), so a row is generated on the chip
        that generates it a round a launch. Batches are ``bmax``
        rounds and the tail batch its exact width: no padded round
        generates anything.
        None for a host-page connector (_scan_staged). Shared by the
        bare scan (d_scan) and the fused scan chain (d_fused,
        d_fused_batch), so all see the same rounds, splits and
        slots."""
        conn = self.catalogs[node.catalog]
        schema = conn.table_schema(node.table)
        names = tuple(node.columns)
        splits = conn.splits(node.table, target_rows=self.page_rows)
        n = splits[0].row_count
        total = splits[-1].start_row + splits[-1].row_count
        body = conn.gen_body(node.table, n, names)
        if body is None:
            return None
        dicts = getattr(conn, "_dicts", {}).get(node.table, {})

        def gen_split(start):
            datas, valid = body(start)
            # rounds are padded to D devices; slots past the table are
            # masked out here (the generator itself has no bound)
            in_range = (
                start + jnp.arange(n, dtype=jnp.int64)
            ) < jnp.int64(total)
            return datas, valid & in_range

        def make_page(datas, valid):
            return Page(blocks=tuple(
                Block(data=data, type=schema.column_type(nm),
                      nulls=None, dictionary=dicts.get(nm))
                for nm, data in zip(names, datas)
            ), valid=valid)

        starts = [s.start_row for s in splits]
        spec = NamedSharding(self.mesh, PS("d"))
        D = self.D

        def launches(bmax: int = 1):
            for r in range(0, len(starts), D * bmax):
                chunk = starts[r:r + D * bmax]
                # launch amortization: a mesh round is one program
                # covering D splits (counted at the launch point:
                # programs.FUSED_SCAN_LABELS), the same accounting
                # the split-batched local scan reports
                self.splits_scanned += len(chunk)
                rounds = -(-len(chunk) // D)
                # pad the tail round; padded starts generate
                # fully-masked rows
                chunk = chunk + [total] * (rounds * D - len(chunk))
                # xfercheck: raw-ok - a host list of split starts
                table = np.asarray(chunk, dtype=np.int64)
                if rounds > 1:
                    table = np.ascontiguousarray(
                        table.reshape(rounds, D).T)
                yield XF.to_device(table, spec=spec,
                                   label="split-starts"), rounds

        return n, gen_split, make_page, -(-len(starts) // D), launches

    def _fused_rounds(self, node: P.PhysicalNode
                      ) -> Optional[Iterator[Page]]:
        """A SHARDED scan chain as ONE program a launch of scan rounds
        (d_fused a round, d_fused_batch a batch of them): where
        ``node`` tops a chain of Filter / Project / build-free
        generated joins over a TableScan of an on-device generator,
        the shard_map body generates the chip's split, builds the page
        and applies the step list the one-chip fused stream applies
        (Executor._chain_steps), so a round pays one launch and one
        pages() boundary instead of one a plan node. The rounds, the
        splits and the page a round are the per-node chain's, slot
        for slot.

        A scan of several rounds is launched a batch of rounds at a
        time, as one chip's is a batch of splits, and by the rule one
        chip uses (split_batch_size through _split_batch_max: B x n
        slots a chip under the row line and the governor's scan share
        a chip; auto engages on a TPU only; B < 2 is a round a
        launch). The body runs the one-split body once a split in a
        SEQUENTIAL loop (lax.map: the loop's step stays the one-split
        program, where one chip's vmapped [B, n] step costs several
        times its rows) and emits the stack as ONE page of B x n
        slots a chip, a chip's splits in scan order, so every
        per-page program above the chain runs once a batch. The flags
        are OR'd over the loop and psum'd once. The two drivers stay
        apart (one chip batches splits under vmap / lax.scan) and
        share the step list and the batch rule.

        When it engages is read from the plan and the connector: None
        (the per-node programs, which stay) where the chain holds an
        Exchange (_scan_chain walks through one because on one chip
        it moves nothing; over a mesh it moves rows, and the chain
        below it is looked at again when the walk reaches it), a
        live result-cache point (the rule of _fused_stream), or a
        host-page connector. Without an Exchange every link of a
        chain down to a TableScan is SHARDED (dist())."""
        walked = self._scan_chain(node, through_joins=True)
        if walked is None:
            return None
        scan, chain = walked
        if any(isinstance(link, P.Exchange) for link in chain):
            return None
        if self._chain_holds_cache_point(scan, chain):
            return None
        gen = self._round_generator(scan)
        if gen is None:
            return None
        n, gen_split, make_page, n_rounds, launches = gen
        steps = self._chain_steps(chain)
        # a windowed generated join's multi-match flag is the one
        # collective of the program (see _dist_join_generated)
        n_flags = sum(kind == "joinw" for kind, _fn in steps)
        out_specs = (PS("d"), (PS(),) * n_flags)

        def one_split(start):
            return _apply_steps(make_page(*gen_split(start)), steps)

        def any_chip(flags):
            return tuple(
                jax.lax.psum(jnp.any(f).astype(jnp.int32), "d") > 0
                for f in flags)

        def body(start_arr):
            page, flags = one_split(start_arr[0])
            return page, any_chip(flags)

        def batch_body(starts):
            # int64[1, B] a chip: its B splits, one a loop step
            pages, flags = jax.lax.map(one_split, starts[0])
            return _merge_leading(pages), any_chip(flags)

        def program(rounds):
            if rounds == 1:
                return self._mesh_jit(
                    ("d_fused", node, n), body, out_specs=out_specs,
                    fenced=n_flags > 0)
            return self._mesh_jit(
                ("d_fused_batch", node, n, rounds), batch_body,
                out_specs=out_specs, fenced=n_flags > 0)

        bmax = 1
        if n_rounds > 1:
            # a round's row is D splits' rows, one a chip, and the
            # mesh's budget D chips' shares: the stack fits a chip's
            bmax = max(1, self._split_batch_max(
                n, scanned=False, row_bytes=self.D * max(
                    _row_bytes(self.output_types(scan)),
                    _row_bytes(self.output_types(node)))))
        # full batches, and the tail batch at its exact width
        fns = {rounds: program(rounds) for rounds in {
            min(bmax, n_rounds - r) for r in range(0, n_rounds, bmax)}}

        def stream():
            for starts, rounds in launches(bmax):
                page, flags = fns[rounds](starts)
                self.mesh_fused_rounds += rounds
                if rounds > 1:
                    self.mesh_batched_rounds += rounds
                self._pending_overflow.extend(flags)
                yield page

        return stream()

    def _scan_staged(self, node, conn, names) -> Iterator[Page]:
        """Host-page connectors (e.g. memory connector): stage each round
        of host splits onto the mesh devices directly."""
        spec = NamedSharding(self.mesh, PS("d"))
        pages = list(conn.pages(node.table, names,
                                target_rows=self.page_rows))
        if not pages:
            return
        cap = max(p.capacity for p in pages)
        for r in range(0, len(pages), self.D):
            chunk = pages[r:r + self.D]
            yield _stack_to_mesh(chunk, cap, self.D, spec)

    # --------------------------------------------------------- exchanges
    def _exec_exchange(self, node: P.Exchange) -> Iterator[Page]:
        src_dist = self.dist(node.source)
        if node.kind in ("gather", "broadcast"):
            if src_dist == REPLICATED:
                yield from self.pages(node.source)
                return
            # compiled collective over the mesh: the exchange never
            # leaves the device — the same zero-crossing contract the
            # spooled mesh-local fast path counts (ISSUE 13)
            self.count_mesh_local()
            fn = self._gather_fn()
            for page in self.pages(node.source):
                yield fn(page)
            return
        if node.kind == "repartition":
            self.count_mesh_local()
            if src_dist == REPLICATED:
                # replicated -> sharded: each device keeps its hash
                # residues (deterministic disjoint split, no comms)
                fn = self._residue_fn(node.keys)
            else:
                fn = self._repartition_fn(node.keys)
            for page in self.pages(node.source):
                out, overflow = fn(page)
                self._pending_overflow.append(overflow)
                yield out
            return
        raise ValueError(f"unknown exchange kind {node.kind!r}")

    def _gather_fn(self):
        def body(page):
            return jax.tree.map(
                lambda x: jax.lax.all_gather(x, "d", tiled=True), page
            )

        # (check_vma=False in _mesh_jit: all_gather(tiled) output IS
        # replicated but jax's varying-axis inference cannot prove it)
        return self._mesh_jit(("d_gather",), body, out_specs=PS(),
                              fenced=True)

    def _key_hash(self, page: Page, keys: Tuple[int, ...]) -> jnp.ndarray:
        blocks = [page.block(c) for c in keys]
        cols, nulls = K.block_key_columns(blocks)
        return H.hash_columns(cols, nulls)

    def _repartition_fn(self, keys: Tuple[int, ...]):
        """hash(keys) % D routing via lax.all_to_all — the
        PartitionedOutputOperator -> ExchangeOperator data plane as one
        compiled collective (SURVEY §3.3 north-star mapping).

        The landing-zone capacity rides the boosted-retry ladder: a
        skewed key routing most rows to one device overflows the 2R
        default and the query retries with 4x landing capacity (SURVEY
        §6.7 — correctness under skew never depends on balance)."""
        D = self.D
        P = self._route_devices()  # hash_partition_count (<= D)
        boost = self._capacity_boost

        def body(page: Page):
            R = page.capacity  # local rows per device
            h = self._key_hash(page, keys)
            tgt = (h % jnp.uint64(P)).astype(jnp.int32)
            tgt = jnp.where(page.valid, tgt, D)
            # stable-sort rows by destination, compute position within
            # each destination bucket
            perm = jnp.argsort(tgt, stable=True)
            st = tgt[perm]
            first = jnp.searchsorted(
                st, jnp.arange(D, dtype=st.dtype), side="left"
            )
            pos = jnp.arange(R, dtype=jnp.int64) - first[
                jnp.clip(st, 0, D - 1)].astype(jnp.int64)
            # send layout [D, R]: slot (dest, pos); invalid rows drop
            slot = jnp.where(
                (st < D) & (pos < R),
                st.astype(jnp.int64) * R + pos,
                jnp.int64(D * R),
            )

            def to_send(x):
                out = jnp.zeros((D * R,), dtype=x.dtype)
                return out.at[slot].set(x[perm], mode="drop").reshape(D, R)

            sent = jax.tree.map(to_send, page)  # includes valid
            recv = jax.tree.map(
                lambda x: jax.lax.all_to_all(
                    x, "d", split_axis=0, concat_axis=0, tiled=False
                ),
                sent,
            )
            flat = jax.tree.map(
                lambda x: x.reshape((D * R,) + x.shape[2:]), recv
            )
            flat_valid = flat.valid
            # compact the D*R landing zone back to a bounded local page
            out_cap = min(D * R, _next_pow2(2 * R * boost))
            targets, out_valid, num = compact_indices(flat_valid, out_cap)
            blocks = []
            for blk in flat.blocks:
                if isinstance(blk.data, tuple):
                    data = tuple(
                        scatter_column(d, targets, out_cap)
                        for d in blk.data
                    )
                else:
                    data = scatter_column(blk.data, targets, out_cap)
                nulls = (
                    scatter_column(blk.nulls, targets, out_cap)
                    if blk.nulls is not None else None
                )
                blocks.append(blk.with_data(data, nulls=nulls))
            out = Page(blocks=tuple(blocks), valid=out_valid)
            overflow = jax.lax.psum(
                (num > out_cap).astype(jnp.int32), "d") > 0
            return out, overflow

        return self._mesh_jit(
            ("d_repartition", keys, self.D, P, boost), body,
            out_specs=(PS("d"), PS()), fenced=True)

    def _residue_fn(self, keys: Tuple[int, ...]):
        """Replicated -> sharded: device i keeps rows with
        hash(keys) % D == i (no data movement; the replica is local)."""
        P = self._route_devices()  # must agree with _repartition_fn

        def body(page: Page):
            me = jax.lax.axis_index("d")
            h = self._key_hash(page, keys)
            mine = (h % jnp.uint64(P)).astype(jnp.int32) == me
            out = page.with_valid(page.valid & mine)
            return out, jnp.asarray(False)

        return self._mesh_jit(
            ("d_residue", keys, self.D, P), body, in_specs=(PS(),),
            out_specs=(PS("d"), PS()))

    # ------------------------------------------------------- aggregation
    def _dist_aggregation(self, node: P.Aggregation) -> Iterator[Page]:
        src_dist = self.dist(node.source)
        if node.step == "partial" and src_dist == SHARDED:
            in_types = self._agg_in_types(node)
            layouts = tuple(
                tuple(S.state_layout(s.function, t))
                for s, t in zip(node.aggregates, in_types)
            )
            if not node.group_channels:
                fn = self._mesh_jit(
                    ("d_gagg_partial", node.aggregates, layouts),
                    functools.partial(
                        _partial_global_agg, node.aggregates, layouts
                    ),
                )
                for page in self.pages(node.source):
                    yield fn(page)
                return
            # every chip may see every group: the rule's cap as it is
            cap = self._mesh_agg_cap(node, sharded_state=False)
            max_iters = 64 * self._capacity_boost

            # where the rule asks for a compaction buffer (sparse join
            # output under a slot-proportional grouping sort: Q3) the
            # chips fold their pages into it first, so this step, the
            # repartition above it and the final step run ONCE a
            # statement on a row-sized page; where it does not (dense
            # dictionary keys: Q5; a boosted attempt above the buffer
            # ceiling) they run once a source page
            for page in self._agg_source_pages(node):
                # distinct groups <= rows: clip to the chip's rows
                local_cap = min(
                    cap, _next_pow2(page.capacity // self.D)
                )

                def body(page, local_cap=local_cap):
                    out, ovf = _partial_agg_page(
                        node.group_channels, node.aggregates, layouts,
                        page, local_cap, max_iters,
                    )
                    return out, jax.lax.psum(
                        ovf.astype(jnp.int32), "d") > 0

                # canonical: the estimate-bearing node stays OUT of the
                # key (exec/shapes.py discipline — content only)
                fn = self._mesh_jit(
                    ("d_agg_partial", node.group_channels,
                     node.aggregates, layouts, local_cap, max_iters),
                    body, out_specs=(PS("d"), PS()), fenced=True)
                out, overflow = fn(page)
                self._pending_overflow.append(overflow)
                yield out
            return
        if node.step == "final" and src_dist == SHARDED:
            # repartitioned state pages: keys are co-located per device,
            # final agg runs shard-locally
            origin = self._partial_origin(node)
            in_types = self._agg_in_types(origin)
            layouts = tuple(
                tuple(S.state_layout(s.function, t))
                for s, t in zip(node.aggregates, in_types)
            )
            pages = list(self.pages(node.source))
            if not pages:
                return
            local_caps = tuple(p.capacity // self.D for p in pages)
            # keys are co-located: a chip holds its 1/D of the groups
            fcap = min(
                self._mesh_agg_cap(origin, sharded_state=True),
                _next_pow2(sum(local_caps)),
            )
            max_iters = 64 * self._capacity_boost

            def body(*pgs):
                merged = concat_all(pgs) if len(pgs) > 1 else pgs[0]
                out, ovf = _final_agg_page(
                    node.group_channels, node.aggregates, layouts,
                    tuple(in_types), merged, fcap, max_iters,
                )
                return out, jax.lax.psum(ovf.astype(jnp.int32), "d") > 0

            fn = self._mesh_jit(
                ("d_agg_final", node.group_channels, node.aggregates,
                 layouts, tuple(in_types), local_caps, fcap, max_iters),
                body, in_specs=tuple(PS("d") for _ in pages),
                out_specs=(PS("d"), PS()), fenced=True)
            out, overflow = fn(*pages)
            self._pending_overflow.append(overflow)
            yield out
            return
        # replicated input: inherited single-stream paths
        yield from super()._exec_aggregation(node)

    def _mesh_agg_cap(self, node: P.Aggregation,
                      sharded_state: bool) -> int:
        """Group capacity of one mesh aggregation step, from THE one
        sizing rule (Executor._agg_sizing: the first attempt sized by
        the rows it expects, a boosted retry from the planner's
        boost-scaled bounds; the psum'd overflow flag re-enters the
        ladder). Where the state is sharded by group key each chip
        holds 1/D of the groups, so 1/D of the rule's capacity. The
        decision is recorded for the attempt span as one chip records
        it: one pass, and the rule's compaction buffer as the partial
        step uses it (_agg_source_pages; 0 = one step a source page)."""
        sizing = self._agg_sizing(node)
        cap = max(sizing.cap // self.D, 8) if sharded_state \
            else sizing.cap
        self._agg_sizings.append(sizing._replace(cap=cap, parts=1))
        return cap

    def _stream_compact_fns(self, node: P.Aggregation, C: int):
        """Over a SHARDED source the rolling compaction buffer is
        shard-local: the rule's C slots are shared over the chips as a
        key-sharded state's capacity is, C // D each. The scan's
        splits go round-robin, so a chip's share of the valid rows is
        1/D in expectation; a chip whose rows pass its share flags
        overflow through the psum and the statement re-enters boosted.
        Correctness never depends on balance."""
        if self.dist(node.source) != SHARDED:
            return super()._stream_compact_fns(node, C)
        c = C // self.D

        def flagged(kernel):
            def body(*pages):
                out, dropped = kernel(*pages, c)
                return out, jax.lax.psum(
                    dropped.astype(jnp.int32), "d") > 0
            return body

        first = self._mesh_jit(
            ("d_stream_compact1", c), flagged(_compact_with_flag),
            out_specs=(PS("d"), PS()), fenced=True)
        merge = self._mesh_jit(
            ("d_stream_compact2", c), flagged(_merge_compact_flag),
            in_specs=(PS("d"), PS("d")), out_specs=(PS("d"), PS()),
            fenced=True)
        return first, merge

    # -------------------------------------------------------------- top-N
    def _dist_topn(self, node: P.TopN) -> Iterator[Page]:
        """Each chip's own top `limit` of a SHARDED stream (reference:
        AddExchanges.visitTopN puts a TopNNode.Step.PARTIAL under the
        gathering exchange): the one-device streaming top-N — per page
        sort_page(limit), then the running _topn_merge — shard-local,
        so the gather above carries D x limit rows instead of the
        source's state and the replicated TopN above it finishes."""
        running = None
        for page in self.pages(node.source):
            local = self._mesh_jit(
                ("d_topn_local", node.keys, node.limit, page.capacity),
                functools.partial(sort_page, sort_keys=node.keys,
                                  limit=node.limit),
            )(page)
            if running is None:
                running = local
                continue
            running = self._mesh_jit(
                ("d_topn_merge", node.keys, node.limit,
                 running.capacity, local.capacity),
                functools.partial(_topn_merge, node.keys, node.limit),
                in_specs=(PS("d"), PS("d")),
            )(running, local)
        if running is not None:
            yield running

    # -------------------------------------------------------------- join
    def _dist_join(self, node: P.HashJoin) -> Iterator[Page]:
        dl, dr = self.dist(node.left), self.dist(node.right)
        gj = self._generated_join_info(node, self.output_types(node.left))
        if gj is not None:
            # build-free generated join is embarrassingly SPMD: each
            # device inverts its shard's probe keys and GENERATES the
            # carried build columns locally — no broadcast, no
            # repartition, no build materialization on any device
            yield from self._dist_join_generated(node, gj, dl)
            return
        if dl == REPLICATED and dr == REPLICATED:
            yield from super()._exec_join(node)
            return
        yield from self._dist_join_materialized(node, dl, dr)

    def _dist_join_generated(self, node: P.HashJoin, info, dl
                             ) -> Iterator[Page]:
        self.generated_joins_used += 1
        kern, windowed = self.generated_join_kernel(node, info)
        spec = PS("d") if dl == SHARDED else PS()
        if not windowed:
            fn = self._mesh_jit(("d_genjoin", node, dl), kern,
                                in_specs=(spec,), out_specs=spec)
            for page in self.pages(node.left):
                yield fn(page)
            return

        def win_body(page):
            out, multi = kern(page)
            return out, jax.lax.psum(multi.astype(jnp.int32), "d") > 0

        # fenced: the windowed multi-match psum is THE collective
        # whose free interleaving deadlocked TPC-DS Q17
        fn = self._mesh_jit(("d_genjoin_win", node, dl), win_body,
                            in_specs=(spec,), out_specs=(spec, PS()),
                            fenced=True)
        for page in self.pages(node.left):
            out, multi = fn(page)
            self._pending_overflow.append(multi)
            yield out

    def _dist_join_materialized(self, node: P.HashJoin, dl, dr
                                ) -> Iterator[Page]:
        # build side: replicated (broadcast) or sharded (partitioned)
        build_pages = list(self.pages(node.right))
        right_types = self.output_types(node.right)
        left_types = self.output_types(node.left)
        if not build_pages:
            from presto_tpu.exec.executor import _empty_page

            build_pages = [_empty_page(right_types, cap=self.D * 8)]
        build_all = (
            concat_all(build_pages) if len(build_pages) > 1
            else build_pages[0]
        )
        build_spec = PS() if dr == REPLICATED else PS("d")
        probe_spec = PS("d") if dl == SHARDED else PS()

        if node.join_type in ("semi", "anti"):
            def semi_body(page, build):
                return _semi_join_page(
                    node.left_keys, node.right_keys, page, build
                )

            fn = self._mesh_jit(
                ("d_semi", node, build_all.capacity), semi_body,
                in_specs=(probe_spec, build_spec), out_specs=probe_spec)
            for page in self.pages(node.left):
                yield fn(page, build_all)
            return

        local_build_cap = (
            build_all.capacity if dr == REPLICATED
            else build_all.capacity // self.D
        )
        matched_acc = None
        probe_pages = list(self.pages(node.left))
        for page in probe_pages:
            local_probe = (
                page.capacity // self.D if dl == SHARDED
                else page.capacity
            )
            oc = _next_pow2(
                max(local_probe, local_build_cap) * 2
                * self._capacity_boost
            )

            def probe_body(pg, build, oc=oc):
                from presto_tpu.exec.executor import _build_join_index

                index = _build_join_index(
                    node.left_keys, node.right_keys, pg, build
                )
                out, matched, ovf = _probe_join_page(
                    node.left_keys, node.right_keys, node.join_type,
                    False, pg, build, index, oc,
                )
                ovf = jax.lax.psum(ovf.astype(jnp.int32), "d") > 0
                if dr == REPLICATED:
                    # matched refers to replicated build rows: OR the
                    # per-device views so outer emission sees every match
                    matched = jax.lax.psum(
                        matched.astype(jnp.int32), "d") > 0
                return out, matched, ovf

            fn = self._mesh_jit(
                ("d_probe", node, page.capacity, build_all.capacity,
                 oc, dl, dr), probe_body,
                in_specs=(probe_spec, build_spec),
                out_specs=(PS("d"), build_spec, PS()), fenced=True)
            out, matched, overflow = fn(page, build_all)
            self._pending_overflow.append(overflow)
            matched_acc = (
                matched if matched_acc is None else matched_acc | matched
            )
            yield out
        if node.join_type in ("right", "full"):
            yield self._outer_build_rows(
                node, build_all, matched_acc, left_types, dr
            )

    def _outer_build_rows(self, node, build_all, matched, left_types, dr):
        """Unmatched build rows with a null probe side. Replicated builds
        are emitted once per hash residue so the sharded stream holds each
        row exactly once."""
        D = self.D

        def body(build, m):
            unmatched = build.valid & ~m
            if dr == REPLICATED:
                me = jax.lax.axis_index("d")
                idx = jnp.arange(build.capacity, dtype=jnp.int32)
                unmatched = unmatched & ((idx % D) == me)
            nulls = _null_blocks(left_types, build.capacity)
            return Page(
                blocks=tuple(nulls) + build.blocks, valid=unmatched
            )

        bspec = PS() if dr == REPLICATED else PS("d")
        fn = self._mesh_jit(("d_outer", node, build_all.capacity, dr),
                            body, in_specs=(bspec, bspec))
        return fn(build_all, matched)

    def _dist_cross_join(self, node: P.CrossJoin) -> Iterator[Page]:
        from presto_tpu.exec.executor import _cross_join_page, compact_page

        # fragmenter guarantees the right side is replicated
        right_pages = list(self.pages(node.right))
        if not right_pages:
            return
        build_all = concat_all(right_pages)
        bcap = min(
            _next_pow2(build_all.capacity),
            _next_pow2(4096 * self._capacity_boost),
        )
        self._pending_overflow.append(build_all.num_rows() > bcap)
        build = compact_page(build_all, bcap)

        for page in self.pages(node.left):
            fn = self._mesh_jit(
                ("d_cross", node, page.capacity, bcap),
                _cross_join_page, in_specs=(PS("d"), PS()))
            yield fn(page, build)

    def _dist_unique_id(self, node: P.UniqueId) -> Iterator[Page]:
        # globally-unique bigint per row: device index in the high bits
        offset = 0

        def body(page, off):
            me = jax.lax.axis_index("d").astype(jnp.int64)
            ids = (
                (me << jnp.int64(40))
                + off
                + jnp.arange(page.capacity, dtype=jnp.int64)
            )
            blk = Block(data=ids, type=T.BIGINT)
            return Page(blocks=page.blocks + (blk,), valid=page.valid)

        for page in self.pages(node.source):
            fn = self._mesh_jit(("d_uid", node, page.capacity), body,
                                in_specs=(PS("d"), PS()))
            yield fn(page, jnp.int64(offset))
            offset += page.capacity


def _stack_to_mesh(pages: List[Page], cap: int, D: int, spec) -> Page:
    """Stage up to D host pages as one mesh-sharded global page (host
    data path for connectors without on-device generators)."""
    import numpy as _np

    padded: List[Optional[Page]] = list(pages) + [None] * (D - len(pages))

    first = pages[0]
    blocks = []
    for ch in range(first.channel_count):
        datas, nulls_l = [], []
        any_nulls = any(
            p is not None and p.block(ch).nulls is not None for p in padded
        )
        for p in padded:
            if p is None:
                blk0 = first.block(ch)
                if isinstance(blk0.data, tuple):
                    datas.append(tuple(
                        _np.zeros(cap, d.dtype) for d in blk0.data
                    ))
                else:
                    datas.append(_np.zeros(cap, blk0.data.dtype))
                nulls_l.append(_np.ones(cap, bool))
                continue
            blk = p.block(ch)
            if isinstance(blk.data, tuple):
                datas.append(tuple(
                    _pad_np(XF.np_host(d), cap) for d in blk.data
                ))
            else:
                datas.append(_pad_np(XF.np_host(blk.data), cap))
            nulls_l.append(
                _pad_np(XF.np_host(blk.nulls), cap)
                if blk.nulls is not None else _np.zeros(cap, bool)
            )
        blk0 = first.block(ch)
        if isinstance(blk0.data, tuple):
            data = tuple(
                XF.to_device(
                    _np.concatenate([d[i] for d in datas]),
                    spec=spec, label="stack-to-mesh",
                )
                for i in range(len(blk0.data))
            )
        else:
            data = XF.to_device(_np.concatenate(datas), spec=spec,
                                label="stack-to-mesh")
        nulls = (
            XF.to_device(_np.concatenate(nulls_l), spec=spec,
                         label="stack-to-mesh")
            if any_nulls else None
        )
        blocks.append(Block(
            data=data, type=blk0.type, nulls=nulls,
            dictionary=blk0.dictionary,
        ))
    valid = _np.concatenate([
        _pad_np(XF.np_host(p.valid), cap) if p is not None
        else _np.zeros(cap, bool)
        for p in padded
    ])
    return Page(blocks=tuple(blocks),
                valid=XF.to_device(valid, spec=spec,
                                   label="stack-to-mesh"))


def _pad_np(arr, cap):
    if arr.shape[0] == cap:
        return arr
    out = np.zeros((cap,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


# ---------------------------------------------------------------------
# ICI exchange plane (ISSUE 18): lower a spooled repartition edge to an
# in-program lax.all_to_all when the producer stage's spools and the
# consumer stage's readers are co-resident on ONE process mesh. The
# spool plane stays authoritative for DCN-remote consumers and for
# replay/fault recovery; this plane only replaces the
# partition -> serialize -> HTTP -> deserialize -> re-stage hop with a
# single collective over the device interconnect.


def ici_exchange_supported(nparts: int, pages) -> bool:
    """Static shape gate for `ici_exchange_pages`: the exchange maps
    partition p to mesh device p, so the partition count must be a
    power of two that the local device pool can host, and every page's
    capacity (a ladder power of two >= 8) must shard evenly across it.
    Anything else stays on the spool plane — a shape, never an error."""
    if nparts < 2 or (nparts & (nparts - 1)) != 0:
        return False
    if nparts > len(jax.devices()):
        return False
    return all(p.capacity % nparts == 0 for p in pages)


_ICI_MESHES: Dict[int, Mesh] = {}

# compiled all_to_all exchange programs, keyed by exchange geometry
# (see _ici_program: process-level so per-query coordinator executors
# share warm programs the way the shapes ladder intends)
_ICI_PROGRAMS: Dict[tuple, object] = {}


def _ici_mesh(d: int) -> Mesh:
    """One cached Mesh per device count: the compiled exchange
    programs close over the mesh object, so handing every jit-cache
    hit the SAME mesh keeps shard_map from re-validating placements."""
    if d not in _ICI_MESHES:
        _ICI_MESHES[d] = make_mesh(d)
    return _ICI_MESHES[d]


def _ici_program(mesh: Mesh, keys: Tuple[int, ...], dicts,
                 nluts: int, d: int, out_cap: int) -> PG.Program:
    """The per-page exchange collective: shard-local splitmix64
    routing + all_to_all + compaction to the ladder landing capacity.
    Mirrors DistExecutor._repartition_fn, with two deltas: the routing
    hash is dist/spool.device_row_hash_u64 — BIT-IDENTICAL to the
    spool plane's host and device partitioners, so a mid-query
    fallback (or one side of a co-partitioned join taking the spool
    path) lands every row in the same partition — and the landing
    capacity is shapes.exchange_partition_cap, the SAME bucket the
    spool partitioner compacts to, so consumer jit keys cannot tell
    the planes apart."""
    from presto_tpu.dist import spool as SPOOL

    def body(pg: Page, *vhs):
        vh_by_key = iter(vhs)
        full = tuple(next(vh_by_key) if dct is not None else None
                     for dct in dicts)
        r = pg.capacity  # local rows per device
        h = SPOOL.device_row_hash_u64(pg, keys, full)
        tgt = (h % jnp.uint64(d)).astype(jnp.int32)
        tgt = jnp.where(pg.valid, tgt, d)
        # stable-sort rows by destination partition (== destination
        # device), position within each destination bucket
        perm = jnp.argsort(tgt, stable=True)
        st = tgt[perm]
        first = jnp.searchsorted(
            st, jnp.arange(d, dtype=st.dtype), side="left"
        )
        pos = jnp.arange(r, dtype=jnp.int64) - first[
            jnp.clip(st, 0, d - 1)].astype(jnp.int64)
        slot = jnp.where(
            (st < d) & (pos < r),
            st.astype(jnp.int64) * r + pos,
            jnp.int64(d * r),
        )

        def to_send(x):
            out = jnp.zeros((d * r,), dtype=x.dtype)
            return out.at[slot].set(x[perm], mode="drop").reshape(d, r)

        sent = jax.tree.map(to_send, pg)  # includes valid
        recv = jax.tree.map(
            lambda x: jax.lax.all_to_all(
                x, "d", split_axis=0, concat_axis=0, tiled=False
            ),
            sent,
        )
        flat = jax.tree.map(
            lambda x: x.reshape((d * r,) + x.shape[2:]), recv
        )
        # compact the d*r landing zone to the spool plane's partition
        # bucket; skew joins the boosted-retry ladder via the
        # OR-reduced flag exactly like device_partition_pages
        targets, out_valid, num = compact_indices(flat.valid, out_cap)
        blocks = []
        for blk in flat.blocks:
            if isinstance(blk.data, tuple):
                data = tuple(scatter_column(dd, targets, out_cap)
                             for dd in blk.data)
            else:
                data = scatter_column(blk.data, targets, out_cap)
            nulls = (scatter_column(blk.nulls, targets, out_cap)
                     if blk.nulls is not None else None)
            blocks.append(blk.with_data(data, nulls=nulls))
        out = Page(blocks=tuple(blocks), valid=out_valid)
        overflow = jax.lax.psum(
            (num > out_cap).astype(jnp.int32), "d") > 0
        return out, overflow

    # PROCESS-level cache, not ex._jit_cache: the coordinator builds
    # one executor per query, and a per-executor cache would re-pay
    # the shard_map compile for every query (and every test) hitting
    # the same exchange shape. The program depends on the dicts only
    # through their None-pattern (LUT values are operands), and jit
    # re-traces per page schema on its own — so the key is just the
    # exchange geometry. Benign-race dict like _ICI_MESHES: a lost
    # write costs one duplicate compile, never a wrong program.
    key = (keys, d, out_cap,
           tuple(dct is not None for dct in dicts), nluts)
    if key not in _ICI_PROGRAMS:
        _ICI_PROGRAMS[key] = PG.Program("d_ici_exchange", jax.shard_map(
            body, mesh=mesh,
            in_specs=(PS("d"),) + (PS(),) * nluts,
            out_specs=(PS("d"), PS()), check_vma=False,
        ))
    return _ICI_PROGRAMS[key]


def ici_exchange_pages(ex, pages, keys: Tuple[int, ...], nparts: int):
    """Exchange spooled raw producer pages into `nparts` partition
    page lists over the device interconnect — ONE all_to_all program
    per raw page, no serialization and no host hop anywhere on the
    path (device-resident inputs stage with zero metered bytes; a
    host-resident input pays its honest h2d once).

    Returns ``(parts, ici_bytes)`` where ``parts[p]`` is the list of
    device partition pages consumer task p reads (capacities identical
    to what `device_partition_pages` would have spooled) and
    ``ici_bytes`` is the static byte footprint routed through the
    collective's send buffers — the ledger row `exec/counters.py`
    declares and `adaptive/replanner.py` costs exchanges with.

    Overflow discipline: the per-program OR-reduced flag settles HERE
    (the coordinator owns this exchange; there is no worker attempt
    loop to defer into) — each overflowing round re-runs EVERY page at
    the next ladder rung so all partition pages land at one capacity,
    counting `capacity_boost_retries` like any other boosted retry."""
    from presto_tpu.dist import spool as SPOOL
    from presto_tpu.exec import shapes as SH
    from presto_tpu.exec.executor import page_bytes

    pages = list(pages)
    if not ici_exchange_supported(nparts, pages):
        raise ValueError(
            f"ici exchange unsupported: nparts={nparts} over "
            f"{len(jax.devices())} devices, caps="
            f"{[p.capacity for p in pages]}")
    d = nparts
    mesh = _ici_mesh(d)
    page_spec = NamedSharding(mesh, PS("d"))
    lut_spec = NamedSharding(mesh, PS())
    ici_bytes = 0
    staged = []
    for page in pages:
        dicts = tuple(page.block(k).dictionary for k in keys)
        luts = tuple(
            XF.to_device(SPOOL._dict_value_hashes(dct), spec=lut_spec,
                         label="dict-hash")
            if dct is not None else None
            for dct in dicts
        )
        pg = XF.to_device(page, spec=page_spec,
                          label="ici-exchange-stage")
        ici_bytes += page_bytes(page)
        staged.append((pg, dicts, luts))
    boost = ex._capacity_boost
    while True:
        outs = []
        overflowed = False
        for pg, dicts, luts in staged:
            out_cap = SH.exchange_partition_cap(
                pg.capacity, nparts, boost)
            prog = _ici_program(mesh, keys, dicts,
                                sum(1 for v in luts if v is not None),
                                d, out_cap)
            # called through THE launch point and counted on the
            # calling executor, like every program _jit makes
            run = DistExecutor._fenced(
                functools.partial(PG.launch, ex, prog))
            out, overflow = run(pg, *[v for v in luts if v is not None])
            outs.append((out, out_cap))
            if bool(overflow):
                overflowed = True
        if not overflowed:
            break
        boost = SH.next_boost(boost)
        ex.capacity_boost_retries += 1
        if boost > SH.DEVICE_FAULT_ROWS:
            raise RuntimeError(
                "ici exchange overflow did not settle on the boost "
                "ladder")
    parts: List[List[Page]] = [[] for _ in range(nparts)]
    for out, out_cap in outs:
        # shard p of the exchanged page IS partition p: slice it out
        # as consumer task p's device page (device-side view, no
        # crossing — the spool data plane serves it from here)
        for p in range(nparts):
            parts[p].append(jax.tree.map(
                lambda x, p=p: x[p * out_cap:(p + 1) * out_cap], out))
    return parts, ici_bytes
