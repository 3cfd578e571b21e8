"""Pallas TPU hash-join kernel (SURVEY §8.2.2).

The join contract is shared with the sort join (ops/join.py): an index
over the HASH-SORTED build side where equal-hash rows form contiguous
segments, and a probe that returns, per probe row, the segment range
(start, count) of equal-hash build rows. ops/join.expand_matches then
flattens ranges into verified matches identically for every range
finder — searchsorted (sort join) or the open-addressing table here.

One table layout, the **"dim"** (dimension-table) layout, for builds of
up to DIM_MAX_BUILD rows (plan_layout answers None above that and the
executor takes the sort join). The table is T radix tiles of 128
entries; each tile is replicated across the 8 sublanes, so a probe
block gathers entries with the ONE per-lane gather this Mosaic
toolchain lowers: jnp.take_along_axis on an (8, 128) value along the
lane axis (verified on hardware; every wider/per-ref gather form
crashes the tpu_compile_helper). Collision chains stay inside a tile's
128 lanes. This is the compiled kernel pallas_join_enabled=auto selects
on a TPU — it serves the broadcast-side joins of star schemas
(region/nation in Q5); off a TPU it runs in interpret mode, the test
path.

Reference: presto-main operator/{PagesIndex,JoinHash}.java — the
address-sorted PagesIndex plus an open-addressing hash over row
addresses is exactly this index, minus the pointer chasing.

u64 handling: TPU lanes are 32-bit, so hashes travel as (lo32, hi32)
int32 pairs and tables are int32 throughout. Loop carries in kernels are
int32/int32-vectors only — boolean vector carries crash this compiler
(bisected on hardware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# dim layout: T (pow2) tiles x 128 lanes, row-replicated; chains wrap
# within a tile's 128 lanes. 2x-entries load factor => builds up to
# DIM_TILES_MAX * 128 / 2 rows.
DIM_TILES_MAX = 32
DIM_MAX_BUILD = DIM_TILES_MAX * 128 // 2  # 2048 rows
# probe groups of (8, 128) keys processed per grid step (amortizes the
# per-step fixed cost)
_DIM_GROUPS = 16

_MAX_ITERS = 64


def _split64(keys: jnp.ndarray):
    u = keys.astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32).astype(jnp.int32)
    return lo, hi


def _mix32(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """32-bit finalizer (murmur3 fmix32 over both words) for slot
    addressing; equality is verified on the full (lo, hi) pair."""
    h = lo.astype(jnp.uint32) ^ (hi.astype(jnp.uint32) *
                                 jnp.uint32(0x85EBCA6B))
    h ^= h >> jnp.uint32(16)
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= h >> jnp.uint32(13)
    h = h * jnp.uint32(0xC2B2AE35)
    h ^= h >> jnp.uint32(16)
    return h


def plan_layout(build_cap: int):
    """Static layout for a build of `build_cap` rows: ("dim", tiles),
    or None above DIM_MAX_BUILD (the sort join's). Hashable —
    executors bind it into jitted kernels."""
    if build_cap > DIM_MAX_BUILD:
        return None
    total = max(128, 1 << (2 * build_cap - 1).bit_length())
    return ("dim", total // 128)


# ----------------------------------------------------------- index build


def _sorted_segments(bhash: jnp.ndarray, bvalid: jnp.ndarray):
    """Hash-sort the build side; equal-hash runs become segments. Per
    sorted row: the segment's first VALID position and valid count.
    Invalid rows poison to the max hash and sort last, so ordinary
    segments hold only valid rows. Callers must exclude VALID rows
    carrying the poison hash itself beforehand (build_index does, via
    the overflow escape): inside the max-hash segment the stable sort
    preserves the original valid/invalid interleaving, so (vstart,
    vcnt) would cover a non-contiguous valid set and drop matches."""
    n = bhash.shape[0]
    poisoned = jnp.where(bvalid, bhash, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    perm = jnp.argsort(poisoned)
    sorted_h = poisoned[perm]
    valid_s = bvalid[perm]
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_h[1:] != sorted_h[:-1]]
    )
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    vcnt = (
        jnp.zeros((n,), jnp.int32).at[seg_id].add(valid_s.astype(jnp.int32))
    )[seg_id]
    vstart = (
        jnp.full((n,), n, jnp.int32)
        .at[jnp.where(valid_s, seg_id, n)]
        .min(idx, mode="drop")
    )[seg_id]
    # one entry per segment with >=1 valid row, anchored at its first
    # valid sorted position
    entry = valid_s & (idx == vstart) & (vcnt > 0)
    return perm, sorted_h, entry, vstart, vcnt


def _insert(sorted_h, entry, vstart, vcnt, base, width, table_cap,
            max_iters: int = _MAX_ITERS):
    """Vectorized open-addressing insert of segment entries by
    scatter-min, lockstep linear probing within each entry's [base,
    base+width) span. Returns flat (lo, hi, start, count) int32 tables
    and an overflow flag (unsettled after max_iters — callers fall back
    to the sort join)."""
    n = sorted_h.shape[0]
    lo, hi = _split64(sorted_h)
    h32 = _mix32(lo, hi)
    wmask = jnp.uint32(width - 1)
    slot0 = base + (h32 & wmask).astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    BIG = jnp.int32(n)

    def settled(owner, slot):
        return entry & (owner[slot] == idx)

    def cond(state):
        owner, slot, it = state
        return jnp.any(entry & ~settled(owner, slot)) & (it < max_iters)

    def body(state):
        owner, slot, it = state
        done = settled(owner, slot)
        claim = jnp.where(done | ~entry, BIG, idx)
        owner = owner.at[slot].min(claim)
        done2 = settled(owner, slot)
        within = (slot - base).astype(jnp.uint32)
        nxt = base + ((within + jnp.uint32(1)) & wmask).astype(jnp.int32)
        slot = jnp.where(done2 | ~entry, slot, nxt)
        return owner, slot, it + 1

    owner0 = jnp.full((table_cap,), BIG, dtype=jnp.int32)
    owner, slot, _ = jax.lax.while_loop(
        cond, body, (owner0, slot0, jnp.int32(0))
    )
    ok = settled(owner, slot)
    overflow = jnp.any(entry & ~ok)
    tgt = jnp.where(ok, slot, table_cap)
    tab_lo = jnp.zeros((table_cap,), jnp.int32).at[tgt].set(lo, mode="drop")
    tab_hi = jnp.zeros((table_cap,), jnp.int32).at[tgt].set(hi, mode="drop")
    tab_start = jnp.zeros((table_cap,), jnp.int32).at[tgt].set(
        vstart, mode="drop")
    tab_count = jnp.zeros((table_cap,), jnp.int32).at[tgt].set(
        vcnt, mode="drop")
    return (tab_lo, tab_hi, tab_start, tab_count), overflow


def build_index(bhash: jnp.ndarray, bvalid: jnp.ndarray, layout):
    """Build the (start, count) range index for `layout` (plan_layout).

    Returns (tables, perm, overflow): `perm` is the hash-sorted build
    order that start/count ranges refer to; `tables` is
    4 x int32[T, 8, 128] (row-replicated tiles).
    """
    # a VALID row whose hash equals the poison value would interleave
    # with poisoned invalid rows inside the max-hash segment and lose
    # matches (stable sort keeps original order there) — exclude such
    # rows and raise overflow so the query retries on the exact sort
    # join. Identity-encoded keys hit this for BIGINT -1; real hashes
    # at 2^-64.
    MAXU = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    poison_conflict = jnp.any(bvalid & (bhash == MAXU))
    bvalid = bvalid & (bhash != MAXU)
    perm, sorted_h, entry, vstart, vcnt = _sorted_segments(bhash, bvalid)
    lo, hi = _split64(sorted_h)
    h32 = _mix32(lo, hi)
    _, tiles = layout
    tile = (
        ((h32 >> jnp.uint32(7))
         & jnp.uint32(tiles - 1)).astype(jnp.int32)
        if tiles > 1 else jnp.zeros(h32.shape, jnp.int32)
    )
    tabs, overflow = _insert(
        sorted_h, entry, vstart, vcnt, tile * 128, 128, tiles * 128
    )
    tabs = tuple(
        jnp.broadcast_to(t.reshape(tiles, 1, 128), (tiles, 8, 128))
        for t in tabs
    )
    return tabs, perm, overflow | poison_conflict


# ------------------------------------------------------------ dim probe

# the ONE per-lane gather this Mosaic version lowers: within-row gather
# along the lane axis of an (8, 128) value, batched over sublanes.
# jnp.take_along_axis builds the same GatherDimensionNumbers but
# promotes indices to int64 under jax_enable_x64, which Mosaic rejects —
# so call lax.gather directly with int32 indices.
_LANE_GATHER_DNUMS = jax.lax.GatherDimensionNumbers(
    offset_dims=(),
    collapsed_slice_dims=(1,),
    start_index_map=(1,),
    operand_batching_dims=(0,),
    start_indices_batching_dims=(0,),
)


def _gather_lanes(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """out[i, j] = x[i, idx[i, j]] for (8, 128) int32 operands."""
    return jax.lax.gather(
        x, idx[..., None], _LANE_GATHER_DNUMS, (1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _dim_kernel(plo_ref, phi_ref, tlo_ref, thi_ref, tstart_ref,
                tcnt_ref, start_ref, cnt_ref, *, tiles: int,
                groups: int, max_probes: int):
    for g in range(groups):
        sl = slice(g * 128, (g + 1) * 128)
        plo = plo_ref[:, sl]
        phi = phi_ref[:, sl]
        h32 = _mix32(plo, phi)
        tile_k = (
            ((h32 >> jnp.uint32(7))
             & jnp.uint32(tiles - 1)).astype(jnp.int32)
            if tiles > 1 else jnp.zeros(plo.shape, jnp.int32)
        )
        slot = (h32 & jnp.uint32(127)).astype(jnp.int32)
        start = jnp.full(plo.shape, -1, jnp.int32)
        cnt = jnp.zeros(plo.shape, jnp.int32)
        live = jnp.ones(plo.shape, jnp.int32)  # int32: bool vector
        # loop carries crash this Mosaic version (bisected)

        def cond(c):
            i, slot, start, cnt, live = c
            # int32 max-reduction: jnp.any's bool reduction trips the
            # Mosaic squeeze lowering under jax_enable_x64
            return (i < max_probes) & (jnp.max(live) > 0)

        def body(c):
            i, slot, start, cnt, live = c
            live_b = live > 0
            die = jnp.zeros(plo.shape, jnp.bool_)
            for t in range(tiles):
                sel = live_b & (tile_k == t) if tiles > 1 else live_b
                glo = _gather_lanes(tlo_ref[t], slot)
                ghi = _gather_lanes(thi_ref[t], slot)
                gc = _gather_lanes(tcnt_ref[t], slot)
                occupied = gc > 0
                hit = sel & occupied & (glo == plo) & (ghi == phi)
                start = jnp.where(
                    hit, _gather_lanes(tstart_ref[t], slot), start
                )
                cnt = jnp.where(hit, gc, cnt)
                die = die | (sel & (hit | ~occupied))
            # jnp.int32(0), not 0: a bare python int becomes an i64
            # scalar under jax_enable_x64 and Mosaic has no 64-bit
            live = jnp.where(die, jnp.int32(0), live)
            slot = jnp.where(live > 0, (slot + 1) & 127, slot)
            return i + 1, slot, start, cnt, live

        _, slot, start, cnt, live = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), slot, start, cnt, live),
        )
        start_ref[:, sl] = start
        cnt_ref[:, sl] = cnt


def _probe_dim(probe_hash, tables, tiles, *, interpret,
               max_probes: int = _MAX_ITERS + 1):
    from jax.experimental import pallas as pl

    n = probe_hash.shape[0]
    groups = _DIM_GROUPS
    block_keys = 8 * 128 * groups
    if n <= 8 * 128:
        groups, block_keys = 1, 8 * 128
    pad = (-n) % block_keys
    if pad:
        probe_hash = jnp.concatenate(
            [probe_hash, jnp.zeros((pad,), probe_hash.dtype)]
        )
    rows = probe_hash.shape[0] // (128 * groups)
    plo, phi = _split64(probe_hash)
    plo2 = plo.reshape(rows, 128 * groups)
    phi2 = phi.reshape(rows, 128 * groups)

    grid = (rows // 8,)
    pblk = pl.BlockSpec((8, 128 * groups), lambda j: (j, 0))
    tblk = pl.BlockSpec((tiles, 8, 128), lambda j: (0, 0, 0))
    kernel = functools.partial(
        _dim_kernel, tiles=tiles, groups=groups, max_probes=max_probes
    )

    def call():
        return pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((rows, 128 * groups), jnp.int32),
                jax.ShapeDtypeStruct((rows, 128 * groups), jnp.int32),
            ),
            grid=grid,
            in_specs=[pblk, pblk, tblk, tblk, tblk, tblk],
            out_specs=(pblk, pblk),
            interpret=interpret,
        )(plo2, phi2, *tables)

    if interpret:
        start, cnt = call()
    else:
        # the engine runs with jax_enable_x64 for i64 columns, but x64
        # tracing breaks Mosaic's loop legalization (bisected on
        # hardware); the kernel is all-32-bit, so trace it in a local
        # x64-off context
        with jax.enable_x64(False):
            start, cnt = call()
    return start.reshape(-1)[:n], cnt.reshape(-1)[:n]


def probe_index(probe_hash: jnp.ndarray, tables, layout, *,
                interpret: bool = False):
    """Per probe row, the hash-sorted build segment (start, count) of
    equal-hash valid build rows ((-1, 0) when none)."""
    _, tiles = layout
    return _probe_dim(probe_hash, tables, tiles, interpret=interpret)


# ------------------------------------------------------- unique wrapper


def join_unique(
    build_keys: jnp.ndarray,
    build_valid: jnp.ndarray,
    probe_keys: jnp.ndarray,
    probe_valid: jnp.ndarray,
    *,
    interpret: bool = False,
):
    """Unique-build-key inner-join mapping: per probe row the matching
    VALID build row id, or -1. Uses the IDENTITY u64 encoding as the
    hash, so in-kernel (lo, hi) equality IS key equality — callers may
    extend rows by the returned id without re-verification.

    Returns (row_ids int32, overflow)."""
    nb = int(build_keys.shape[0])
    layout = plan_layout(nb)
    tables, perm, overflow = build_index(
        build_keys.astype(jnp.uint64), build_valid, layout
    )
    start, cnt = probe_index(
        probe_keys.astype(jnp.uint64), tables, layout, interpret=interpret
    )
    hit = probe_valid & (cnt > 0)
    rid = jnp.where(
        hit,
        perm[jnp.clip(start, 0, None)].astype(jnp.int32),
        jnp.int32(-1),
    )
    return rid, overflow
