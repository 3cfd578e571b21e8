"""Late materialization for join chains: deferred build-side gathers.

Reference: presto-spi spi/block/DictionaryBlock.java — the reference
engine's joins emit DictionaryBlocks over the build-side PagesIndex
(positions + a shared values block) so carried columns are never copied
per operator; values materialize once, at the first consumer that needs
them. The TPU translation (the join chain is
gather-bound at ~25 M rows/s per carried column, floor = 1 gather per
column per JOIN) replaces the per-join value gathers with ONE int64
row-id indirection column per build side:

  - a join emits its probe columns plus one id Block (build row per
    output row) instead of gathering every carried build column;
  - a downstream join gathers the id column like any probe column, so
    N chained joins COMPOSE the indirection into one id column per
    build side (ids' = ids[probe_idx] — a single gather per side per
    join, independent of how many columns the side carries);
  - join keys a downstream join needs are lifted (gathered) eagerly,
    one column each (``lift_page``);
  - everything else gathers exactly once, at the chain boundary
    (``finish_page``) — the first consumer that needs values (final
    project / aggregation / output).

The executor drives this through ``LazyPage`` items (exec/executor.py
``_lazy_pages`` / ``_join_pass(defer=True)``); pages leaving the join
subtree are always fully materialized, so every other operator is
untouched. ``Block.take`` (page.py) is the shared indirection
primitive.

Physical layout of ``LazyPage.reduced``: the materialized logical
channels in ascending logical order, then ONE id Block per deferred
side (side i at position ``len(mat) + i``). An id Block's ``nulls``
marks rows whose build side is SQL NULL (left-join padding); value
materialization ORs it over the gathered build nulls.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp

from presto_tpu.page import Page


@dataclasses.dataclass
class LazySide:
    """One deferred build side: the retained build page plus the map
    from logical output channels to build channels."""

    build: Page
    channel_map: Tuple[Tuple[int, int], ...]  # (logical channel, build ch)


@dataclasses.dataclass
class LazyPage:
    """A join output page with deferred build sides (see module doc)."""

    reduced: Page
    width: int  # logical channel count of the node's output
    mat: Tuple[int, ...]  # materialized logical channels, ascending
    sides: Tuple[LazySide, ...]

    def phys(self, channel: int) -> int:
        """Physical position of a MATERIALIZED logical channel."""
        return self.mat.index(channel)

    def signature(self):
        """Static layout key (jit cache / kernel-recipe identity)."""
        return (
            self.width,
            self.mat,
            tuple(s.channel_map for s in self.sides),
        )


def lift_layout(mat, maps, need):
    """Static recipe shared by ``lift_page`` and the executor's host
    bookkeeping: materializing ``need`` moves those channels into the
    sorted mat set and drops them (and empty sides) from the deferred
    maps. Returns (need, new_mat, new_maps, surviving side indices)."""
    need = tuple(sorted(set(need) - set(mat)))
    new_mat = tuple(sorted(set(mat) | set(need)))
    new_maps = tuple(
        tuple(pair for pair in m if pair[0] not in need) for m in maps
    )
    keep = tuple(i for i, m in enumerate(new_maps) if m)
    return need, new_mat, new_maps, keep


def _side_ids(id_block, build):
    return jnp.clip(
        id_block.data.astype(jnp.int64), 0, build.capacity - 1
    )


def lift_page(mat, maps, need, reduced: Page, *builds) -> Page:
    """Kernel: materialize the ``need`` channels (one gather each) and
    re-emit the reduced page in lift_layout order. Used for downstream
    join keys and filter-referenced channels — the liveness-driven
    eager subset of the ISSUE's contract."""
    need, new_mat, new_maps, keep = lift_layout(mat, maps, need)
    nm = len(mat)
    got = {}
    for si, (m, build) in enumerate(zip(maps, builds)):
        id_block = reduced.blocks[nm + si]
        wanted = [pair for pair in m if pair[0] in need]
        if not wanted:
            continue
        ids = _side_ids(id_block, build)
        for oc, bc in wanted:
            got[oc] = build.blocks[bc].take(
                ids, extra_nulls=id_block.nulls
            )
    blocks = []
    for c in new_mat:
        if c in got:
            blocks.append(got[c])
        else:
            blocks.append(reduced.blocks[mat.index(c)])
    for si in keep:
        blocks.append(reduced.blocks[nm + si])
    return Page(blocks=tuple(blocks), valid=reduced.valid)


def finish_page(mat, maps, width, reduced: Page, *builds) -> Page:
    """Kernel: full materialization at the chain boundary — every
    deferred column gathers exactly ONCE through its side's composed
    id column; materialized channels pass through."""
    blocks = [None] * width
    for i, c in enumerate(mat):
        blocks[c] = reduced.blocks[i]
    nm = len(mat)
    for si, (m, build) in enumerate(zip(maps, builds)):
        id_block = reduced.blocks[nm + si]
        ids = _side_ids(id_block, build)
        for oc, bc in m:
            blocks[oc] = build.blocks[bc].take(
                ids, extra_nulls=id_block.nulls
            )
    assert all(b is not None for b in blocks)
    return Page(blocks=tuple(blocks), valid=reduced.valid)
