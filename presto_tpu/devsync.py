"""Forced device synchronization for honest timing.

Not re-verified on the current chip (ROADMAP C3): on an earlier TPU
runtime ``jax.block_until_ready`` returned at dispatch — it did NOT
wait for device completion, and queued work drained only when a
device->host read forced it. Every timing path in the tree (the
executor's EXPLAIN ANALYZE stats_drain mode) must use THIS helper so a
future protocol correction lands in one place.
"""

from __future__ import annotations


def drain(tree) -> None:
    """Force REAL completion of all device work queued before ``tree``
    was produced: reads one element of the last leaf; FIFO execution
    order means everything queued earlier has truly finished. Costs
    ~0.1s on an empty queue; dispatch+drain cycles are repeatable."""
    import jax
    import numpy as np

    from presto_tpu.exec.xfer import device_wait

    leaves = jax.tree_util.tree_leaves(tree)
    if leaves and hasattr(leaves[-1], "ravel") and leaves[-1].size:
        with device_wait("drain"):
            np.asarray(leaves[-1].ravel()[:1])
