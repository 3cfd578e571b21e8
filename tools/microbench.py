"""Honest per-op microbenchmarks on an earlier TPU runtime.

Timing protocol (round-4 discovery, see bench.py docstring): on an
earlier TPU runtime `jax.block_until_ready` returned at dispatch — it
did NOT wait for device completion. Queued work drained only when a
device->host read forced it. So every measurement here is a dispatch+drain cycle:

    t0; dispatch N launches; np.asarray(last.ravel()[0]); t1

The first cycle per program pays a one-time flush and is discarded;
subsequent cycles are stable (+-5%). The tiny read's own cost (~0.1s
when the queue is empty) amortizes over N.

Usage: python tools/microbench.py [rows_log2=18]
"""

import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import configure_jax  # noqa: E402


def main() -> int:
    rows_log2 = int(sys.argv[1]) if len(sys.argv) > 1 else 18
    n = 1 << rows_log2
    jax = configure_jax()
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    vals = jnp.ones((n,), jnp.int64)
    ids4096 = jnp.arange(n, dtype=jnp.int32) % 4096
    ids4 = ids4096 % 4
    fvals = vals.astype(jnp.float32)
    np.asarray(vals[0])  # initial flush

    from presto_tpu.devsync import drain

    def cycle(tag, f, *args, reps=20, cycles=3):
        y = f(*args)
        drain(y)  # warm + first flush
        best = None
        for _ in range(cycles):
            t0 = time.time()
            for _ in range(reps):
                y = f(*args)
            drain(y)
            dt = (time.time() - t0) / reps
            best = dt if best is None else min(best, dt)
        rate = n / best / 1e6
        print(f"{tag:44s} {best*1e3:8.2f} ms  {rate:9.0f} M rows/s")
        return best

    jit = jax.jit
    cycle("noop (launch overhead)", jit(lambda v: v[:8] * 2), vals)
    cycle("elementwise i64 mul+add", jit(lambda v: v * 2 + 1), vals)
    cycle("reduce-sum i64", jit(lambda v: jnp.sum(v)), vals)
    cycle("scatter segsum G=4096", jit(
        lambda v, i: jax.ops.segment_sum(v, i, num_segments=4096)),
        vals, ids4096)
    cycle("scatter segsum G=4", jit(
        lambda v, i: jax.ops.segment_sum(v, i, num_segments=4)),
        vals, ids4)
    cycle("scatter segsum G=4096 sorted-flag", jit(
        lambda v, i: jax.ops.segment_sum(
            v, i, num_segments=4096, indices_are_sorted=True)),
        vals, jnp.sort(ids4096))

    def where_agg(v, i):
        return jnp.stack([jnp.sum(jnp.where(i == g, v, 0))
                          for g in range(4)])
    cycle("where+sum x4 i64", jit(where_agg), vals, ids4)

    def onehot_i8(v, i, G):
        # exact int64 aggregation on the MXU: 8x8-bit limb decompose,
        # i8 one-hot, dot with i32 accumulation, recombine
        oh = (i[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
              ).astype(jnp.int8)
        limbs = jnp.stack(
            [((v >> (8 * k)) & 0xFF).astype(jnp.int8) for k in range(8)]
        )  # (8, n)
        acc = jax.lax.dot_general(
            limbs, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8, G)
        return jnp.sum(acc.astype(jnp.int64)
                       << (8 * jnp.arange(8, dtype=jnp.int64))[:, None],
                       axis=0)
    cycle("one-hot i8 matmul G=4 (exact)", jit(
        lambda v, i: onehot_i8(v, i, 4)), vals, ids4)
    cycle("one-hot i8 matmul G=64 (exact)", jit(
        lambda v, i: onehot_i8(v, i, 64)), vals, ids4096 % 64)
    cycle("one-hot i8 matmul G=1024 (exact)", jit(
        lambda v, i: onehot_i8(v, i, 1024)), vals, ids4096 % 1024)

    cycle("one-hot f32 matmul G=4", jit(
        lambda v, i: (v.astype(jnp.float32)[None, :]
                      @ jax.nn.one_hot(i, 4, dtype=jnp.float32))),
        fvals, ids4)
    cycle("sort [i32 key, i64 val]", jit(
        lambda v, i: jax.lax.sort([i, v], num_keys=1)), vals, ids4096)
    cycle("argsort i32", jit(lambda i: jnp.argsort(i)), ids4096)
    cycle("cumsum i64", jit(lambda v: jnp.cumsum(v)), vals)
    cycle("gather 256k from 256k", jit(
        lambda v, i: v[i]), vals, ids4096 * 0 + jnp.arange(n) % n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
