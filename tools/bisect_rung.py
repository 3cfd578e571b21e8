"""Run ONE bench rung in a fresh process with immediate decode.

Usage: python tools/bisect_rung.py {tpch|tpcds} QID SF [k=v ...]

Isolates >=4M-row kernel-fault / slow-D2H diagnosis (see
.claude/skills/verify/SKILL.md): a rung whose decode hangs or raises
UNAVAILABLE here has a faulting buffer somewhere in its pipeline;
bench.py's orchestrator runs every phase in bounded children, so use
this to bisect exactly which rung (or which session-property
configuration, e.g. spill_threshold_bytes=33554432) misbehaves.
"""

import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import configure_jax, make_runner, queries  # noqa: E402


def main() -> int:
    suite, qid, sf = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    jax = configure_jax()
    runner = make_runner(suite, sf, props=sys.argv[4:])
    plan = runner.plan(queries(suite)[qid])
    ex = runner.executor
    pages = []
    from presto_tpu.devsync import drain

    for label in ("compile", "steady", "steady2"):
        t0 = time.time()
        ex._pending_overflow = []
        pages = list(ex.pages(plan))
        # drain protocol (block_until_ready returned at dispatch on an
        # earlier TPU runtime) — honest wall = dispatch + FIFO-draining
        # read
        drain(pages)
        ex._stream_cache = {}
        print(f"{label} {time.time() - t0:.3f}s", flush=True)
    flags = list(ex._pending_overflow)
    t0 = time.time()
    rows = []
    for p in pages:
        rows.extend(p.to_pylist())
    decode_s = time.time() - t0
    overflow = any(bool(f) for f in flags)
    print(f"decode {decode_s:.1f}s rows={len(rows)} "
          f"overflow={overflow}", flush=True)
    print("sample:", rows[0] if rows else None, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
