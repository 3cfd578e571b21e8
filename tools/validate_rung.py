"""Validate ONE bench rung in a fresh process: run the query once
end-to-end (decode included), print a single JSON line for bench.py.

Why a subprocess: on an earlier TPU runtime any device->host read degrades the
whole process (and some transfers are pathologically slow or hang), so
bench.py keeps its timing child D2H-clean and farms decoding out here,
one bounded child per rung — a slow or faulting rung then cannot poison
the other rungs' validation (observed 2026-07-30: a single >=4M-row
buffer hang lost a full ladder's decode phase).

Usage: validate_rung.py {tpch|tpcds} QID SF [k=v session props...]
"""

import json
import os
import sys
import time
import zlib

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from tools._common import configure_jax, make_runner, queries  # noqa: E402


def main() -> int:
    suite, qid, sf = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    configure_jax()
    runner = make_runner(suite, sf, props=sys.argv[4:])
    t0 = time.time()
    result = runner.execute(queries(suite)[qid])
    wall = time.time() - t0
    # order-insensitive row checksum (verifier-style) so runs can be
    # compared across processes/rounds without shipping rows
    csum = 0
    for row in result.rows:
        csum = (csum + zlib.crc32(repr(row).encode())) & 0xFFFFFFFF
    print(json.dumps({
        "rows": len(result.rows),
        "wall_with_decode_s": round(wall, 2),
        "checksum_crc32": csum,
        "capacity_boost": runner.executor._capacity_boost,
        "pallas_joins_used": runner.executor.pallas_joins_used,
        "head": [str(v)[:24] for v in (result.rows[0] if result.rows
                                       else [])],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
