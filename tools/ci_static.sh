#!/usr/bin/env bash
# Pre-PR static gate: the engine-invariant linter, the concurrency
# soundness pass (lock registry + acquisition graph +
# blocking-under-lock), the host<->device transfer audit (transfer
# registry + plane classification + choke-point routing), the full
# plan audit (plans at served scale + TPC-H/TPC-DS corpus, strict
# mode), and the wire-serde property suite (codec x type round-trip
# matrix, byte-stability, truncation/corruption rejection — the
# pure-serde subset; the WorkerServer-backed streaming/pool tests stay
# in tier 1). All legs are pure host Python — nothing compiles or
# touches a device. The serving races under the lock sanitizer run in
# tier 1 (tests/test_concurrent_serving.py), the Pallas kernel in
# tests/test_pallas_join.py.
#
# Usage: tools/ci_static.sh   (exit nonzero on any finding/violation)
set -euo pipefail
cd "$(dirname "$0")/.."

t0=$(date +%s)
echo "# ci_static: engine-invariant lint (python -m tools.lint)" >&2
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.lint

echo "# ci_static: concurrency soundness (tools/concheck.py)" >&2
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python tools/concheck.py

echo "# ci_static: transfer audit (tools/xfercheck.py)" >&2
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python tools/xfercheck.py

echo "# ci_static: plan audit (tools/plan_audit.py)" >&2
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python tools/plan_audit.py

echo "# ci_static: wire-serde property suite (tests/test_wire_serde.py)" >&2
# pure-serde subset: everything that does not spin a WorkerServer
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
    tests/test_wire_serde.py -q -p no:cacheprovider \
    -k "not spooled_task and not connpool and not streaming \
        and not q3_family and not executor_surface"

echo "# ci_static: clean in $(( $(date +%s) - t0 ))s" >&2
