"""The driver's multi-chip gate, wired into the test suite.

__graft_entry__.dryrun_multichip is the contract the driver snapshot
checks between rounds: an n-device virtual mesh running the FULL
distributed engine step with exact single-device parity. It regressed
silently between snapshots once because nothing in
tier-1 exercised it — this wrapper makes any future break loud.

Runs in a SUBPROCESS because dryrun_multichip must set
XLA_FLAGS/JAX_PLATFORMS before jax initializes a backend, and the
pytest process (conftest.py) has long since latched its own 8-device
CPU config. Marked slow: it compiles the 8-device shard_map program
family (~minutes cold; the conftest-warmed persistent compile cache
makes repeat runs cheap).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_multichip_subprocess():
    env = dict(os.environ)
    # fresh backend latch for the child; the persistent compile cache
    # (conftest default or the caller's override) carries over
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import __graft_entry__ as g; g.dryrun_multichip(8)",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    assert proc.returncode == 0, (
        f"dryrun_multichip(8) failed (rc={proc.returncode})\n"
        f"stdout tail: {proc.stdout[-800:]}\n"
        f"stderr tail: {proc.stderr[-1500:]}"
    )
    assert "distributed == single" in proc.stdout
