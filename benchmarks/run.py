#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

One process holds the chip: it starts the coordinator in-process from
the cell's configuration file, drives it over real HTTP on localhost
from client threads, and compares every completed statement with the
plain reference after the window. The last line of standard output is
the result object. Without a TPU it exits non-zero and prints no result;
``--rehearse`` is the CPU rehearsal at SF0.01, which prints no metric.
See benchmarks/README.md.
"""

import faulthandler
import os
import sys
import time

# a crash in native code (exit code 139) leaves every thread's Python
# stack on standard error, which is all a later reader gets to see
faulthandler.enable()

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmarks.harness import cell

    sys.exit(cell.main(sys.argv[1:], T_PROCESS))
