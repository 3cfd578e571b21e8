#!/usr/bin/env python
"""Reads a profiler recording by the program's own account (ISSUE 25).

The recorder's spans, every program launch and every wait on the device
are ``jax.profiler.TraceAnnotation``s, so a recording's ``/host:CPU``
plane holds ``execute:<query id>``, ``launch:<label>``, ``wait:<site>``
and the phases beside ``/device:TPU:<n>``'s ``XLA Modules`` (one event a
program run, ``jit_<label>(<fingerprint>)``) and ``XLA Ops``, on one
clock. Two commands:

    read FILE            the account of an .xplane.pb(.gz): which host
                         lines hold the annotations, device time by
                         program with its family, the share of it under
                         a declared label, and per execute:<id> the
                         first launch against the first device
                         operation and the last phase's end against the
                         last
    record CELL ID...    on the chip: the cell's coordinator (the
                         benchmark's own configuration and statements),
                         each statement id served once to load it and
                         once more, whole, under the profiler; keeps
                         each recording and prints its account

The harness's ``--keep-trace`` run keeps its recording at
``.perfbench/<cell>/kept.xplane.pb.gz``: ``read`` that.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PHASES = ("queue", "parse", "plan", "encode")


def account(path: str) -> Dict:
    from benchmarks.harness import trace as tracing
    from presto_tpu.exec.programs import family_of

    host_lines, notes = [], []
    modules, ops = [], []
    for plane in tracing.load(path).planes:
        device = tracing.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [(e.name, e.start_ns / 1e9,
                       (e.start_ns + e.duration_ns) / 1e9)
                      for e in line.events]
            if device and line.name == tracing.MODULES_LINE:
                modules += events
            elif device and line.name == tracing.OPS_LINE:
                ops += events
            elif plane.name == "/host:CPU":
                mine = [e for e in events
                        if e[0].split(":", 1)[0] in
                        ("execute", "launch", "wait", "attempt") + PHASES]
                if mine:
                    kinds: Dict[str, int] = {}
                    for name, _a, _b in mine:
                        k = name.split(":", 1)[0]
                        kinds[k] = kinds.get(k, 0) + 1
                    host_lines.append({"line": line.name,
                                       "events": len(events),
                                       "annotations": kinds})
                    notes += mine
    by_program: Dict[str, List[float]] = {}
    for name, a, b in modules:
        by_program.setdefault(name, []).append(b - a)
    total = sum(sum(v) for v in by_program.values())
    programs = [{"program": name, "family": family_of(name),
                 "runs": len(v), "device_s": sum(v)}
                for name, v in sorted(by_program.items(),
                                      key=lambda kv: -sum(kv[1]))]
    labelled = sum(p["device_s"] for p in programs if p["family"])
    statements = []
    for name, a, b in sorted(n for n in notes
                             if n[0].startswith("execute:")):
        inside = [m for m in modules if a <= m[1] <= b]
        launches = sorted(n[1] for n in notes
                          if n[0].startswith("launch:") and a <= n[1] <= b)
        ops_in = [o for o in ops if a <= o[1] <= b]
        encode = [n for n in notes if n[0] == "encode" and
                  b - 1e-4 <= n[1] <= b + 1e-3]
        mine: Dict[str, float] = {}
        for m in inside:
            label = m[0].split("(", 1)[0]
            mine[label] = mine.get(label, 0.0) + (m[2] - m[1])
        statements.append({
            "annotation": name, "start_s": a, "end_s": b,
            "launches": len(launches),
            "waits": sum(1 for n in notes if n[0].startswith("wait:")
                         and a <= n[1] <= b),
            "first_launch_s": launches[0] if launches else None,
            "first_device_op_s": min((o[1] for o in ops_in),
                                     default=None),
            "last_device_op_end_s": max((o[2] for o in ops_in),
                                        default=None),
            "encode_end_s": encode[0][2] if encode else None,
            "device_s_by_program": dict(sorted(
                mine.items(), key=lambda kv: -kv[1])),
        })
    return {
        "file": path,
        "host_lines_with_annotations": host_lines,
        "program_runs": len(modules), "op_events": len(ops),
        "device_program_s": total,
        "labelled_share": labelled / total if total else None,
        "unknown_programs": [p["program"] for p in programs
                             if "unknown" in p["program"]],
        "programs": programs,
        "statements": statements,
    }


def _served(result, st):
    if result.error or result.state != "FINISHED":
        raise RuntimeError(f"{st.key} {result.state}: {result.error}")


def record(cell_name: str, sids: List[str], out_dir: str,
           rehearse: bool = False) -> List[Dict]:
    from benchmarks.harness import manifest, serve
    from benchmarks.harness import trace as tracing

    cell = manifest.load_cell(cell_name)
    work = os.path.join(out_dir, "work")
    serve.write_etc(os.path.join(work, "etc"), cell.config, rehearse)
    served = serve.Served(os.path.join(work, "etc"), cell.chips)
    out = []
    try:
        for sid in sids:
            st = cell.statements[sid][0]
            client = served.client(st.catalog)
            for _ in range(2):  # loaded, then once more: steady state
                _served(client.execute(st.sql), st)
            rec = tracing.Recorder(os.path.join(work, f"trace_{sid}"))
            rec.start()
            result = client.execute(st.sql)
            rec.stop()
            _served(result, st)
            kept = os.path.join(out_dir, f"{sid}.xplane.pb.gz")
            with open(rec.xplane(), "rb") as src, \
                    gzip.open(kept, "wb") as dst:
                shutil.copyfileobj(src, dst)
            shutil.rmtree(rec.out_dir, ignore_errors=True)
            acc = account(kept)
            acc["statement"] = st.key
            acc["query_id"] = result.query_id
            acc["query_info_phases"] = served.query_info(
                result.query_id).get("phases")
            acc["metrics_after"] = {
                k: v for k, v in served.metrics().items()
                if k in ("device_launches", "program_launches",
                         "exchange_launches", "mesh_fused_rounds",
                         "mesh_batched_rounds",
                         "row_counts_launched", "row_counts_eager",
                         "dispatch_wall_us", "device_wait_us",
                         "spill_partitions_used",
                         "resident_splits_scanned",
                         "resident_bytes_scanned",
                         "resident_table_bytes", "resident_loads",
                         "resident_load_wall_us")}
            out.append(acc)
    finally:
        served.stop()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    rd = sub.add_parser("read")
    rd.add_argument("file")
    rd.add_argument("--out")
    rc = sub.add_parser("record")
    rc.add_argument("cell")
    rc.add_argument("statement_ids", nargs="+")
    rc.add_argument("--out", required=True, help="directory")
    rc.add_argument("--rehearse", action="store_true",
                    help="SF0.01, for a try on the CPU")
    args = ap.parse_args(argv)
    if args.cmd == "read":
        result = account(args.file)
        out = args.out
    else:
        os.makedirs(args.out, exist_ok=True)
        result = record(args.cell, args.statement_ids, args.out,
                        args.rehearse)
        out = os.path.join(args.out, "account.json")
    text = json.dumps(result, indent=1)
    if out:
        with open(out, "w") as f:
            f.write(text + "\n")
    print(text if len(text) < 20000 else text[:20000] + "\n...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
