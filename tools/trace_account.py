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
                         operation, the last phase's end against the
                         last, and where the first chip idled
                         (``idle_by_host_span``, below)
    record CELL ID...    on the chip: the cell's coordinator (the
                         benchmark's own configuration and statements),
                         each statement id served once to load it and
                         once more, whole, under the profiler; keeps
                         each recording and prints its account, with
                         the statement's ``phases`` as /v1/query/{id}
                         gives them (``query_info_phases``) beside it

The harness's ``--keep-trace`` run keeps its recording at
``.perfbench/<cell>/kept.xplane.pb.gz``: ``read`` that.

The two planes joined (ISSUE 41). ``/v1/query/{id}``'s ``phases`` give
the ``execute`` phase a ``spans`` list: every ``launch``, ``wait``,
``xfer`` and ``eager`` span of its attempts in microseconds from
submission (the benchmark reads ``execute_self_ms_per_query``,
``first_launch_ms`` and ``execute_tail_ms`` from it). The same
intervals are annotations of the recording's host plane, so per
``execute:<query id>`` the account has

    idle_by_host_span    the first chip's gaps between merged ``XLA
                         Ops`` intervals inside the statement, its two
                         ends included, each gap's length split among
                         the innermost of the program's annotations in
                         flight over it (``launch:<label>``,
                         ``wait:<site>``, ``eager:<site>``,
                         ``xfer:h2d:<label>``, a phase's name, or
                         ``execute`` where none is): seconds and gaps
                         by annotation, most first; the parts sum to
                         ``idle_s``
    idle_before_first_op_s, idle_after_last_op_s
                         the two ends of it
    uncovered_s, uncovered_by_event
                         the stretches of the statement that no
                         launch / wait / eager / xfer annotation
                         covers, and the other events of the driver
                         thread's line in them (``PjitFunction(<name>)``
                         of an eager ``jnp`` call), by name: where the
                         next span belongs

``attribute_idle(gaps, annotations)`` is the attribution alone, a pure
function of intervals: what a ``benchmark`` issue hands to
``harness/trace.reduce`` in ``cell._gap_labeller``'s place.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import shutil
import sys
from typing import Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PHASES = ("queue", "parse", "plan", "encode")
TOP_EVENTS = 12
# the annotations that are real intervals of a thread at one site
# (obs/trace.INTERVAL_KINDS, not imported: that would import jax with
# this module): the ones a device gap is put down to, innermost first
# (``attempt`` is a container and ``execute:<id>`` the statement itself)
SITE_KINDS = ("launch", "wait", "eager", "xfer", "resident_load",
              "join_build")
Interval = Tuple[float, float]
Note = Tuple[str, float, float]


def _kind(name: str) -> str:
    return name.split(":", 1)[0]


def innermost(annotations: Sequence[Note], lo: float, hi: float,
              default: str) -> List[Note]:
    """[lo, hi] cut into (name, start, end) pieces by the innermost
    annotation in flight: of those that cover a piece the one begun
    last (the shortest of those begun together); ``default`` where
    none does."""
    live = [n for n in annotations if n[2] > lo and n[1] < hi]
    cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                              for _n, a, b in live for t in (a, b)})
    out: List[Note] = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(n0, a0, b0) for n0, a0, b0 in live
                if a0 <= a and b <= b0]
        name = (max(over, key=lambda n: (n[1], -n[2]))[0] if over
                else default)
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def attribute_idle(gaps: Sequence[Interval],
                   annotations: Sequence[Note],
                   default: str = "execute") -> List[Dict]:
    """Each gap's length split among the innermost annotations in
    flight over it, summed by annotation: ``[{"span", "idle_s",
    "gaps"}]``, most idle first. A pure function of intervals on one
    clock: the gaps of a device (``harness/trace.gaps``) and the
    (name, start, end) annotations of the host plane."""
    if not gaps:
        return []
    pieces = innermost(annotations, min(a for a, _b in gaps),
                       max(b for _a, b in gaps), default)
    starts = [a for _n, a, _b in pieces]
    by_name: Dict[str, List[float]] = {}
    for lo, hi in gaps:
        seen = set()
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][1] < hi:
            name, a, b = pieces[i]
            share = min(b, hi) - max(a, lo)
            if share > 0:
                acc = by_name.setdefault(name, [0.0, 0])
                acc[0] += share
                if name not in seen:
                    seen.add(name)
                    acc[1] += 1
            i += 1
    return [{"span": name, "idle_s": s, "gaps": n}
            for name, (s, n) in sorted(by_name.items(),
                                       key=lambda kv: -kv[1][0])]


def statement_idle(a: float, b: float, ops: Sequence[Note],
                   notes: Sequence[Note], line_events: Sequence[Note]
                   ) -> Dict:
    """Where one chip idled inside the statement [a, b] and what the
    host was doing meanwhile: ``ops`` that chip's ``XLA Ops`` events,
    ``notes`` the program's annotations, ``line_events`` every event of
    the host line that holds the statement's annotation."""
    from benchmarks.harness import trace as tracing

    busy = [(max(lo, a), min(hi, b)) for _n, lo, hi in ops
            if hi > a and lo < b]
    # the statement's own ends count as gaps too
    idle = tracing.gaps([(a, a)] + busy + [(b, b)])
    sites = [n for n in notes if _kind(n[0]) in SITE_KINDS + PHASES]
    covered = [(lo, hi) for n, lo, hi in sites
               if _kind(n) in SITE_KINDS]
    bare = tracing.gaps([(a, a)] + [
        (max(lo, a), min(hi, b)) for lo, hi in covered
        if hi > a and lo < b] + [(b, b)])
    by_event: Dict[str, List[float]] = {}
    bare_ends = [hi for _lo, hi in bare]
    for name, lo, hi in line_events:
        if _kind(name) in SITE_KINDS + PHASES + ("execute", "attempt"):
            continue
        # an event of the line counts where it overlaps a bare stretch
        # (nested events each count their own overlap)
        i = bisect.bisect_left(bare_ends, lo)
        inside = 0.0
        while i < len(bare) and bare[i][0] < hi:
            inside += max(min(hi, bare[i][1]) - max(lo, bare[i][0]), 0)
            i += 1
        if inside > 0:
            acc = by_event.setdefault(name, [0.0, 0])
            acc[0] += inside
            acc[1] += 1
    return {
        "idle_s": sum(hi - lo for lo, hi in idle),
        "idle_gaps": len(idle),
        "idle_before_first_op_s": (min(lo for lo, _ in busy) - a
                                   if busy else b - a),
        "idle_after_last_op_s": (b - max(hi for _, hi in busy)
                                 if busy else 0.0),
        "idle_by_host_span": attribute_idle(idle, sites),
        "uncovered_s": sum(hi - lo for lo, hi in bare),
        "uncovered_by_event": [
            {"event": name, "s": s, "events": n}
            for name, (s, n) in sorted(
                by_event.items(), key=lambda kv: -kv[1][0])[:TOP_EVENTS]],
    }


def account(path: str) -> Dict:
    from benchmarks.harness import trace as tracing
    from presto_tpu.exec.programs import family_of

    host_lines, notes = [], []
    modules, ops = [], []
    first_chip_ops: List[Note] = []
    first_chip = None
    line_of: Dict[str, List[Note]] = {}
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
                chip = int(device.group(1))
                if first_chip is None or chip < first_chip:
                    first_chip, first_chip_ops = chip, events
            elif plane.name == "/host:CPU":
                mine = [e for e in events
                        if _kind(e[0]) in
                        ("execute", "attempt") + SITE_KINDS + PHASES]
                if mine:
                    kinds: Dict[str, int] = {}
                    for name, _a, _b in mine:
                        k = _kind(name)
                        kinds[k] = kinds.get(k, 0) + 1
                        if k == "execute":
                            line_of[name] = events
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
            "first_chip": first_chip,
            **statement_idle(a, b, first_chip_ops, notes,
                             line_of.get(name, ())),
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


def spans_against_counters(phases, metrics: Dict) -> Dict:
    """The ``execute`` phase's spans beside the counters they are cut
    from (the serial path's /metrics after the statement): as many
    ``launch`` spans as ``device_launches``, their summed length
    ``dispatch_wall_us``, the pulls' and waits' ``device_wait_us``."""
    spans = next((p.get("spans", ()) for p in phases or ()
                  if p["kind"] == "execute"), ())

    def us(keep) -> int:
        return sum(s["endUs"] - s["startUs"] for s in spans if keep(s))

    def launch(s) -> bool:
        return s["kind"] == "launch"

    return {
        "launch_spans": sum(1 for s in spans if launch(s)),
        "device_launches": metrics.get("device_launches"),
        "launch_span_us": us(launch),
        "dispatch_wall_us": metrics.get("dispatch_wall_us"),
        "wait_span_us": us(lambda s: s["kind"] == "wait" or (
            s["kind"] == "xfer" and s["name"].startswith("d2h:"))),
        "device_wait_us": metrics.get("device_wait_us"),
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
            metrics = served.metrics()
            acc["spans_against_counters"] = spans_against_counters(
                acc["query_info_phases"], metrics)
            acc["metrics_after"] = {
                k: v for k, v in metrics.items()
                if k in ("device_launches", "program_launches",
                         "exchange_launches", "mesh_fused_rounds",
                         "mesh_batched_rounds",
                         "row_counts_launched", "row_counts_eager",
                         "dispatch_wall_us", "device_wait_us",
                         "spill_partitions_used",
                         "resident_splits_scanned",
                         "resident_bytes_scanned",
                         "join_builds", "join_build_rows",
                         "join_build_bytes", "join_build_wall_us",
                         "join_probes_at_build",
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
