"""One run of one cell: set-up, window, and after the window the
comparison with the plain reference and the metrics.

    set-up   where the persistent cache is not known to hold the cell's
             programs (a checkout's first run), compile them in a child
             process that ends before this one touches the chip; start
             the coordinator from the cell's configuration; serve each
             statement once, which loads its programs (serve.warm: all
             at once on the concurrent server, in a fixed order on the
             serial path)
    window   the cell's clients, closed loop, for --seconds (traffic.py);
             a traced run records the device for a shorter window, the
             traffic file's ``traced_seconds``
    after    statements in flight finish and count; every completed
             statement's decoded rows are compared with the reference
             (reference.py); a program compiled inside the window makes
             the run not correct; counters and the trace are read

Every line printed is one JSON object; the last is the result.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

from . import layers, manifest, reference, scanbytes, serve, stats, traffic
from . import trace as tracing

WORK_DIR = os.path.join(manifest.ROOT, ".perfbench")


_log_lock = threading.Lock()


def log(**record) -> None:
    """One JSON object on one line, whole, whichever thread writes."""
    with _log_lock:
        sys.stdout.write(json.dumps(record) + "\n")
        sys.stdout.flush()


def _device_or_exit(cell, rehearse: bool):
    """The devices this process holds, or SystemExit: no fallback."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform != "cpu":
            raise SystemExit(
                "--rehearse is the CPU rehearsal; this process holds "
                f"{dev.platform!r} devices")
        if len(devices) < cell.chips:
            raise SystemExit(
                f"rehearsal of a {cell.chips}-chip cell needs that many "
                "virtual devices (XLA_FLAGS="
                "--xla_force_host_platform_device_count)")
        return devices, None
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0].platform == {dev.platform!r}; the "
            "benchmark measures on the chip only (--rehearse rehearses "
            "on the CPU and prints no metric)")
    if len(devices) != cell.chips:
        raise SystemExit(
            f"cell {cell.name} asks for {cell.chips} chip(s), JAX reports "
            f"{len(devices)}")
    return devices, manifest.load_peaks(dev.device_kind)


def _memory_peaks(devices) -> List[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def _end_to_end(cell, samples: List, t_window: float, seconds: float
                ) -> Dict[str, float]:
    """The cell's end-to-end metrics but setup_s, from the correct
    completed statements' raw client-side times."""
    ok = [s for s in samples if s.ok]
    by_group: Dict[str, List[float]] = {}
    for s in ok:
        by_group.setdefault(s.statement.sid, []).append(
            s.latency_s * 1e3)
    interactive = [s.latency_s * 1e3 for s in ok
                   if s.statement.klass == "interactive"]
    p90, n_interactive = stats.tail(interactive, 0.90)
    # the interactive tail is printed, not a metric: in this saturated
    # closed loop it spreads 16-19 % from run to run (PERF.md)
    log(phase="stats", interactive_samples=n_interactive,
        interactive_p90_ms=p90,
        min_tail_samples=stats.min_tail_samples(0.90),
        statements_by_group={g: len(v) for g, v in by_group.items()},
        median_ms_by_group={
            g: stats.quantile(v, 0.5) for g, v in by_group.items()})
    geomean = stats.geomean_of_group_medians(by_group)
    return {
        # one arithmetic under two names: alone on the chip, and under
        # contention, where run-to-run spread is several times wider
        # and the bound with it
        "query_geomean_ms": geomean,
        "contended_geomean_ms": geomean,
        "queries_per_s": stats.throughput(
            [s.t_done - t_window for s in ok], seconds),
    }


def _statements_in(samples, lo: float, hi: float) -> List:
    """(statement, share of it that ran inside [lo, hi]) for every
    sample that overlaps the interval: the recorded stretch holds parts
    of statements, and a per-statement device number divides by the sum
    of the shares."""
    out = []
    for s in samples:
        overlap = min(s.t_done, hi) - max(s.t_submit, lo)
        if overlap > 0:
            out.append((s.statement, overlap / s.latency_s))
    return out


def _gap_labeller(recorder, samples):
    def label(lo: float, _length: float) -> str:
        t = recorder.t_zero + lo
        inflight = sorted(f"{s.statement.key} client {s.client}"
                          for s in samples if s.t_submit <= t <= s.t_done)
        if len(inflight) > 2:
            return f"{len(inflight)} in flight: {inflight[0]}, ..."
        return ", ".join(inflight) or "between statements"
    return label


def _compare(samples, statements, catalogs, catalog_props, compiled,
             control: bool) -> Dict[str, Dict]:
    """After the window: every completed statement against the plain
    reference (marks the wrong ones). Returns each number compared
    beside its limit, ``ok`` where it holds: the run is correct where
    all do."""
    cache_dir = os.path.join(WORK_DIR, "reference_cache")
    want = reference.answers(statements, catalogs, catalog_props,
                             cache_dir, log=log)
    for s in samples:
        if s.error is None:
            s.wrong = reference.mismatch(
                reference.engine_encoding(s.columns, s.rows),
                want[s.statement.key])
    for s in [s for s in samples if not s.ok][:5]:
        log(phase="failed", statement=s.statement.key, client=s.client,
            error=s.error, wrong=s.wrong)
    completed = [s for s in samples if s.error is None]
    wrong = [s for s in completed if s.wrong]
    checks = {
        "statements_differing_from_reference": {
            "value": len(wrong), "limit": 0, "ok": not wrong,
            "compared": len(completed)},
        "programs_compiled_in_window": {
            "value": compiled["programs_compiled"], "limit": 0,
            "ok": compiled["programs_compiled"] == 0},
        "statements_completed": {
            "value": len(completed), "at_least": 1,
            "ok": len(completed) >= 1},
    }
    for name, check in checks.items():
        log(phase="check", check=name, **check)
    if control:
        ctl = reference.answers(statements, catalogs, catalog_props,
                                cache_dir, control=True, log=log)
        differing = sorted(
            k for k in ctl if reference.mismatch(ctl[k], want[k]))
        log(phase="control", note="the reference with one guarantee "
            "broken, in the served rows' place: has to differ",
            statements=len(ctl), differing=differing, limit=0,
            control_correct=not differing)
    return checks


def _traced(result: Dict, cell, work: str, recorder, samples, ok,
            catalogs, metrics_start, metrics_end, peaks,
            keep_trace: bool) -> Dict:
    """The traced run's result: the trace reduced, the per-layer
    metrics' readers run, the breakdown."""
    xplane = recorder.xplane()
    reduced = tracing.reduce(
        xplane, recorder.t_start - recorder.t_zero,
        recorder.t_stop - recorder.t_zero,
        _gap_labeller(recorder, samples))
    if keep_trace:
        kept = os.path.join(work, "kept.xplane.pb.gz")
        with open(xplane, "rb") as src, gzip.open(kept, "wb") as dst:
            shutil.copyfileobj(src, dst)
        with open(os.path.join(work, "trace_lines.json"), "w") as f:
            json.dump(tracing.describe(xplane), f, indent=1)
        log(phase="trace", kept=kept, bytes=os.path.getsize(kept))
    shutil.rmtree(recorder.out_dir, ignore_errors=True)
    log(phase="trace", programs=reduced["programs"][:tracing.TOP_N],
        programs_in_stretch=len(reduced["programs"]),
        op_events=reduced["op_events"],
        first_op_offset_s=reduced["first_op_offset_s"],
        busy_s_by_device=reduced["busy_s_by_device"])

    def scan_bytes(st):
        mod = manifest.load_module("references", st.template)
        tables = ({mod.TABLE: mod.COLUMNS} if mod.KIND == "columns"
                  else mod.TABLES)
        return sum(scanbytes.scan_bytes(catalogs[st.catalog], t, c)
                   for t, c in tables.items())

    ctx = {
        "samples": ok,
        "traced_statements": _statements_in(
            ok, recorder.t_start, recorder.t_stop),
        "metrics_start": metrics_start, "metrics_end": metrics_end,
        "trace": reduced,
        "concurrent": cell.concurrent,
        "peaks": peaks, "scan_bytes": scan_bytes,
    }
    gather = metrics_end.get("batch_gather_wait_ms", 0.0) - \
        metrics_start.get("batch_gather_wait_ms", 0.0)
    log(phase="per_layer", batch_gather_wait_ms_per_statement=(
        gather / len(ok)))
    result["metrics"] = layers.read_all(cell, ctx, log)
    result["device"]["busy_s"] = reduced["busy_s"]
    result["device"]["window_s"] = reduced["window_s"]
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}
    return result


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, control: bool = False,
        keep_trace: bool = False, t_process: Optional[float] = None
        ) -> Dict:
    """Runs the cell and returns the result line's object. Raises
    SystemExit where the run cannot be made (no chip, an unknown device,
    a failing set-up)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = manifest.load_cell(workload)
    if rehearse and trace:
        raise SystemExit("--rehearse refuses --trace 1: a CPU run has no "
                         "device trace")

    from presto_tpu import compilecache

    work = os.path.join(WORK_DIR, cell.name + ("_rehearse" * rehearse))
    catalog_props = serve.write_etc(
        os.path.join(work, "etc"), cell.config, rehearse)
    uncompiled = serve.uncompiled(cell, rehearse)
    log(phase="start", cell=cell.name, seed=seed, seconds=seconds,
        trace=trace, rehearse=rehearse, work_dir=work,
        cache_dir=compilecache.cache_dir(),
        compile_in_child=[st.key for st in uncompiled])
    if uncompiled:
        # before this process looks for a device: the child holds it
        serve.compile_in_child(
            [sys.executable, os.path.join(manifest.ROOT, "benchmarks",
                                          "run.py"),
             "--workload", workload, "--seed", str(seed), "--seconds", "0",
             "--compile-only", work] + ["--rehearse"] * rehearse, log)
    devices, peaks = _device_or_exit(cell, rehearse)
    dev = devices[0]
    served = serve.Served(os.path.join(work, "etc"), cell.chips)
    try:
        log(phase="device", platform=dev.platform,
            device_kind=dev.device_kind, devices=len(devices))
        plans = traffic.plan_clients(cell, seed)
        statements = traffic.statements_used(plans)
        serve.warm(served, statements, cell.concurrent, log)

        recorder = None
        if trace:
            seconds = min(seconds, float(cell.traffic["traced_seconds"]))
            trace_dir = os.path.join(work, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            recorder = tracing.Recorder(trace_dir)
        metrics_start = served.metrics()
        compiled_before = compilecache.snapshot()
        timers = []
        if recorder:
            # the device is recorded for traced_seconds, beginning
            # traced_offset_seconds into the window, whatever the
            # statements' boundaries: a trace of a whole 30 s statement
            # would be gigabytes
            offset = float(cell.traffic.get("traced_offset_seconds", 0))
            timers = [threading.Timer(offset, recorder.start),
                      threading.Timer(offset + seconds, recorder.stop)]
            seconds += offset
        setup_s = time.perf_counter() - t_process
        for t in timers:
            t.start()
        t_window, samples = traffic.run_window(
            served, plans, seconds, cell.traffic["stop"], scrape=trace)
        window_end = time.perf_counter()
        for t in timers:
            t.join()
        compiled = compilecache.delta(compiled_before)
        metrics_end = served.metrics()
        memory_peaks = _memory_peaks(devices)
        if cell.chips > 1:
            # that the statements ran over the mesh: exchanges compiled
            # onto it, and any that fell back to the spool
            log(phase="mesh", **{k: metrics_end.get(k) for k in (
                "mesh_local_exchanges", "ici_exchanges",
                "mesh_exchange_fallbacks")})
        if trace:
            for s in samples:
                if s.query_id:
                    s.query_info = served.query_info(s.query_id)
    finally:
        served.stop()

    checks = _compare(samples, statements, served.catalogs,
                      catalog_props, compiled, control)
    correct = all(check["ok"] for check in checks.values())
    failed = [s for s in samples if not s.ok]
    ok = [s for s in samples if s.ok]
    with open(os.path.join(work, "samples.jsonl"), "w") as f:
        for s in samples:
            f.write(json.dumps({
                "client": s.client, "statement": s.statement.key,
                "submit_s": s.t_submit - t_window,
                "latency_s": s.latency_s, "ok": s.ok}) + "\n")
    log(phase="window", window_s=window_end - t_window,
        asked_s=seconds, attempted=len(samples), failed=len(failed),
        program_cache_hits_in_window=compiled["program_cache_hits"],
        memory_peak_bytes_by_device=memory_peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(memory_peaks)}
    result = {"correct": bool(correct), "attempted": len(samples),
              "failed": len(failed), "metrics": {}, "device": device,
              "checks": checks}
    if rehearse:
        # a CPU run prints no time, rate or share under a metric's name
        log(phase="rehearsal", note="CPU rehearsal: counts only, no "
            "metric", completed_by_statement={
                st.key: sum(1 for s in ok if s.statement.key == st.key)
                for st in statements})
        return result
    if not ok:
        return result
    if not trace:
        values = _end_to_end(cell, samples, t_window, seconds)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
        return result

    return _traced(result, cell, work, recorder, samples, ok,
                   served.catalogs, metrics_start, metrics_end, peaks,
                   keep_trace)


def compile_only(workload: str, work: str, rehearse: bool) -> None:
    """The child process of a checkout's first run: compiles the cell's
    programs into the persistent cache from the etc/ directory its
    parent wrote under ``work``, and prints no result."""
    cell = manifest.load_cell(workload)
    _device_or_exit(cell, rehearse)
    serve.compile_phase(os.path.join(work, "etc"), cell, rehearse, log)


def main(argv, t_process: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="One run of one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at SF0.01: counts, no metric")
    ap.add_argument("--control", action="store_true",
                    help="also compare the reference's control")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the traced run's .xplane.pb in the work "
                         "directory")
    ap.add_argument("--compile-only", metavar="WORK_DIR",
                    help="what a first run's child process does: compile "
                         "the cell's programs from WORK_DIR/etc, no result")
    args = ap.parse_args(argv)
    if args.compile_only:
        compile_only(args.workload, args.compile_only, args.rehearse)
        return 0
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 rehearse=args.rehearse, control=args.control,
                 keep_trace=args.keep_trace, t_process=t_process)
    # what was compared, beside its limit: last in the result's line and
    # the last lines of standard error
    checks = result["checks"] = result.pop("checks")
    sys.stdout.flush()
    for name, check in checks.items():
        print(f"check {name}: {json.dumps(check)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
