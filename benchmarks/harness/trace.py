"""The device trace: recording a sub-window with JAX's profiler, and the
reduction from the recorded ``.xplane.pb`` to numbers.

What the planes are called on this chip (read by hand from a trace of
the scan cell, PR 24; PERF.md has the longer note): one plane per chip
named ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per
executed HLO operation and whose line ``XLA Modules`` holds one event
per executed program (``jit_<name>(<fingerprint>)``); the host's
threads are the lines of the plane ``/host:CPU``. Busy time is the
union of the ``XLA Ops`` intervals of a device plane; the reduction
takes no number from the host plane.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10


class Recorder:
    """start() ... stop() around the traced sub-window. Only the process
    that holds the chip can trace it, so this runs in the benchmark's
    own process."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        # time.perf_counter() readings: just before the profiler's
        # session begins (the zero of the trace's clock, to within the
        # call's own start-up), when recording is on, and when it ends
        self.t_zero: Optional[float] = None
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        self.t_zero = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        """Ends the recording (and waits for the profiler to write)."""
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.out_dir}")
        return found[-1]


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals: Sequence[Tuple[float, float]]
         ) -> List[Tuple[float, float]]:
    """The (start, end) stretches between merged busy intervals."""
    out, cur_hi = [], None
    for lo, hi in sorted(intervals):
        if cur_hi is not None and lo > cur_hi:
            out.append((cur_hi, lo))
        cur_hi = hi if cur_hi is None else max(cur_hi, hi)
    return out


def load(path: str):
    """An .xplane.pb, or a gzip of one (.gz), as ProfileData."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def op_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO text,
    ``%fusion.12 = f32[...] fusion(...)``; the name is what stands
    before the equals sign."""
    return event_name.split(" = ", 1)[0]


def read_planes(path: str) -> Dict[int, Dict[str, List[Tuple[str, float,
                                                             float]]]]:
    """device index -> line name -> [(event name, start s, end s)] for
    the device planes of an .xplane.pb; times are seconds on the
    trace's own clock."""
    out = {}
    for plane in load(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (op_name(ev.name), ev.start_ns / 1e9,
                 (ev.start_ns + ev.duration_ns) / 1e9)
                for ev in line.events]
        out[int(m.group(1))] = lines
    return out


def describe(path: str) -> List[Dict]:
    """Every plane and line of a trace with its event count and first
    names: what one reads by hand before trusting the reduction."""
    out = []
    for plane in load(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name,
                "events": len(events),
                "first": [e.name[:120] for e in events[:4]],
            })
    return out


def _clip(events, lo: float, hi: float):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def reduce(path: str, lo: float, hi: float, label_gap=None) -> Dict:
    """The numbers of the stretch [lo, hi] of a recorded trace, in
    seconds on the trace's clock (the profiler goes on recording for a
    few milliseconds around the stretch the harness timed: what lies
    outside is cut off, so busy time cannot pass the window's length).

    ``busy_s``: seconds in which an operation ran on the device, the
    union of the ``XLA Ops`` intervals, averaged over the chips in the
    trace. ``device_ops``: the ``TOP_N`` operations that took most
    time, summed by name over the chips. ``programs``: every program
    with its time, summed likewise, most time first (a reader sums a
    family's; the log line prints the first ``TOP_N``). ``idle_gaps``:
    the longest stretches of the first chip with no operation running,
    labelled by ``label_gap(start on the trace's clock, length)``.
    """
    planes = read_planes(path)
    if not planes:
        raise RuntimeError(f"{path}: no /device:TPU:<n> plane")
    busy, op_time, program_time = [], {}, {}
    for _dev, lines in sorted(planes.items()):
        ops = _clip(lines.get(OPS_LINE, []), lo, hi)
        busy.append(union_seconds([(a, b) for _n, a, b in ops]))
        for name, a, b in ops:
            op_time[name] = op_time.get(name, 0.0) + (b - a)
        for name, a, b in _clip(lines.get(MODULES_LINE, []), lo, hi):
            program_time[name] = program_time.get(name, 0.0) + (b - a)
    first = planes[min(planes)]
    intervals = [(a, b) for _n, a, b in
                 _clip(first.get(OPS_LINE, []), lo, hi)]
    origin = min((a for a, _b in intervals), default=lo)
    # the stretch's own ends count as gaps too
    edges = [(lo, lo)] + intervals + [(hi, hi)]
    longest = sorted(gaps(edges), key=lambda g: g[0] - g[1])[:TOP_N]
    idle = []
    for a, b in longest:
        name = "unlabelled"
        if label_gap is not None:
            name = label_gap(a, b - a)
        idle.append([name, b - a])

    def by_time(times):
        return [[n, s] for n, s in sorted(
            times.items(), key=lambda kv: -kv[1])]

    return {
        "busy_s": sum(busy) / len(busy),
        "busy_s_by_device": busy,
        "window_s": hi - lo,
        "device_ops": by_time(op_time)[:TOP_N],
        "programs": by_time(program_time),
        "idle_gaps": idle,
        "first_op_offset_s": origin,
        "op_events": sum(len(l.get(OPS_LINE, []))
                         for l in planes.values()),
    }
