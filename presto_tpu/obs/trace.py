"""Span recorder for one query's lifecycle.

Reference: presto-main's QueryInfo/StageInfo/TaskInfo tree (server/
QueryStateMachine + execution/StageStateMachine assembling it live)
and the QueryMonitor that flattens it into EventListener payloads.

Timing model (the ISSUE 9 drift fix): every span interval is measured
on `time.monotonic()` as an offset from the trace's creation instant,
and the trace carries exactly ONE wall-clock anchor (`anchor_wall`,
taken once at creation). Cross-node ingestion never subtracts two
machines' wall clocks — worker spans arrive as offsets from the
worker's own task-creation instant and are re-based into the
coordinator's task-span window, clamped to it, so clock skew can
shift a remote span inside its parent but can never make a duration
negative or a child escape its parent.

The recorder is deliberately dumb: append-only span list, explicit
parent links, one lock. All structure (QueryInfo tree, Chrome trace,
critical path) is derived at read time — recording at page/stage
boundaries stays O(1) and allocation-light, and NOTHING here is
reachable from jit keys or traced functions (tools/lint purity rule).

Phases: the top-level spans ``queue``, ``parse``, ``plan``, ``execute``
and ``encode`` tile a served statement from submission to its last
encoded row. ``phase()`` opens one where the last one ended (one clock
reading ends the old and begins the new), so the tiling holds by
construction however the threads are scheduled between them.

Every span opened with ``begin()``/``phase()`` is also a
``jax.profiler.TraceAnnotation`` (``annotation`` below), so a profiler
session's ``/host:CPU`` plane holds the program's own account on the
device trace's clock. With no session an annotation is one inactive
TraceMe; a span ended on another thread than it began on leaves its
annotation unended, which the profiler drops.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional

from presto_tpu.obs.sanitizer import make_lock, register_owner


_TraceAnnotation = None

# the phases of a served statement, in order (obs.SPAN_KINDS has each)
PHASE_KINDS = ("queue", "parse", "plan", "execute", "encode")
# the kinds below an ``execute`` phase that are real intervals of the
# driver thread, each timed at its site: what ``phases()`` lists under
# the phase, and what its self time is taken against. Not ``attempt``
# (the container) nor ``operator`` (per-node wall totals laid at the
# attempt's start, overlapping by design)
INTERVAL_KINDS = ("launch", "wait", "xfer", "eager", "resident_load",
                  "join_build")


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` begun now (a context manager:
    its exit ends it). Callers build ``name`` once per site or program,
    not per call. jax is imported on first use: the recorder itself
    stays importable without it."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name)


@dataclasses.dataclass
class Span:
    """One interval. t0/t1 are seconds since the trace's monotonic
    anchor; t1 is None while the span is open."""

    span_id: int
    parent_id: Optional[int]
    kind: str
    name: str
    t0: float
    t1: Optional[float] = None
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)
    # (annotation, ident of the thread that began it) while open
    note: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    def dur(self, now: float = 0.0) -> float:
        end = self.t1 if self.t1 is not None else now
        return max(end - self.t0, 0.0)


class QueryTrace:
    """One query's span tree. Thread-safe (worker status polls and the
    scheduler's dispatch loop record concurrently); reads snapshot."""

    # lock discipline (tools/lint `locks` rule): the span list and its
    # sequence counter are the shared recording surface
    _shared_attrs = ("_spans", "_seq", "_phase", "_phase_end")

    def __init__(self, query_id: str, sql: Optional[str] = None,
                 anchor_mono: Optional[float] = None,
                 anchor_wall: Optional[float] = None):
        self.query_id = query_id
        # THE one wall-clock read per query (display/correlation only;
        # never used in interval arithmetic)
        self.anchor_wall = (time.time() if anchor_wall is None
                            else anchor_wall)
        self._anchor_mono = (time.monotonic() if anchor_mono is None
                             else anchor_mono)
        self._lock = make_lock("obs.trace.QueryTrace._lock")
        self._spans: List[Span] = []
        self._seq = 0
        # the open phase span, and where the last phase ended
        self._phase: Optional[Span] = None
        self._phase_end: Optional[float] = None
        # origin of the QueryInfo tree's startMs/endMs (seconds from
        # the anchor): the instant the runner has parsed the statement
        # and begins to plan it, which is where a runner-made trace
        # used to be created — a coordinator-owned trace is anchored
        # earlier, at submission, and the tree keeps its origin
        self.stage_origin = 0.0
        attrs = {"sql": sql} if sql else {}
        self.root = self._new("query", query_id, None, 0.0, None, attrs)
        register_owner(self)

    # ------------------------------------------------------- recording
    def now(self) -> float:
        return time.monotonic() - self._anchor_mono

    def _new(self, kind, name, parent, t0, t1, attrs) -> Span:
        with self._lock:
            self._seq += 1
            sp = Span(self._seq, parent, kind, name, t0, t1,
                      dict(attrs))
            self._spans.append(sp)
            return sp

    def _annotate(self, span: Span) -> Span:
        if span.kind in PHASE_KINDS:
            name = (f"execute:{self.query_id}" if span.kind == "execute"
                    else span.kind)
        else:
            name = f"{span.kind}:{span.name}"
        span.note = (annotation(name), threading.get_ident())
        return span

    @staticmethod
    def _end_note(span: Span) -> None:
        note, span.note = span.note, None
        if note is not None and note[1] == threading.get_ident():
            note[0].__exit__(None, None, None)

    def begin(self, kind: str, name: str,
              parent: Optional[Span] = None, **attrs) -> Span:
        pid = (parent or self.root).span_id
        return self._annotate(
            self._new(kind, name, pid, self.now(), None, attrs))

    def end(self, span: Span, **attrs) -> Span:
        self._end_note(span)
        with self._lock:
            if span.t1 is None:
                span.t1 = time.monotonic() - self._anchor_mono
                if span is self._phase:
                    self._phase, self._phase_end = None, span.t1
            span.attrs.update(attrs)
        return span

    def phase(self, kind: str, name: str = "",
              at: Optional[float] = None, **attrs) -> Span:
        """Open the next top-level phase where the last one ended: an
        open phase ends at the instant this one begins, a closed one
        hands over its end (what ran between them is this phase's).
        The first phase begins now, or ``at`` seconds from the anchor
        (the coordinator's ``queue`` begins at submission, 0.0)."""
        with self._lock:
            prev = self._phase
            if prev is not None:
                self._end_note(prev)
                t = prev.t1 = time.monotonic() - self._anchor_mono
            else:
                t = self._phase_end if at is None else at
                if t is None:  # the first phase, not backdated
                    t = time.monotonic() - self._anchor_mono
            self._seq += 1
            sp = Span(self._seq, self.root.span_id, kind, name, t, None,
                      dict(attrs))
            self._spans.append(sp)
            self._phase = sp
        return self._annotate(sp)

    def complete(self, kind: str, name: str, t0: float, t1: float,
                 parent: Optional[Span] = None, **attrs) -> Span:
        return self._new(kind, name, (parent or self.root).span_id,
                         t0, max(t1, t0), attrs)

    def ingest(self, remote: List[dict], parent: Span,
               lo: float, hi: float) -> int:
        """Nest worker-shipped spans (offsets from the worker's task
        creation) under a coordinator span, re-based at `lo` and
        CLAMPED to [lo, hi] — the skew guard: a remote interval can
        never go negative or escape its coordinator-side window."""
        n = 0
        for d in remote:
            try:
                t0 = min(max(lo + float(d["t0"]), lo), hi)
                t1 = min(max(lo + float(d["t1"]), t0), hi)
                self._new(str(d["kind"]), str(d.get("name", "")),
                          parent.span_id, t0, t1,
                          dict(d.get("attrs") or {}))
                n += 1
            except (KeyError, TypeError, ValueError):
                continue  # a malformed remote span is dropped, not fatal
        return n

    def finish(self, at_mono: Optional[float] = None) -> None:
        """End the root (at ``at_mono``, a time.monotonic() reading,
        where the owner has its own finish clock) and with it the open
        phase and any straggler."""
        with self._lock:
            if self.root.t1 is None:
                self.root.t1 = max(
                    (time.monotonic() if at_mono is None else at_mono)
                    - self._anchor_mono, 0.0)
            # close any straggler open spans at the root's end (a failed
            # query abandons its in-flight task spans)
            stragglers = [sp for sp in self._spans if sp.t1 is None]
            for sp in stragglers:
                sp.t1 = max(self.root.t1, sp.t0)
            self._phase = None
        for sp in stragglers:
            self._end_note(sp)

    # ----------------------------------------------------------- reads
    @property
    def span_count(self) -> int:
        with self._lock:
            return len(self._spans)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def has(self, kind: str) -> bool:
        with self._lock:
            return any(sp.kind == kind for sp in self._spans)

    def phases(self) -> List[dict]:
        """The top-level spans in order, microseconds from the anchor
        (/v1/query/{id}'s ``phases``): they tile the root. The
        ``execute`` phase carries ``spans``: its descendants of
        ``INTERVAL_KINDS`` (every launch, device wait, transfer and
        eager dispatch of its attempts) on the same clock, in order
        of their starts."""
        now = self.now()
        root = self.root.span_id
        spans = self.spans()

        def us(t: Optional[float]) -> int:
            return int(round((now if t is None else t) * 1e6))

        out = []
        for sp in sorted(spans, key=lambda s: (s.t0, s.span_id)):
            if sp.parent_id != root or sp.kind not in PHASE_KINDS:
                continue
            phase = {"kind": sp.kind, "startUs": us(sp.t0),
                     "endUs": us(sp.t1), "attrs": sp.attrs}
            if sp.kind == "execute":
                # a parent is recorded before its children
                below = {sp.span_id}
                inside = []
                for c in spans:
                    if c.parent_id in below:
                        below.add(c.span_id)
                        if c.kind in INTERVAL_KINDS:
                            inside.append(c)
                inside.sort(key=lambda s: (s.t0, s.span_id))
                phase["spans"] = [
                    {"kind": c.kind, "name": c.name,
                     "startUs": us(c.t0), "endUs": us(c.t1)}
                    for c in inside]
            out.append(phase)
        return out

    def export(self) -> List[dict]:
        """Wire form for shipping to a coordinator (worker status
        plane): non-root spans as plain dicts of anchored offsets."""
        out = []
        now = self.now()
        for sp in self.spans():
            if sp.span_id == self.root.span_id:
                continue
            out.append({
                "kind": sp.kind, "name": sp.name, "t0": sp.t0,
                "t1": sp.t1 if sp.t1 is not None else now,
                "attrs": sp.attrs,
            })
        return out

    # ------------------------------------------------- QueryInfo tree
    def to_info(self) -> dict:
        """The QueryInfo/StageInfo/TaskInfo tree (reference:
        /v1/query/{id}'s JSON). Stage-DAG queries render their real
        stages; local executions synthesize one stage ("local") whose
        single task holds the attempt/operator spans — every query
        shape serves the same tree."""
        spans = self.spans()
        now = self.now()
        children: Dict[int, List[Span]] = {}
        for sp in spans:
            if sp.parent_id is not None:
                children.setdefault(sp.parent_id, []).append(sp)

        origin = self.stage_origin

        def ms(t: float) -> int:
            return int(round(t * 1000))

        def at(t: float) -> int:
            return ms(t - origin)

        def at_us(t: float) -> int:
            return int(round((t - origin) * 1e6))

        def descend(sp: Span) -> List[dict]:
            out = []
            for c in sorted(children.get(sp.span_id, ()),
                            key=lambda s: (s.t0, s.span_id)):
                end = c.t1 if c.t1 is not None else now
                # a launch is 1-2 ms and a pull 0.45: whole
                # milliseconds alone would lay them on one instant
                out.append({
                    "kind": c.kind, "name": c.name,
                    "startMs": at(c.t0), "endMs": at(end),
                    "startUs": at_us(c.t0), "endUs": at_us(end),
                    "attrs": c.attrs,
                })
                out.extend(descend(c))
            return out

        def task_info(sp: Span, task_id: str) -> dict:
            return {
                "taskId": task_id,
                "uri": sp.attrs.get("uri"),
                "state": ("RUNNING" if sp.t1 is None else
                          str(sp.attrs.get("state", "FINISHED"))),
                "startMs": at(sp.t0),
                "endMs": at(sp.t1 if sp.t1 is not None else now),
                "wallMs": ms(sp.dur(now)),
                "rows": sp.attrs.get("rows"),
                "pages": sp.attrs.get("pages"),
                "retries": sp.attrs.get("retries", 0),
                "spans": descend(sp),
            }

        stages = []
        for sp in sorted((s for s in spans if s.kind == "stage"),
                         key=lambda s: (s.t0, s.span_id)):
            tasks = [task_info(c, c.name)
                     for c in children.get(sp.span_id, ())
                     if c.kind == "task"]
            stages.append({
                "stageId": sp.name,
                "state": "RUNNING" if sp.t1 is None else "FINISHED",
                "startMs": at(sp.t0),
                "endMs": at(sp.t1 if sp.t1 is not None else now),
                "wallMs": ms(sp.dur(now)),
                "tasks": tasks,
            })
        if not stages:
            # local execution: one synthetic stage per executor run
            execs = [s for s in spans if s.kind == "execute"]
            tasks = [task_info(sp, f"local.{i}")
                     for i, sp in enumerate(execs)]
            if tasks:
                stages = [{
                    "stageId": "local",
                    "state": ("RUNNING" if any(s.t1 is None
                                               for s in execs)
                              else "FINISHED"),
                    "startMs": at(min(s.t0 for s in execs)),
                    "endMs": at(max(s.t1 if s.t1 is not None else now
                                    for s in execs)),
                    "wallMs": ms(max(s.dur(now) for s in execs)),
                    "tasks": tasks,
                }]
        return {
            "queryId": self.query_id,
            # the tree's own clock: the wall instant its startMs/endMs
            # count from, and the time from there to the root's end
            "createTime": self.anchor_wall + origin,
            "elapsedMs": ms(self.root.dur(now) - origin),
            "spanCount": len(spans),
            "stages": stages,
        }

    # ------------------------------------------------- Chrome export
    def to_chrome(self) -> dict:
        """Chrome-trace (Perfetto-loadable) JSON: complete (`X`)
        events in microseconds since the query's wall anchor, sorted
        by ts, one tid lane per stage/task/execute container."""
        spans = self.spans()
        now = self.now()
        lane_of: Dict[int, int] = {self.root.span_id: 0}
        by_id = {sp.span_id: sp for sp in spans}
        next_lane = [0]

        def lane(sp: Span) -> int:
            if sp.span_id in lane_of:
                return lane_of[sp.span_id]
            if sp.kind in ("stage", "task", "execute"):
                next_lane[0] += 1
                lane_of[sp.span_id] = next_lane[0]
                return next_lane[0]
            parent = by_id.get(sp.parent_id)
            lane_of[sp.span_id] = lane(parent) if parent else 0
            return lane_of[sp.span_id]

        events = []
        for sp in spans:
            end = sp.t1 if sp.t1 is not None else now
            args = {k: v for k, v in sp.attrs.items() if v is not None}
            events.append({
                "name": f"{sp.kind}:{sp.name}",
                "cat": sp.kind,
                "ph": "X",
                "ts": int(round(sp.t0 * 1e6)),
                "dur": int(round(max(end - sp.t0, 0.0) * 1e6)),
                "pid": 1,
                "tid": lane(sp),
                "args": args,
            })
        events.sort(key=lambda e: (e["ts"], e["tid"]))
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "queryId": self.query_id,
                "wallAnchorUnixS": self.anchor_wall,
            },
        }

    def write_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1, default=str)
        return path
