"""Span tracing: an in-memory span log on the program's own clock, and
Chrome-trace / Perfetto-compatible JSONL.

``SpanLog`` keeps the spans the program records where the work happens
(the scheduler's step and its phases, each admission) in a bounded
in-memory log: name, start, end, parent and id (the step's index, or the
request's ``req_id``). Stamps are ``time.perf_counter()`` seconds, the
clock of the scheduler's ``t_*`` step stamps. ``Span`` records one span
and, while a profiler trace is being taken, also opens a
``jax.profiler.TraceAnnotation`` of the same name, so a traced run shows
the program's spans beside the device's operations. ``SPANS`` is the
process's log, as ``prometheus_client`` has one registry per process: a
``Scheduler`` writes there unless it is given another log, or ``None``.

``TraceWriter`` writes one JSON event object per line (the streaming
flavor of the Trace Event Format — ``chrome://tracing`` and Perfetto both ingest it after wrapping in
a ``[...]`` array, which ``launch/obs_report.py --to-json`` does). Events
use wall-clock microseconds relative to the writer's creation:

 * ``X`` complete spans — request lifecycle phases (queued / replay /
   decode / request) on tid = request id, and the span log's records
   (``sched.step`` and its phases, ``sched.admit``) on the scheduler's
   tid 0;
 * ``i`` instants — enqueue, admit, shed/evict, tier transitions, index
   swap/restore;
 * ``C`` counters — harvested gauges (queue depth, occupancy, per-tier
   shadow rel-err), drawn as tracks;
 * ``M`` metadata — thread names.

Everything is host-side and append-only. Events buffer as plain dicts in
the serving loop and serialize in batches at ``flush()`` / ``close()`` —
JSON encoding stays off the goodput-critical path, and a crashed run
leaves a readable prefix through the last flush (the buffer also
self-flushes past ``MAX_BUFFERED`` events to bound memory). No external
deps.
"""
from __future__ import annotations

import collections
import json
import time
from typing import Iterable, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation


class SpanRecord(NamedTuple):
    name: str
    start: float            # time.perf_counter() seconds
    end: float
    parent: Optional[str]   # the enclosing span's name
    id: Optional[int]       # step index, or the request's req_id


class SpanLog:
    """Bounded in-memory span log: the newest ``capacity`` records.

    The default holds a whole benchmark run of the fastest configuration
    (~5,000 scheduler steps of 6 spans each, plus admissions). Recording
    appends one tuple, so a span costs two clock reads and an append."""
    CAPACITY = 1 << 16

    def __init__(self, capacity: int = CAPACITY):
        self._rec: collections.deque = collections.deque(maxlen=capacity)
        self.added = 0          # records ever added: a cursor for polling

    def add(self, name: str, start: float, end: float,
            parent: Optional[str] = None, id: Optional[int] = None) -> None:
        self._rec.append((name, start, end, parent, id))
        self.added += 1

    def __len__(self) -> int:
        return len(self._rec)

    def records(self) -> List[SpanRecord]:
        return [SpanRecord._make(r) for r in self._rec]

    def since(self, mark: int) -> Tuple[List[SpanRecord], int]:
        """The records added after cursor ``mark`` (as far as the log
        still holds them), and the cursor to pass next time."""
        n = min(self.added - mark, len(self._rec))
        recs = [SpanRecord._make(self._rec[i])
                for i in range(len(self._rec) - n, len(self._rec))]
        return recs, self.added


SPANS = SpanLog()


class Span:
    """Context manager that records one span into ``log`` (nothing when
    ``log`` is None) under a profiler annotation of the same name. The
    stamps are taken inside the annotation, so the two agree to the
    annotation's own cost. ``start`` and ``end`` stay readable after
    the span closes."""
    __slots__ = ("log", "name", "id", "parent", "start", "end", "_ann")

    def __init__(self, log: Optional[SpanLog], name: str,
                 id: Optional[int] = None, parent: Optional[str] = None):
        self.log, self.name, self.id, self.parent = log, name, id, parent
        self._ann = None

    def child(self, name: str) -> "Span":
        return Span(self.log, name, self.id, self.name)

    def __enter__(self) -> "Span":
        if self.log is not None and TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self.log is not None:
            self.log.add(self.name, self.start, self.end, self.parent,
                         self.id)
        return False


class TraceWriter:
    PID = 1
    MAX_BUFFERED = 16384

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w")
        self._t0 = time.perf_counter()
        self._named_tids: set = set()
        self._buf: List[dict] = []
        self.events_written = 0
        self.name_thread(0, "scheduler")

    def _ts(self, t: Optional[float]) -> float:
        """Wall stamp (time.perf_counter seconds) -> trace µs."""
        return ((time.perf_counter() if t is None else t) - self._t0) * 1e6

    def _emit(self, ev: dict) -> None:
        self._buf.append(ev)
        self.events_written += 1
        if len(self._buf) >= self.MAX_BUFFERED:
            self.flush()

    def name_thread(self, tid: int, name: str) -> None:
        if tid in self._named_tids:
            return
        self._named_tids.add(tid)
        self._emit({"ph": "M", "name": "thread_name", "pid": self.PID,
                    "tid": tid, "args": {"name": name}})

    def span(self, name: str, t_start: float, t_end: float, tid: int = 0,
             cat: str = "serve", args: Optional[dict] = None) -> None:
        ts = self._ts(t_start)
        self._emit({"ph": "X", "name": name, "cat": cat, "pid": self.PID,
                    "tid": tid, "ts": ts,
                    "dur": max(self._ts(t_end) - ts, 0.0),
                    "args": args or {}})

    def instant(self, name: str, t: Optional[float] = None, tid: int = 0,
                cat: str = "serve", args: Optional[dict] = None) -> None:
        self._emit({"ph": "i", "name": name, "cat": cat, "pid": self.PID,
                    "tid": tid, "ts": self._ts(t), "s": "t",
                    "args": args or {}})

    def counter(self, name: str, values: dict,
                t: Optional[float] = None) -> None:
        self._emit({"ph": "C", "name": name, "pid": self.PID, "tid": 0,
                    "ts": self._ts(t),
                    "args": {k: float(v) for k, v in values.items()}})

    def write_spans(self, records: Iterable[SpanRecord],
                    args_by_name: Optional[dict] = None) -> None:
        """Write span-log records as ``X`` spans on the scheduler's tid 0,
        each with its id and parent; ``args_by_name`` adds arguments to
        the spans of a name (the step's tier, say)."""
        extra = args_by_name or {}
        for r in records:
            args = {"id": r.id, "parent": r.parent}
            args.update(extra.get(r.name, ()))
            self.span(r.name, r.start, r.end, cat="sched", args=args)

    def flush(self) -> None:
        if self._buf:
            self._f.write("".join(
                json.dumps(ev, separators=(",", ":")) + "\n"
                for ev in self._buf))
            self._buf.clear()
        self._f.flush()

    def close(self) -> None:
        if not self._f.closed:
            self.flush()
            self._f.close()
