"""The open loop: wall-clock load on the program's slot scheduler.

It makes the calls ``repro.serve.Server.run`` makes -- ``admit`` while a
lane is free, then ``step`` -- but on the host's clock: every request is
released into a FIFO queue at its scheduled time, whether or not earlier
ones have finished, and is timed from that scheduled time. It never
fast-forwards: when no lane is busy it sleeps until the next arrival.

Each call into the program runs inside a ``jax.profiler.TraceAnnotation``
(``bench.admit``, ``bench.step``, ``bench.idle``), so that a traced run
can say what the host was doing in each gap of the device.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Sent:
    """What the loop saw of one request."""
    index: int
    segment: str
    at: float                      # scheduled send time (perf_counter)
    released: float = 0.0          # when the loop queued it
    admitted: float = 0.0
    n_prompt: int = 0
    n_answer: int = 0
    temperature: float = 0.0
    prompt: Optional[np.ndarray] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    log_probs: List[float] = dataclasses.field(default_factory=list)
    log_zs: List[float] = dataclasses.field(default_factory=list)
    done: float = 0.0              # 0 until the request completed
    error: Optional[str] = None


@dataclasses.dataclass
class Step:
    """One scheduler step as the loop saw it."""
    t_start: float                 # the scheduler's own stamps ...
    t_dispatch: float
    t_device_done: float
    t_done: float
    t_loop: float                  # ... and when the loop's turn began
    n_active: int
    head_live: int
    n_emitted: int
    health_flagged: int
    attended: int                  # sum over busy lanes of positions attended
    idle_s: float                  # the loop slept this long before it
    paused_s: float                # ... and started or stopped the profiler
    traced: bool


class OpenLoop:
    """Drive ``scheduler`` through ``plan`` (``traffic.Planned`` list).

    ``origin`` is the perf_counter time of the schedule's zero;
    ``window`` the measured (start, end); the run continues past the end
    until every request due in the window has completed, for at most
    ``follow_s`` seconds. ``tracer(now)`` is called between steps and may
    start or stop a profiler trace; it returns whether one is running."""

    def __init__(self, scheduler, request_cls, plan, origin: float,
                 window: tuple, follow_s: float,
                 tracer: Optional[Callable[[float], bool]] = None):
        self.sched = scheduler
        self.window = window
        self.follow_s = follow_s
        self.tracer = tracer or (lambda now: False)
        self.sent: List[Sent] = []
        self.steps: List[Step] = []
        self._requests = []
        for p in plan:
            rec = Sent(index=p.index, segment=p.segment,
                       at=origin + p.at_s, n_prompt=len(p.prompt),
                       n_answer=p.answer_tokens,
                       temperature=p.temperature, prompt=p.prompt)
            self.sent.append(rec)
            self._requests.append(request_cls(
                prompt=p.prompt, max_new_tokens=p.answer_tokens,
                key=p.key, temperature=p.temperature,
                on_token=self._on_token(rec),
                on_complete=self._on_complete(rec)))
        self._admit_step: Dict[int, int] = {}   # index -> step admitted at
        self.lateness: List[float] = []         # release - scheduled (s)

    @staticmethod
    def _on_token(rec: Sent):
        def on_token(req, token, wall_time):
            rec.token_times.append(wall_time)
        return on_token

    def _on_complete(self, rec: Sent):
        def on_complete(req, comp):
            rec.done = comp.done_time
            rec.tokens = list(comp.tokens)
            rec.log_probs = list(comp.log_probs)
            rec.log_zs = list(comp.log_zs)
            rec.error = comp.error
            self._admit_step.pop(rec.index, None)
        return on_complete

    def _window_open(self) -> int:
        """Requests due in the window that have not completed."""
        return sum(1 for r in self.sent if r.segment == "window"
                   and not r.done and r.error is None)

    def run(self) -> None:
        sched = self.sched
        queue = deque()
        nxt = 0
        n = len(self.sent)
        w_end = self.window[1]
        stop_at = w_end + self.follow_s
        slept = paused = 0.0
        while True:
            now = time.perf_counter()
            if now >= w_end and (self._window_open() == 0 or now >= stop_at):
                break
            while nxt < n and self.sent[nxt].at <= now:
                self.sent[nxt].released = now
                self.lateness.append(now - self.sent[nxt].at)
                queue.append(nxt)
                nxt += 1
            while queue and sched.n_free:
                i = queue.popleft()
                rec = self.sent[i]
                with jax.profiler.TraceAnnotation("bench.admit"):
                    try:
                        sched.admit(self._requests[i])
                    except (ValueError, RuntimeError) as e:
                        rec.error = f"admission refused: {e}"
                        continue
                rec.admitted = time.perf_counter()
                self._admit_step[i] = len(self.steps)
            t_trace = time.perf_counter()
            traced = self.tracer(t_trace)
            # starting or stopping the profiler is the benchmark's own
            # pause, not the program's host time
            paused += time.perf_counter() - t_trace
            if sched.n_in_flight == 0:
                if nxt >= n:
                    break
                t_sleep = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, self.sent[nxt].at - t_sleep))
                slept += time.perf_counter() - t_sleep
                continue
            s_now = len(self.steps)
            # positions a busy lane attends this step: its cache holds
            # (steps since admission) + 1 tokens after the step's write
            attended = sum(s_now - a + 1 for a in self._admit_step.values())
            with jax.profiler.TraceAnnotation("bench.step"):
                rec = sched.step()
            self.steps.append(Step(
                t_start=rec["t_start"], t_dispatch=rec["t_dispatch"],
                t_device_done=rec["t_device_done"], t_done=rec["t_done"],
                t_loop=now, n_active=rec["n_active"],
                head_live=rec["head_live"], n_emitted=rec["n_emitted"],
                health_flagged=rec["health_flagged"], attended=attended,
                idle_s=slept, paused_s=paused, traced=traced))
            slept = paused = 0.0
        self.stopped = time.perf_counter()
        self.tracer(float("inf"))
        # let go of the program, so that its state can be freed
        self.sched = None
        self._requests = []
