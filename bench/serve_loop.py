"""Drive the engine on an open-loop schedule and record, on the host
clock, what a user of each request would see.

Each request is submitted when it falls due, whether or not earlier
ones have finished, with ``submitted_s`` set to its due time.  After
every ``engine.step()`` the loop stamps each new token of each request
with the time the step returned: with the engine's pipelined drain,
that is when the token reaches the host.  A request leaves the queue in
the step whose start is its placement time.  Every ``step()`` and
``submit()`` runs inside a ``jax.profiler.TraceAnnotation``, and the
measured window inside one named ``bench.window``, so a trace can put
the device's idle gaps under what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

STEP, SUBMIT, WAIT, WINDOW = ("bench.step", "bench.submit", "bench.wait",
                              "bench.window")


@dataclasses.dataclass
class Tracked:
    """One request as the user sees it."""
    index: int                  # position in the schedule
    req: object                 # the engine's Request
    due: float                  # host time it fell due
    prompt: np.ndarray
    out_len: int
    placed: Optional[float] = None
    last: int = 0               # tokens the engine held at the last look
    token_t: List[float] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.token_t) >= self.out_len


@dataclasses.dataclass
class Records:
    t0: float                   # window opens (host clock)
    t1: float                   # window closes
    t_end: float                # loop ended
    requests: List[Tracked]
    steps: List[tuple]          # (start, end) of each step that had work
    lateness: List[float]       # submit time minus due time, per request
    preempted: int = 0          # token streams the engine restarted

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def due_in_window(self) -> List[Tracked]:
        return [r for r in self.requests if self.in_window(r.due)]


def warm_up(engine, vocab: int, prompt_len: int, rng) -> None:
    """Fill every row once with a one-chunk prompt and two new tokens:
    compiles the decode and chunk steps, and every small program the
    engine's host path runs per row."""
    for _ in range(engine.max_batch):
        engine.submit(rng.integers(0, vocab, prompt_len, dtype=np.int32),
                      max_new_tokens=2)
    engine.run_until_done()


def drive(engine, schedule, prompts, *, lead_s: float, seconds: float,
          grace_s: float, clock=time.perf_counter) -> Records:
    """Serve ``schedule`` (``traffic.*.Schedule``) from now on.  The
    window opens ``lead_s`` after the first request falls due and lasts
    ``seconds``.  After it closes the schedule keeps running until every
    request due in the window has its first token, or ``grace_s`` has
    passed.  Requests that come later than that are late, and count as
    failed."""
    start = clock()
    t0, t1 = start + lead_s, start + lead_s + seconds
    n = len(schedule)
    due = start + np.asarray(schedule.due_s, np.float64)
    tracked: List[Tracked] = []
    open_: List[Tracked] = []   # requests still owed tokens
    steps, lateness = [], []
    preempted = 0
    window, closed = None, False
    nxt = 0
    busy = False
    while True:
        now = clock()
        if window is None and not closed and now >= t0:
            window = TraceAnnotation(WINDOW)
            window.__enter__()
        if window is not None and now >= t1:
            window.__exit__(None, None, None)
            window, closed = None, True
        while nxt < n and due[nxt] <= now:
            with TraceAnnotation(SUBMIT):
                engine.submit(prompts[nxt], int(schedule.output_len[nxt]),
                              submitted_s=float(due[nxt]))
            t = Tracked(nxt, engine.queue[-1], float(due[nxt]), prompts[nxt],
                        int(schedule.output_len[nxt]))
            tracked.append(t)
            open_.append(t)
            lateness.append(clock() - t.due)
            nxt += 1
        if closed and (now >= t1 + grace_s or all(
                r.token_t for r in tracked if t0 <= r.due <= t1)):
            break
        if busy or len(engine.queue):
            ts = clock()
            with TraceAnnotation(STEP):
                busy = engine.step() > 0
            te = clock()
            steps.append((ts, te))
            queued = {id(q) for q in engine.queue}
            still = []
            for r in open_:
                if r.placed is None and id(r.req) not in queued:
                    r.placed = ts
                have = len(r.req.tokens)
                if have < r.last:
                    preempted += 1      # evicted: replays from scratch
                r.last = have
                r.token_t.extend([te] * max(have - len(r.token_t), 0))
                if not r.finished:
                    still.append(r)
            open_ = still
        else:
            wait = (due[nxt] if nxt < n else now + 1e-3) - now
            with TraceAnnotation(WAIT):
                time.sleep(min(max(wait, 0.0), 2e-3))
    if window is not None:
        window.__exit__(None, None, None)
    return Records(t0=t0, t1=t1, t_end=clock(), requests=tracked,
                   steps=steps, lateness=lateness, preempted=preempted)
