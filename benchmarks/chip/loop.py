"""Drive a ``ServeSession`` the way clients do, and record what they see.

One thread plays both sides: it submits every request that is due, then
runs one ``session.step()``, and sleeps (``generator.wait``) only when
the session has nothing to do.  Each step runs inside a
``jax.profiler.TraceAnnotation("session.step", i=<index>)`` span, so a
device trace can be cut into steps.  Token arrival times are the end of
the step that produced them, on ``time.perf_counter``, in seconds after
the window opened.  After the window closes the loop steps on, with no
new arrivals, until every request sent has its first token.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax

from traffic import ClosedLoop, Request


@dataclass
class Sent:
    req: Request
    handle: object
    times: list = field(default_factory=list)   # token arrival times


@dataclass
class Step:
    index: int
    rows: int                 # decode rows dispatched (padded batch)
    prefill_tokens: int       # dispatched prompt tokens (buckets)
    prefills: list            # real prompt lengths admitted
    ctxs: list                # KV length of each live decode row

    @property
    def decode_only(self) -> bool:
        return self.prefill_tokens == 0


@dataclass
class Window:
    seconds: float
    sent: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    waits: list = field(default_factory=list)     # (t0, t1)
    lateness: list = field(default_factory=list)  # submit - due, open loop
    traced: tuple | None = None                   # (t0, t1) of the trace


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


def _step(session, sent_active, w: Window, clock) -> None:
    st = session.stats
    before = (st["decode_rows"], st["prefill_tokens"])
    seen = [len(s.handle.tokens) for s in sent_active]
    with jax.profiler.TraceAnnotation("session.step", i=len(w.steps)):
        session.step()
    t1 = clock()
    prefills, ctxs = [], []
    for s, n0 in zip(sent_active, seen):
        toks = s.handle.tokens
        for j in range(n0, len(toks)):
            s.times.append(t1)
            if j == 0:
                prefills.append(int(s.req.prompt.size))
            else:
                ctxs.append(int(s.req.prompt.size) + j)
    w.steps.append(Step(len(w.steps), st["decode_rows"] - before[0],
                        st["prefill_tokens"] - before[1], prefills, ctxs))


def _submit(session, req: Request, now: float, w: Window) -> Sent:
    h = session.submit(req.prompt, max_new_tokens=req.max_new_tokens)
    s = Sent(req, h)
    w.sent.append(s)
    return s


def run(session, *, seconds: float, schedule: list[Request] | None = None,
        closed: ClosedLoop | None = None, clients: int = 0,
        trace_span: tuple | None = None, trace_dir: str | None = None
        ) -> Window:
    """Run one window of ``seconds``: an open loop over ``schedule`` or a
    closed loop of ``clients`` drawing from ``closed``.  With
    ``trace_span`` = (start, length) the profiler records that part of
    the window into ``trace_dir``."""
    w = Window(seconds)
    clock = Clock()
    active: list[Sent] = []
    nxt = 0
    tracing = False
    if closed is not None:
        for _ in range(clients):
            active.append(_submit(session, closed.next(0.0), 0.0, w))
    while True:
        now = clock()
        if now >= seconds:
            break
        if trace_span is not None:
            if not tracing and w.traced is None and now >= trace_span[0]:
                jax.profiler.start_trace(trace_dir)
                tracing = True
                w.traced = (clock(), None)
            elif tracing and now >= w.traced[0] + trace_span[1]:
                jax.profiler.stop_trace()
                tracing = False
                w.traced = (w.traced[0], clock())
                continue
        if schedule is not None:
            while nxt < len(schedule) and schedule[nxt].due <= now:
                active.append(_submit(session, schedule[nxt], now, w))
                w.lateness.append(now - schedule[nxt].due)
                nxt += 1
        if session.pending:
            _step(session, active, w, clock)
            done = [s for s in active if s.handle.done]
            if done:
                active = [s for s in active if not s.handle.done]
                if closed is not None:
                    t = clock()
                    for _ in done:
                        active.append(_submit(session, closed.next(t), t, w))
            continue
        until = seconds
        if schedule is not None and nxt < len(schedule):
            until = min(until, schedule[nxt].due)
        t0 = clock()
        with jax.profiler.TraceAnnotation("generator.wait"):
            time.sleep(max(0.0, until - t0))
        w.waits.append((t0, clock()))
    if tracing:
        jax.profiler.stop_trace()
        w.traced = (w.traced[0], clock())
    # every request sent in the window counts in the TTFT tail: step on,
    # with no new arrivals, until each has its first token
    while session.pending and any(not s.times for s in active):
        _step(session, active, w, clock)
    return w


def drain(session) -> None:
    """Finish whatever is in flight (between windows of one process)."""
    while session.pending:
        session.step()
