"""Closed-loop callers driving a served engine's ``submit`` / ``run``.

Each of ``callers`` callers keeps one request outstanding: it sends its
next request the moment its previous answer is on the host.  One thread
plays all callers: after every ``engine.run(max_batches=1)`` the request
the scheduler picked is complete (its answer, on the host, is what the
kind's ``answer`` reads off the engine), and its caller sends again.  A
request's latency runs from its send to that moment, on the host clock.
The kind (``kinds/``) gives ``send(engine, req)`` and ``answer(engine)``.

The loop learns which request a batch is by wrapping the engine's
scheduler's ``submit`` and ``next_batch`` (a batch's ``seq``).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np


class Done:
    """One answered request."""
    __slots__ = ("request", "sent", "done", "answer")

    def __init__(self, request, sent: float):
        self.request = request
        self.sent = sent
        self.done = 0.0
        self.answer: Optional[np.ndarray] = None


class ClosedLoop:
    def __init__(self, engine, requests: List, callers: int,
                 send: Callable, answer: Callable):
        self.engine = engine
        self._send = send
        self._answer = answer
        self.requests = requests
        self.callers = callers
        self.next = 0                    # index of the next request to send
        self.wrapped = 0                 # times the request list restarted
        self.inflight: Dict[int, Done] = {}
        self._submitted = None
        self._popped = None
        sched = engine.scheduler
        submit, next_batch = sched.submit, sched.next_batch

        def submit_hook(*a, **kw):
            self._submitted = b = submit(*a, **kw)
            return b

        def next_batch_hook(*a, **kw):
            self._popped = b = next_batch(*a, **kw)
            return b

        sched.submit, sched.next_batch = submit_hook, next_batch_hook

    def send(self) -> None:
        if self.next == len(self.requests):
            self.next = 0
            self.wrapped += 1
        req = self.requests[self.next]
        self.next += 1
        rec = Done(req, time.perf_counter())
        self._send(self.engine, req)
        self.inflight[self._submitted.seq] = rec

    def step(self) -> Done:
        """Serve one batch; returns the request it answered."""
        self._popped = None
        self.engine.run(max_batches=1)
        if self._popped is None:
            raise RuntimeError("engine.run served no batch")
        rec = self.inflight.pop(self._popped.seq)
        rec.answer = self._answer(self.engine)
        rec.done = time.perf_counter()
        return rec

    def serve_count(self, n: int) -> List[Done]:
        """Serve ``n`` requests, then drain: no request is left in flight."""
        out: List[Done] = []
        sent = 0
        while len(self.inflight) < self.callers and sent < n:
            self.send()
            sent += 1
        while self.inflight:
            out.append(self.step())
            if sent < n:
                self.send()
                sent += 1
        return out

    def serve_for(self, seconds: float) -> tuple:
        """The measured window: callers start together, and requests are
        answered until ``seconds`` have passed.  Returns ``(t0, t_end,
        answered)``, where ``t_end`` is the last answer's time; requests
        still in flight at the close are not answered in the window."""
        out: List[Done] = []
        t0 = time.perf_counter()
        close = t0 + seconds
        for _ in range(self.callers):
            self.send()
        while True:
            rec = self.step()
            out.append(rec)
            if rec.done >= close:
                break
            self.send()
        return t0, out[-1].done, out
