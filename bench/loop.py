"""The serving loop the window drives, and what it records.

``ServeFabric.run`` takes a queue fixed before it starts, so the benchmark
repeats the fabric's single-replica round itself (a copy of
``runtime/fabric.py``'s ``_admit_from_queue`` and the round in ``run``):
admit from the queue into free slots, then one ``ServeReplica.step()``.
Requests join the queue when they fall due.  What happens inside ``admit``
and ``step`` is the program's.

Each token is stamped with the host clock when the call that produced it
returns: ``admit`` (the first token, from the prefill's logits) and ``step``
both end in a host read of device results.  The calls run inside
``TraceAnnotation``s named ``bench.admit``, ``bench.step`` and
``bench.wait`` (waiting for the next arrival), which the trace reduction
reads.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional

import numpy as np

from bench.traffic import Req, Schedule


class Recorder:
    """Host-clock records of one run, in absolute ``perf_counter`` seconds
    until :meth:`relative_to` shifts them to the window's start."""

    def __init__(self):
        self.spans: List[tuple] = []        # (name, start, end)
        self.due: Dict[int, float] = {}     # rid -> when it was due
        self.admit: Dict[int, tuple] = {}   # rid -> (start, end, prompt length)
        self.tokens: Dict[int, List[float]] = collections.defaultdict(list)
        self.steps: List[tuple] = []        # (start, end, contexts of the active slots)
        self.finished: Dict[int, List[int]] = {}
        self.rejected: Dict[int, str] = {}
        self.marks: Dict[str, float] = {}   # e.g. trace_on, trace_off

    def stamp(self, rid: int, n: int, t: float) -> None:
        ts = self.tokens[rid]
        ts.extend([t] * (n - len(ts)))

    def relative_to(self, t0: float) -> "Recorder":
        self.spans = [(n, a - t0, b - t0) for n, a, b in self.spans]
        self.due = {r: d - t0 for r, d in self.due.items()}
        self.admit = {r: (a - t0, b - t0, s) for r, (a, b, s) in self.admit.items()}
        self.tokens = {r: [t - t0 for t in ts] for r, ts in self.tokens.items()}
        self.steps = [(a - t0, b - t0, c) for a, b, c in self.steps]
        self.marks = {k: v - t0 for k, v in self.marks.items()}
        return self


class Traffic:
    """Arrivals: an open loop releases requests at their due times; a closed
    loop makes the next request due when one finishes."""

    def __init__(self, sched: Schedule, t0: float):
        self.closed = sched.closed
        self.pending = collections.deque(sched.requests)
        self.queue: collections.deque = collections.deque()
        self.t0 = t0

    def release(self, now: float, rec: Recorder) -> None:
        while self.pending and not self.closed and self.t0 + self.pending[0].due <= now:
            req = self.pending.popleft()
            rec.due[req.rid] = self.t0 + req.due
            self.queue.append(req)

    def finished(self, now: float, rec: Recorder) -> None:
        if self.closed and self.pending:
            req = self.pending.popleft()
            rec.due[req.rid] = now
            self.queue.append(req)

    def next_due(self) -> Optional[float]:
        if self.closed or not self.pending:
            return None
        return self.t0 + self.pending[0].due


def to_request(req: Req):
    from repro.runtime.fabric import Request

    return Request(rid=req.rid, prompt=req.prompt, gen=req.output - 1)


class Loop:
    def __init__(self, rep, traffic: Traffic, rec: Recorder, clock=time.perf_counter):
        from jax.profiler import TraceAnnotation
        from repro.runtime.faults import RequestRejected

        self.rep, self.traffic, self.rec, self.clock = rep, traffic, rec, clock
        self._span, self._rejected = TraceAnnotation, RequestRejected

    def admit_from_queue(self) -> None:
        rep, q, rec = self.rep, self.traffic.queue, self.rec
        while q and rep.free_slots():
            req = q[0]
            t_a = self.clock()
            try:
                with self._span("bench.admit"):
                    rep.admit(to_request(req))
            except self._rejected as err:
                q.popleft()
                rec.rejected[req.rid] = str(err)
                self.traffic.finished(self.clock(), rec)
                continue
            q.popleft()
            t_b = self.clock()
            rec.spans.append(("admit", t_a, t_b))
            rec.admit[req.rid] = (t_a, t_b, len(req.prompt))
            rec.stamp(req.rid, 1, t_b)

    def step(self) -> None:
        rep, rec = self.rep, self.rec
        ctx = rep.lengths[rep.active] + 1
        t_a = self.clock()
        with self._span("bench.step"):
            done = rep.step()
        t_b = self.clock()
        rec.spans.append(("step", t_a, t_b))
        rec.steps.append((t_a, t_b, np.asarray(ctx)))
        for b in range(rep.B):
            r = rep.requests[b]
            if r is not None:
                rec.stamp(r.rid, len(rep.emitted[b]), t_b)
        for res in done:
            rec.stamp(res.rid, len(res.tokens), t_b)
            rec.finished[res.rid] = list(res.tokens)
            self.traffic.finished(t_b, rec)

    def run(self, until: float, *, stop=None, on_round=None) -> None:
        """Rounds until ``until`` (absolute) or until ``stop()`` is true."""
        traffic, rep = self.traffic, self.rep
        while True:
            now = self.clock()
            if now >= until or (stop is not None and stop()):
                return
            if on_round is not None:
                on_round(now)
            traffic.release(now, self.rec)
            if traffic.queue and rep.free_slots():
                self.admit_from_queue()
            if rep.has_work():
                self.step()
                continue
            nxt = traffic.next_due()
            if nxt is None and not traffic.queue:
                if traffic.closed:
                    return  # every request of a closed loop is served
                nxt = until
            wait = min(nxt if nxt is not None else until, until) - self.clock()
            if wait > 0:
                t_a = self.clock()
                with self._span("bench.wait"):
                    time.sleep(wait)
                self.rec.spans.append(("wait", t_a, self.clock()))
