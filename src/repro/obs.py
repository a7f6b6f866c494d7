"""The program's spans and counters.

``span(name, **attrs)`` times a stretch of host code on ``time.perf_counter``
and keeps ``(id, parent id, name, start, end, attrs)`` in a bounded ring; the
parent is the innermost span open in the same thread (0 for none).  It also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so whenever a
profile is taken every span lies in the trace on the device's clock; with no
profile running the annotation does nothing.  ``count(name, n)`` keeps
counters, and ``snapshot()`` returns the spans, the counters and the number
of spans the ring dropped.

``install()`` (idempotent) adds two sources to the same ring: a ``gc`` span
for each collection of generation 1 or 2 (``gen``, ``collected``) with a
counter ``gc.collections.<gen>`` for every generation, and a ``compile``
span for each of JAX's ``/jax/core/compile/*`` events (``event``,
``fun_name``) with a counter ``compiles.<fun_name>`` for each backend
compile, so a retrace, a cache load or a compile shows where it happened.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import Dict, List, NamedTuple

from jax.profiler import TraceAnnotation

# a 51 s serving window and its warm-up at about ten spans a step
RING_SPANS = 65536
COMPILE_EVENTS = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    id: int
    parent: int
    name: str
    start: float
    end: float
    attrs: Dict


class Ring:
    """The last ``size`` closed spans, the counters, and how many spans fell
    out of the ring."""

    def __init__(self, size: int = RING_SPANS):
        self.spans: collections.deque = collections.deque(maxlen=size)
        self.counters: Dict[str, int] = collections.Counter()
        self.closed = 0
        self.ids = itertools.count(1)
        self._open = threading.local()

    def stack(self) -> List[int]:
        """Ids of the spans open in this thread, innermost last."""
        try:
            return self._open.ids
        except AttributeError:
            self._open.ids = []
            return self._open.ids

    def innermost(self) -> int:
        stack = self.stack()
        return stack[-1] if stack else 0

    def keep(self, record: tuple) -> None:
        self.spans.append(record)
        self.closed += 1

    def snapshot(self) -> Dict:
        # copy first: a collection during the loop below appends a gc span
        held, closed = list(self.spans), self.closed
        return {"spans": [Span(*s) for s in held],
                "counters": dict(self.counters),
                "dropped": closed - len(held)}


RING = Ring()


class span:
    """``with span("serve.step", step=7):`` -- records the stretch into
    ``ring`` when it closes; ``start`` and ``end`` are then its stamps."""

    __slots__ = ("name", "attrs", "ring", "parent", "start", "end", "_ann", "_id")

    def __init__(self, name: str, *, ring: Ring = RING, **attrs):
        self.name, self.attrs, self.ring = name, attrs, ring

    def __enter__(self) -> "span":
        self.parent = self.ring.innermost()
        self._id = next(self.ring.ids)
        self.ring.stack().append(self._id)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self.ring.stack().pop()
        self.ring.keep((self._id, self.parent, self.name, self.start, self.end, self.attrs))


def count(name: str, n: int = 1, *, ring: Ring = RING) -> None:
    ring.counters[name] += n


def snapshot(ring: Ring = RING) -> Dict:
    return ring.snapshot()


class _GcSpans:
    """``gc.callbacks`` hook: a counter per generation, a span (and a
    profiler annotation) for generations 1 and 2."""

    def __init__(self, ring: Ring):
        self.ring = ring
        self.open: Dict[int, tuple] = {}

    def __call__(self, phase: str, info: Dict) -> None:
        gen = info["generation"]
        tid = threading.get_ident()
        if phase == "start":
            self.ring.counters[f"gc.collections.{gen}"] += 1
            if gen >= 1:
                ann = TraceAnnotation("gc", gen=gen)
                ann.__enter__()
                self.open[tid] = (time.perf_counter(), ann)
        elif tid in self.open:
            start, ann = self.open.pop(tid)
            end = time.perf_counter()
            ann.__exit__(None, None, None)
            ring = self.ring
            ring.keep((next(ring.ids), ring.innermost(), "gc", start, end,
                       {"gen": gen, "collected": info["collected"]}))


def _compile_listener(ring: Ring):
    def listen(event: str, start: float, end: float, **kw) -> None:
        if not event.startswith(COMPILE_EVENTS):
            return
        fun = str(kw.get("fun_name", ""))
        # JAX stamps these with time.time(); move them onto perf_counter
        shift = time.perf_counter() - time.time()
        ring.keep((next(ring.ids), ring.innermost(), "compile", start + shift, end + shift,
                   {"event": event[len(COMPILE_EVENTS):], "fun_name": fun}))
        if event == BACKEND_COMPILE:
            ring.counters[f"compiles.{fun}"] += 1

    return listen


_installed = False


def install() -> None:
    """Hook gc collections and JAX's compile events into the ring, once."""
    global _installed
    if _installed:
        return
    _installed = True
    from jax import monitoring

    gc.callbacks.append(_GcSpans(RING))
    monitoring.register_event_time_span_listener(_compile_listener(RING))
