"""Reduction of a profiler trace to the device's busy time, each kernel's
device time and the idle gaps, named by what the host was doing.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into two
lists: the device's operations ``(name, start_s, end_s)`` and the host spans
the benchmark opened (``TraceAnnotation``s named ``bench.<what>``), on one
clock.  :func:`reduce` works on those lists only, so it can be checked on a
small recorded trace.

* The traced window runs from the start of the first host span to the end
  of the last one.
* Busy is the union of the device operations' intervals inside it;
  ``busy_s / window_s`` is the busy share.
* Operations are named by their HLO instruction without its number
  (``flash_decode.7`` is ``flash_decode``) and timed by their self time:
  a loop's operation holds its body's operations on the same line, and
  its own time is what they leave.
* A kernel's device time is the self time of the operations whose name
  contains one of its patterns.
* An idle gap is a stretch of the window with no device operation; it is
  named by the host span that covers its midpoint (``"none"`` if no span
  does).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]
SPAN_PREFIX = "bench."
_DEVICE_LINES = ("XLA Ops",)


def load(trace_dir: str) -> Tuple[List[Event], List[Event]]:
    """Device operations and ``bench.`` host spans of the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return [], []
    data = ProfileData.from_file(paths[-1])
    ops: List[Event] = []
    spans: List[Event] = []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            named = [l for l in lines if l.name in _DEVICE_LINES]
            for line in named or lines[:0]:
                for e in line.events:
                    ops.append((short_name(e.name), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return ops, spans


def short_name(hlo: str) -> str:
    """``%flash_decode.7 = bf16[...] custom-call(...)`` -> ``flash_decode.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``flash_decode.7`` -> ``flash_decode``: one name for every instance."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each operation's time less the operations nested inside it (a loop
    holds its body's operations on the same line)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []  # [name, start, end, child seconds]
    for n, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += b - a
        stack.append([n, a, b, 0.0])
    out.extend((n, b - a - c) for n, a, b, c in stack)
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce(ops: Sequence[Event], spans: Sequence[Event]) -> Dict:
    """Busy and window seconds, self seconds and count per operation name,
    and the operations that took most time and the longest idle gaps (10
    each)."""
    if not spans:
        return {}
    w0 = min(a for _, a, _ in spans)
    w1 = max(b for _, _, b in spans)
    inside = [(n, max(a, w0), min(b, w1)) for n, a, b in ops if b > w0 and a < w1]
    busy = _union((a, b) for _, a, b in inside)
    busy_s = sum(b - a for a, b in busy)
    by_name: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for n, s in self_times(inside):
        k = base_name(n)
        by_name[k] = by_name.get(k, 0.0) + s
        count[k] = count.get(k, 0) + 1
    idle, t = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    named = []
    for a, b in idle:
        mid = 0.5 * (a + b)
        cover = [n for n, s0, s1 in spans if s0 <= mid <= s1]
        named.append((cover[-1] if cover else "none", b - a))
    named.sort(key=lambda x: -x[1])
    top_ops = sorted(by_name.items(), key=lambda x: -x[1])[:10]
    return {
        "window_s": w1 - w0, "busy_s": busy_s, "op_s": by_name, "op_n": count,
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[n, s] for n, s in named[:10]],
    }


def kernel_seconds(reduced: Dict, patterns: Sequence[str]) -> float:
    """Device seconds of the operations whose name contains a pattern."""
    return sum(s for n, s in reduced["op_s"].items() if any(p in n for p in patterns))
