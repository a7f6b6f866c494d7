"""The program's own spans (``repro.obs``) on the window's clock, for the
per-layer readers that look inside the replica's calls (not a metric).

The program stamps its spans with ``time.perf_counter`` as the harness
stamps ``rec.steps``, but the harness's records are shifted to the window's
start.  :func:`aligned` pairs the program's last N ``serve.step`` spans
with the N steps the harness recorded, in order, and shifts every program
span by the median of the start differences.  Each shifted ``serve.step``
must then lie inside its harness step to within ``SLACK_S``.  It returns
None when the pairing fails, when the ring dropped spans of the stretch it
reads, and when the program keeps no spans (one without ``repro.obs``).

The traced stretch (``rec.marks`` ``trace_on`` .. ``trace_off``) is left
out by the readers, so the profiler's own cost does not enter them.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

SLACK_S = 1e-3
STEP = "serve.step"


def _snapshot() -> Optional[Dict]:
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def aligned(run) -> Optional[Dict]:
    """``{"spans": [...], "steps": [...]}``: every program span and the
    paired ``serve.step`` spans, each a ``(id, parent, name, start, end,
    attrs)`` tuple on the window's clock; or None."""
    snap = _snapshot()
    bench_steps = run.rec.steps
    if not snap or not bench_steps:
        return None
    spans = snap["spans"]
    prog = [s for s in spans if s[2] == STEP][-len(bench_steps):]
    if len(prog) < len(bench_steps):
        return None
    if snap["dropped"] and spans[0][4] > prog[0][3]:
        return None  # spans that closed inside the stretch fell out of the ring
    off = statistics.median(b[0] - p[3] for b, p in zip(bench_steps, prog))
    for (a, b, _), p in zip(bench_steps, prog):
        if p[3] + off < a - SLACK_S or p[4] + off > b + SLACK_S:
            return None
    shift = lambda s: (s[0], s[1], s[2], s[3] + off, s[4] + off, s[5])
    return {"spans": [shift(s) for s in spans], "steps": [shift(p) for p in prog]}


def traced(run) -> Optional[Tuple[float, float]]:
    lo, hi = run.rec.marks.get("trace_on"), run.rec.marks.get("trace_off")
    return None if lo is None or hi is None else (lo, hi)


def overlap(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def window_steps(run, steps: List[tuple]) -> List[tuple]:
    """The paired steps inside the window and clear of the traced stretch."""
    cut = traced(run)
    return [s for s in steps if s[3] >= 0.0 and s[4] <= run.seconds
            and (cut is None or overlap(s[3], s[4], *cut) == 0.0)]
