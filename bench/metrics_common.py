"""Helpers the per-layer readers share (not a metric)."""
from __future__ import annotations

from typing import List, Tuple


def in_window(a: float, b: float, lo: float, hi: float) -> bool:
    return lo <= a and b <= hi


def traced_steps(run) -> List[Tuple[float, float, object]]:
    """Decode steps that ran wholly inside the traced stretch."""
    lo, hi = run.rec.marks.get("trace_on"), run.rec.marks.get("trace_off")
    if lo is None or hi is None:
        return []
    return [s for s in run.rec.steps if in_window(s[0], s[1], lo, hi)]


def roofline(flops: float, bytes_: float, seconds: float, peaks) -> float:
    """Least time the chip needs for the work over the time it took, in %."""
    least = max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
