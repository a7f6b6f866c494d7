"""The metric arithmetic shared by the harness and the per-layer readers.

Times are seconds from the start of the measured window; the window is
``[0, seconds)``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile, linear between order statistics (numpy's
    default); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(due: Dict[int, float], first: Dict[int, float], seconds: float) -> List[float]:
    """Time to first token of every request due in the window, from when it
    was due: the wait for admission counts."""
    return [first[r] - d for r, d in due.items() if 0.0 <= d < seconds and r in first]


def gaps(token_times: Dict[int, List[float]], seconds: float) -> List[float]:
    """Every gap between consecutive tokens of a request, both in the window."""
    out: List[float] = []
    for ts in token_times.values():
        for a, b in zip(ts, ts[1:]):
            if 0.0 <= a and b < seconds:
                out.append(b - a)
    return out


def tokens_in(token_times: Dict[int, List[float]], seconds: float) -> int:
    return sum(1 for ts in token_times.values() for t in ts if 0.0 <= t < seconds)


def span_seconds(spans: Iterable[Tuple[str, float, float]], name: str,
                 seconds: float) -> float:
    """Time spent in spans called ``name``, clipped to the window."""
    total = 0.0
    for n, a, b in spans:
        if n == name:
            total += max(0.0, min(b, seconds) - max(a, 0.0))
    return total
