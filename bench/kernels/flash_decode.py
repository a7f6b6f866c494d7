"""flash_decode: one decode launch of one layer's attention over the cache.

Token ``i`` attends to ``lengths[i]`` cache rows (its own included): a
``q . k`` and a ``p . v`` product of ``head_dim`` per query head and row.
Each KV row is read once per launch for its KV head (grouped-query heads
share it), and each query and output row once.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def work(lengths: Sequence[int], nq: int, nkv: int, hd: int,
         kv_bytes: int = 2, act_bytes: int = 2) -> Tuple[float, float]:
    rows = float(sum(int(n) for n in lengths))
    tokens = len(lengths)
    flops = 4.0 * nq * hd * rows
    bytes_ = rows * 2 * nkv * hd * kv_bytes + tokens * 2 * nq * hd * act_bytes
    return flops, bytes_

# names of its device operations in a profiler trace
NAMES = ("flash_decode",)
