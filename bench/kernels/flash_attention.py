"""flash_attention: causal prefill attention of one layer over ``S`` tokens.

Query ``i`` attends to ``i + 1`` rows, so the products cover
``S (S + 1) / 2`` (query, key) pairs: ``4 * nq * hd`` operations each.  The
least traffic reads q, k and v once and writes the output once.
"""
from __future__ import annotations

from typing import Tuple


def work(S: int, nq: int, nkv: int, hd: int, act_bytes: int = 2) -> Tuple[float, float]:
    pairs = S * (S + 1) / 2.0
    flops = 4.0 * nq * hd * pairs
    bytes_ = S * (2 * nq + 2 * nkv) * hd * act_bytes
    return flops, bytes_

# names of its device operations in a profiler trace
NAMES = ("flash_attention",)
