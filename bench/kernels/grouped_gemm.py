"""grouped_gemm: ``y[e] = x[e] @ w[e]`` over the experts' routed rows.

Useful work counts the rows routed to an expert, not the padded capacity:
``2 * rows * K * N`` operations; the weights of every expert that receives a
row read once (with ``rows`` assignments over ``experts`` experts, at most
``min(rows, experts)`` of them), and each routed row read and written once.
"""
from __future__ import annotations

from typing import Optional, Tuple


def work(rows: int, experts: int, K: int, N: int,
         w_bytes: int = 2, act_bytes: int = 2) -> Tuple[float, float]:
    flops = 2.0 * rows * K * N
    bytes_ = min(rows, experts) * K * N * w_bytes + rows * (K + N) * act_bytes
    return flops, bytes_


def swiglu(tokens: int, top_k: int, experts: int, d: int, f: int, *,
           held: Optional[int] = None, router: Optional[int] = None) -> Tuple[float, float]:
    """The three launches of one MoE layer's experts: gate and up (d -> f),
    down (f -> d).  A chip that holds ``held`` of the router's ``router``
    experts (both default to ``experts``) computes the
    ``tokens * top_k * held / router`` routed rows that fall to its experts,
    on weights of at most ``min(rows, held)`` of them."""
    held = experts if held is None else held
    router = experts if router is None else router
    rows = tokens * top_k * held / router
    parts = [work(rows, held, d, f), work(rows, held, d, f), work(rows, held, f, d)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)

# names of its device operations in a profiler trace
NAMES = ("grouped_gemm",)
