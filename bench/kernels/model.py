"""Model operations of the published block, per token, from the sizes of a
configuration (:func:`bench.weights.dims_of`).

A token at context ``c`` (it attends to ``c`` rows, itself included) costs,
per layer, the q/k/v/o projections ``2 d (nq + 2 nkv) hd + 2 nq hd d``,
attention ``4 nq hd c``, and the FFN: ``6 d ff`` dense, or for MoE the
router ``2 d R`` at its published width R and ``6 d ff`` for each of the
``top_k`` experts a token routes to, scaled by the held share ``E / R``: the
chip's expected part of them (all of them where every expert is held).  A
token whose logits are computed adds the head, ``2 d V``.  Prefill computes
logits for the last prompt token only; decode for every token.
"""
from __future__ import annotations

from typing import Dict, Iterable


def _fixed(dm: Dict[str, int]) -> float:
    d, nq, nkv, hd, ff = dm["d"], dm["nq"], dm["nkv"], dm["hd"], dm["ff"]
    proj = 2.0 * d * (nq + 2 * nkv) * hd + 2.0 * nq * hd * d
    if dm["experts"]:
        R = dm["router"]
        ffn = 2.0 * d * R + 6.0 * d * ff * dm["top_k"] * dm["experts"] / R
    else:
        ffn = 6.0 * d * ff
    return proj + ffn


def decode(dm: Dict[str, int], contexts: Iterable[int]) -> float:
    """Decode tokens at the given contexts, each with its logits."""
    ctx = [int(c) for c in contexts]
    per_layer = len(ctx) * _fixed(dm) + 4.0 * dm["nq"] * dm["hd"] * sum(ctx)
    return dm["layers"] * per_layer + len(ctx) * 2.0 * dm["d"] * dm["vocab"]


def prefill(dm: Dict[str, int], S: int) -> float:
    """A prompt of S tokens, logits for its last token."""
    attn = 4.0 * dm["nq"] * dm["hd"] * S * (S + 1) / 2.0
    return dm["layers"] * (S * _fixed(dm) + attn) + 2.0 * dm["d"] * dm["vocab"]
