"""Shared int8 quantization: the scale is a control word.

Symmetric int8 with a float scale, used on both planes of the control/data
split:

* wire (``parallel/collectives``): per-tensor scales ride the gradient
  all-reduce as 4-byte control words next to the int8 payload.
* serve (quantized bandwidth plane): per-expert weight scales ride the
  scalar-prefetch path next to the plan's expert ids, and per-token KV
  scales follow their KV tile through the index map that lengths and block
  tables steer — the data plane streams int8 with its scales beside it.

``axis=`` selects blockwise scales: the amax reduces over the given axes
(keepdims) so the returned scale broadcasts against the quantized tensor —
e.g. ``axis=(-2, -1)`` on a (B, S, nkv, hd) KV buffer yields one scale per
token row, the granularity at which speculative rollback and paged CoW move
cache rows.

``dequantize_int8`` accumulates the product in f32 and by default returns
the SCALE's dtype — quantizing a bf16 tensor hands back a bf16 scale, so
the round trip honors the input's target dtype without every caller
re-threading it.  Pass ``dtype=`` to override (the compressed-psum path
casts the int32 partial sums back to the gradient dtype explicitly).
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax.numpy as jnp

Axis = Union[int, Tuple[int, ...]]


def quantize_int8(x: jnp.ndarray, axis: Optional[Axis] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization; the scale is the control word.

    ``axis=None``: one per-tensor scalar scale (f32, wire behavior).
    ``axis=int | tuple``: blockwise — amax over the given axes with
    keepdims, scale broadcastable against ``x`` and carried in ``x``'s own
    floating dtype so the default dequantization round-trips it.
    """
    xf = x.astype(jnp.float32)
    if axis is None:
        amax = jnp.max(jnp.abs(xf))
    else:
        amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    if axis is not None and jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        scale = scale.astype(x.dtype)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """f32-accumulated dequantization, cast to ``dtype`` (default: the
    scale's dtype — the target dtype the quantizer recorded)."""
    out = q.astype(jnp.float32) * scale.astype(jnp.float32)
    return out.astype(dtype if dtype is not None else scale.dtype)
