"""jit'd wrapper for the RG-LRU blocked recurrence kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas


def rglru_scan(
    a: jnp.ndarray,   # (B, T, W)
    b: jnp.ndarray,   # (B, T, W)
    h0: Optional[jnp.ndarray] = None,  # (B, W)
    *,
    bt: int = 256,
    bw: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    it = resolve_interpret(interpret)
    B, T, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    bt_ = min(bt, T)
    bw_ = min(bw, W)
    pad_t = (-T) % bt_
    pad_w = (-W) % bw_
    a32 = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pad_t), (0, pad_w)))
    b32 = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, pad_t), (0, pad_w)))
    h0p = jnp.pad(h0.astype(jnp.float32), ((0, 0), (0, pad_w)))
    out = rglru_scan_pallas(a32, b32, h0p, bt=bt_, bw=bw_, interpret=it)
    return out[:, :T, :W]
