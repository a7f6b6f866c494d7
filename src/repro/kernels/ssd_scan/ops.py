"""jit'd wrapper: model layout (B, T, H, P) in, chunk-local cumsum prep."""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


def ssd_scan(
    x: jnp.ndarray,    # (B, T, H, P)
    dt: jnp.ndarray,   # (B, T, H) post-softplus
    a: jnp.ndarray,    # (H,) negative
    bm: jnp.ndarray,   # (B, T, N)
    cm: jnp.ndarray,   # (B, T, N)
    chunk: int = 128,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B, T, H, P) f32, final state (B, H, N, P) f32)."""
    it = resolve_interpret(interpret)
    B, T, H, P = x.shape
    q = min(chunk, T)
    pad = (-T) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad

    xt = jnp.transpose(x, (0, 2, 1, 3)).astype(jnp.float32)        # (B, H, T, P)
    dtt = jnp.transpose(dt, (0, 2, 1)).astype(jnp.float32)         # (B, H, T)
    # within-chunk inclusive cumsum of dt * a
    l = dtt * a[None, :, None]
    cum = jnp.cumsum(l.reshape(B, H, Tp // q, q), axis=-1).reshape(B, H, Tp)

    y, h = ssd_scan_pallas(
        xt, dtt, bm.astype(jnp.float32), cm.astype(jnp.float32), cum,
        q=q, interpret=it,
    )
    y = jnp.transpose(y, (0, 2, 1, 3))[:, :T]  # back to (B, T, H, P)
    return y, h
