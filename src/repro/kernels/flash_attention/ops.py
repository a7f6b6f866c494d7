"""jit'd wrapper: (B, S, n, hd) layout in/out, padding to block multiples."""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp

from repro.kernels import resolve_interpret, shard_over_heads
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, nq, hd) — model layout
    k: jnp.ndarray,  # (B, Skv, nkv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bkv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    run = functools.partial(
        _flash_attention, causal=causal, window=window, bq=bq, bkv=bkv, interpret=interpret
    )
    return shard_over_heads(run, (q, k, v), (2, 2, 2))


def _flash_attention(q, k, v, *, causal, window, bq, bkv, interpret):
    it = resolve_interpret(interpret)
    B, Sq, nq, hd = q.shape
    Skv = k.shape[1]
    bq_ = min(bq, Sq)
    bkv_ = min(bkv, Skv)
    pad_q = (-Sq) % bq_
    pad_kv = (-Skv) % bkv_
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        # padded KV columns are masked inside the kernel via kv_len.
    out = flash_attention_pallas(
        qt, kt, vt, causal=causal, window=window, bq=bq_, bkv=bkv_,
        kv_len=Skv, interpret=it,
    )
    out = out[:, :, :Sq]
    return jnp.swapaxes(out, 1, 2)
