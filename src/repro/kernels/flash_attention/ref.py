"""Pure-jnp oracles.  Prefill delegates to the model's blockwise
online-softmax attention (the semantics source of truth shared with the LM
stack); the decode oracles are one masked softmax over the whole cache in
f32, written from the kernels' documented masks and nothing else."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import blockwise_attention


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, nq, hd)
    k: jnp.ndarray,  # (B, Skv, nkv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    return blockwise_attention(q, k, v, causal=causal, local_window=window)


# ---------------------------------------------------------------------------
# decode oracles: one masked softmax over the whole cache, in f32
# ---------------------------------------------------------------------------


def _masked_attention(q, k, v, valid):
    """q (B, T, nq, hd); k/v (B, S, nkv, hd); valid (B, T, S) bool."""
    B, T, nq, hd = q.shape
    nkv = k.shape[2]
    qg = q.astype(jnp.float32).reshape(B, T, nkv, nq // nkv, hd)
    s = jnp.einsum("btngh,bsnh->btngs", qg, k.astype(jnp.float32)) / math.sqrt(hd)
    s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btngs,bsnh->btngh", w, v.astype(jnp.float32))
    return out.reshape(B, T, nq, hd)


def _dequant(c, s):
    if s is None:
        return c
    return c.astype(jnp.float32) * s.astype(jnp.float32)[..., None, None]


def _token_positions(cache_index, B: int, T: int):
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    if idx.ndim == 1:
        idx = idx[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    return idx  # (B, T) highest visible cache row per token


def flash_decode(q, k, v, cache_index, *, ancestors=None, base=None, scales=None):
    """Oracle of :func:`repro.kernels.flash_attention.decode.flash_decode`:
    token (b, t) sees rows ``[0, index(b, t)]``; with a tree, draft rows
    ``base[b] + u`` only where bit ``u`` of ``ancestors[t]`` is set."""
    B, T = q.shape[:2]
    S = k.shape[1]
    k = _dequant(k, None if scales is None else scales[0])
    v = _dequant(v, None if scales is None else scales[1])
    rows = jnp.arange(S, dtype=jnp.int32)
    valid = rows[None, None, :] <= _token_positions(cache_index, B, T)[:, :, None]
    if ancestors is not None:
        u = rows[None, :] - jnp.asarray(base, jnp.int32)[:, None]  # (B, S)
        bit = jnp.right_shift(
            jnp.asarray(ancestors, jnp.int32)[None, :, None], jnp.clip(u, 0, 31)[:, None, :]
        ) & 1
        valid &= (u[:, None, :] < 0) | (bit > 0)
    return _masked_attention(q, k, v, valid)


def flash_decode_window(q, k, v, cache_index, *, window: int, scales=None):
    """Oracle of the rolling-cache kernel: slot ``s`` of a W-slot buffer
    holds the largest position ``p <= head`` with ``p % W == s``; token at
    position ``pos`` sees ``(pos - window, pos]``."""
    B, T = q.shape[:2]
    W = k.shape[1]
    k = _dequant(k, None if scales is None else scales[0])
    v = _dequant(v, None if scales is None else scales[1])
    pos = _token_positions(cache_index, B, T)  # (B, T)
    head = pos[:, -1]
    slot = jnp.arange(W, dtype=jnp.int32)
    abs_pos = head[:, None] - jnp.remainder(jnp.remainder(head, W)[:, None] - slot[None, :], W)
    a = abs_pos[:, None, :]
    p = pos[:, :, None]
    valid = (a >= 0) & (a <= p) & (a > p - window)
    return _masked_attention(q, k, v, valid)


def flash_decode_paged(q, k, v, cache_index, pages, *, page_size: int,
                       ancestors=None, base=None, scales=None):
    """Oracle of the paged kernel: gather each slot's pages into a
    contiguous view, then :func:`flash_decode` with unmapped pages masked."""
    B, mp = pages.shape
    safe = jnp.where(pages >= 0, pages, 0)
    rows = ((safe * page_size)[:, :, None] + jnp.arange(page_size)[None, None, :]).reshape(B, -1)
    kv = k[rows], v[rows]  # (B, mp * ps, nkv, hd)
    scl = None if scales is None else scales[:, rows]  # (2, B, mp * ps)
    # rows of unmapped pages lie beyond every token's length by contract
    return flash_decode(q, *kv, cache_index, ancestors=ancestors, base=base, scales=scl)
