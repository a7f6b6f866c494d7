"""Pallas flash attention forward (causal / local-window, GQA).

Grid (B, nq, Sq/bq, Skv/bkv), KV innermost with "arbitrary" semantics;
online-softmax running stats (m, l) and the (bq, hd) accumulator live in
f32 VMEM scratch carried across KV steps.  GQA maps query head h to KV head
h // (nq/nkv) inside the K/V BlockSpec index_maps — no KV replication in
HBM.  Fully-masked causal/local blocks are skipped with pl.when (the MXU
never sees them), which is what makes 32k-prefill memory- rather than
compute-catastrophic-free.

Layouts (ops.py transposes): q (B, nq, Sq, hd); k/v (B, nkv, Skv, hd).
hd is 64..256 in the assigned configs (lane-aligned); bq=bkv=128 sublane
tiles feed the 128x128 MXU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *,
    bq: int,
    bkv: int,
    n_kv: int,
    kv_len: int,
    scale: float,
    causal: bool,
    window: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_base = qi * bq
    kv_base = ki * bkv

    # block-level reachability: skip fully-masked blocks entirely
    reachable = True
    if causal:
        reachable = jnp.asarray(kv_base <= q_base + bq - 1)
    if window:
        reachable = jnp.logical_and(
            reachable, kv_base + bkv - 1 > q_base - window
        )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_base + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = kv_base + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kv_pos < kv_len  # exclude KV padding columns
        if causal:
            mask &= q_pos >= kv_pos
        if window:
            mask &= q_pos - kv_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None, None]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bkv", "kv_len", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, nq, Sq, hd)
    k: jnp.ndarray,  # (B, nkv, Skv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bkv: int = 128,
    kv_len: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    B, nq, Sq, hd = q.shape
    nkv, Skv = k.shape[1], k.shape[2]
    group = nq // nkv
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    kv_len = kv_len or Skv
    assert Sq % bq == 0 and Skv % bkv == 0, "pad seq to block multiple in ops.py"
    n_kv = Skv // bkv
    grid = (B, nq, Sq // bq, n_kv)

    kern = functools.partial(
        _flash_kernel,
        bq=bq, bkv=bkv, n_kv=n_kv, kv_len=kv_len, scale=scale, causal=causal,
        window=window,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
