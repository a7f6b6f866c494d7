"""Pallas chunked SSD (Mamba-2 state-space duality) scan.

Grid (B, H, T/Q) with the chunk axis innermost ("arbitrary"); the carried
(N, P) state lives in f32 VMEM scratch.  Per chunk, the four dual-form
matmuls run on the MXU:

    scores  = (C B^T ∘ decay ∘ dt)          (Q x Q)
    y       = scores @ x  +  (C ∘ exp(cum)) @ state        (Q x P)
    state   = exp(last) * state + (B ∘ w)^T @ x            (N x P)

Q (chunk) = 128..256, N (state) = 128, P (head dim) = 64 in mamba2-2.7b —
all MXU-aligned.  The quadratic term never leaves VMEM: chunking bounds it
at Q^2 instead of T^2, which is the paper-free lunch SSD brings to TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, cum_ref, o_ref, hout_ref, h_ref, *, q: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)         # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)    # (Q,)
    bm = b_ref[0].astype(jnp.float32)           # (Q, N)
    cm = c_ref[0].astype(jnp.float32)           # (Q, N)
    cum = cum_ref[0, 0, 0].astype(jnp.float32)  # (Q,)

    cb = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ii >= jj
    scores = cb * jnp.where(causal, decay, 0.0) * dt[None, :]
    y_intra = jnp.dot(scores, x, preferred_element_type=jnp.float32)  # (Q, P)

    h = h_ref[...]
    state_decay = jnp.exp(cum)[:, None]                       # (Q, 1)
    y_inter = jnp.dot(cm * state_decay, h, preferred_element_type=jnp.float32)

    last = cum[q - 1]
    w = jnp.exp(last - cum) * dt                              # (Q,)
    s_chunk = jnp.dot((bm * w[:, None]).T, x, preferred_element_type=jnp.float32)  # (N, P)
    h_ref[...] = jnp.exp(last) * h + s_chunk

    o_ref[...] = (y_intra + y_inter).astype(o_ref.dtype)[None, None]

    @pl.when(ci == n_chunks - 1)
    def _store_state():
        hout_ref[...] = h_ref[...][None, None]


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,    # (B, H, T, P) f32
    dt: jnp.ndarray,   # (B, H, T) f32 (post-softplus)
    bm: jnp.ndarray,   # (B, T, N) f32
    cm: jnp.ndarray,   # (B, T, N) f32
    cum: jnp.ndarray,  # (B, H, T) f32 inclusive cumsum of dt*a within chunks
    *,
    q: int = 128,
    interpret: bool = False,
):
    B, H, T, P = x.shape
    N = bm.shape[-1]
    assert T % q == 0, "pad T to chunk multiple in ops.py"
    nc = T // q
    grid = (B, H, nc)

    # reshape time into (nc, q) blocks for clean BlockSpecs
    dt2 = dt.reshape(B, H, nc, q)
    cum2 = cum.reshape(B, H, nc, q)

    kern = functools.partial(_ssd_kernel, q=q, n_chunks=nc)
    y, h_final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, T, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt2, bm, cm, cum2)
    return y, h_final
