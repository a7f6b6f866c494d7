"""Pallas blocked linear recurrence: h_t = a_t * h_{t-1} + b_t.

Grid (B, W/bw, T/bt) with the time axis innermost ("arbitrary"); the carried
state h lives in a (1, bw) f32 VMEM scratch that persists across sequential
time steps.  Within a block the recurrence walks bt rows on the VPU (channel
dim bw = lane dim, 128-aligned); blocks along W are independent (diagonal
recurrence) so the channel grid axis is "parallel".

This is the TPU-native shape of the RG-LRU scan: HBM traffic is exactly one
read of (a, b) and one write of h — the op is bandwidth-bound and the kernel
exists to guarantee that bound (no (T, W) temporaries like the
associative-scan lowering can materialize).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)  # (bt, bw)
    b = b_ref[0].astype(jnp.float32)

    def body(i, h):
        h = a[i] * h + b[i]
        o_ref[0, i] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, body, h_ref[0], unroll=False)
    h_ref[0] = h


@functools.partial(jax.jit, static_argnames=("bt", "bw", "interpret"))
def rglru_scan_pallas(
    a: jnp.ndarray,   # (B, T, W) f32
    b: jnp.ndarray,   # (B, T, W) f32
    h0: jnp.ndarray,  # (B, W) f32
    *,
    bt: int = 256,
    bw: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, W = a.shape
    bt = min(bt, T)
    bw = min(bw, W)
    assert T % bt == 0 and W % bw == 0, "pad T/W to block multiples in ops.py"
    grid = (B, W // bw, T // bt)
    kern = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
            pl.BlockSpec((1, bw), lambda bb, wi, ti: (bb, wi)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda bb, wi, ti: (bb, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, T, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0)
