"""Pallas grouped GEMM: y[e] = x[e] @ w[e] for E experts in one launch.

MXU tiling: grid (E, M/bm, N/bn, K/bk) with the K axis innermost
("arbitrary" semantics) accumulating into an f32 VMEM scratch tile; the
(bm, bk) x (bk, bn) blocks are 128-aligned for the 128x128 systolic array.
Expert slots arrive from moe_dispatch already padded to capacity, so M is
static per expert — the fixed-capacity design keeps the kernel shape-stable
across steps (no recompilation when routing changes: only the *plan* tensor
changes, which is the whole point of the control-flow plane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gg_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype")
)
def grouped_gemm_pallas(
    x: jnp.ndarray,  # (E, M, K)
    w: jnp.ndarray,  # (E, K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    E, M, K = x.shape
    N = w.shape[-1]
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)

    def pad_to(a, axis, mult):
        r = (-a.shape[axis]) % mult
        if r:
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, r)
            a = jnp.pad(a, pad)
        return a

    x = pad_to(pad_to(x, 1, bm), 2, bk)
    w = pad_to(pad_to(w, 1, bk), 2, bn)
    Mp, Kp, Np = x.shape[1], x.shape[2], w.shape[2]
    nk = Kp // bk
    grid = (E, Mp // bm, Np // bn, nk)

    out = pl.pallas_call(
        functools.partial(_gg_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, m, n, k: (e, m, k)),
            pl.BlockSpec((1, bk, bn), lambda e, m, n, k: (e, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, m, n, k: (e, m, n)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="grouped_gemm",
    )(x, w)
    return out[:, :M, :N]
