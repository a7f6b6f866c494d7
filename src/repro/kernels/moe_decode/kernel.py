"""Tiny-T decode MoE data plane: plan-steered expert SwiGLU in ONE Pallas
launch, no slot tensors.

The prefill-shaped path pays, per decode step and MoE layer: an argsort-based
plan build over T*k assignments, a gather into (E, C, d) slots, grouped GEMMs
over ALL E*C slots (mostly padding at decode T), and a scatter back — three
HBM round-trips of tensors that are ~E*C/(T*k) times larger than the live
work.  Here the DecodePlan's (T, k) control words ride the scalar-prefetch
path instead and *steer the weight DMA itself*:

* grid (T, k, f-tiles): for assignment (t, j) the expert id read from SMEM is
  used inside the w_gate/w_up/w_down BlockSpec index_maps, so only the
  selected expert's weight tiles are ever fetched from HBM — the dispatch IS
  the weight stream.  Compute per step is exactly one token row through one
  expert's SwiGLU tile; the f-tile axis keeps the three weight tiles within
  VMEM at production d_ff.
* tokens move in sublane-aligned blocks of ``TOKEN_BLOCK`` rows: step
  (t, j, n) reads row t % TOKEN_BLOCK of token block t // TOKEN_BLOCK, and
  the matching f32 output block is revisited across the sequential grid —
  per-assignment results accumulate in place scaled by the SMEM combine
  weight (the scatter-combine is the GEMM epilogue, with token-major slots
  so no slot->token indirection is needed at all).  Work per step is
  O(TOKEN_BLOCK * d), independent of T.

This is the Agile-PE-Assignment shape of the paper applied to decode: the
loop body (one token per sequence) is far too small to fill the prefill
plane's spatial capacity, so the plane is re-assigned — T*k assignment-steps
that each fetch exactly the configuration (weights) the control plan names.
The control plane ran one step earlier (plan carried in the decode cache);
the data plane executes it with zero exposed control cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens per x / output block: the TPU compiler accepts a dynamic row only at
# tile-aligned offsets, so a block holds a sublane-aligned group of rows and
# the kernel selects row t inside it
TOKEN_BLOCK = 8


def _pad_axis(a: jnp.ndarray, axis: int, mult: int, value=0) -> jnp.ndarray:
    r = (-a.shape[axis]) % mult
    if r:
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, r)
        a = jnp.pad(a, pad, constant_values=value)
    return a


def _decode_moe_kernel(
    ids_ref, w_ref, scl_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
    *, k: int, quantized: bool,
):
    t, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((t % TOKEN_BLOCK == 0) & (j == 0) & (n == 0))
    def _init():  # first visit of this token block's output rows
        o_ref[...] = jnp.zeros_like(o_ref)

    # row t of its token block, selected with a mask-sum (adding zeros is
    # exact) instead of a dynamic sublane slice
    rows = jax.lax.broadcasted_iota(jnp.int32, (TOKEN_BLOCK, 1), 0)
    is_t = rows == t % TOKEN_BLOCK
    row = jnp.sum(jnp.where(is_t, x_ref[...].astype(jnp.float32), 0.0), axis=0, keepdims=True)  # (1, d)
    wg = wg_ref[0].astype(jnp.float32)
    wu = wu_ref[0].astype(jnp.float32)
    wd = wd_ref[0].astype(jnp.float32)
    if quantized:
        # per-expert scales are control words in SMEM next to the plan's
        # expert ids; the int8 tile is dequantized elementwise BEFORE the
        # dot, so the launch is bitwise-equal to running the unquantized
        # kernel on the jnp-dequantized stacks ((x·w)*s would not be)
        e = ids_ref[t * k + j]
        wg = wg * scl_ref[0, e]
        wu = wu * scl_ref[1, e]
        wd = wd * scl_ref[2, e]
    g = jnp.dot(row, wg, preferred_element_type=jnp.float32)  # (1, bf)
    u = jnp.dot(row, wu, preferred_element_type=jnp.float32)
    y = jnp.dot(jax.nn.silu(g) * u, wd, preferred_element_type=jnp.float32)  # (1, d)

    # combine epilogue: accumulate into the destination token row, scaled by
    # the assignment's router weight from SMEM.  Padded f-tiles contribute
    # silu(0)*0 = 0, so accumulating across n needs no masking.
    w = w_ref[t * k + j]
    o_ref[...] = o_ref[...] + jnp.where(is_t, w * y, 0.0)


def _vmem_limit(d: int, bf: int, itemsize: int) -> int:
    """Scoped VMEM this launch needs: three double-buffered weight tiles,
    their f32 working copies, and the double-buffered token input and f32
    output blocks — with headroom, capped below the chip's 128 MiB."""
    tiles = 3 * d * bf * (2 * itemsize + 4)
    tokens = 2 * TOKEN_BLOCK * d * (4 + 4)
    return int(min(tiles + tokens + (8 << 20), 100 << 20))


@functools.partial(jax.jit, static_argnames=("bf", "quantized", "interpret"))
def decode_moe_pallas(
    x: jnp.ndarray,           # (T, d) decode tokens (one per sequence)
    expert_ids: jnp.ndarray,  # (T, k) int32 plan control words
    weights: jnp.ndarray,     # (T, k) f32 combine weights
    w_gate: jnp.ndarray,      # (E, d, f) — int8 when quantized
    w_up: jnp.ndarray,        # (E, d, f)
    w_down: jnp.ndarray,      # (E, f, d)
    scales: jnp.ndarray = None,  # (3, E) f32 per-expert gate/up/down scales
    *,
    bf: int = 256,
    quantized: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Plan-steered decode MoE, (T, d) -> (T, d) f32, single launch."""
    T, d = x.shape
    k = expert_ids.shape[1]
    f = w_gate.shape[-1]
    bf = min(bf, f)

    ids = expert_ids.reshape(-1).astype(jnp.int32)  # (T*k,) SMEM control words
    ws = weights.reshape(-1).astype(jnp.float32)
    if scales is None:
        scales = jnp.ones((3, 1), jnp.float32)  # never read: quantized is static
    wg = _pad_axis(w_gate, 2, bf)
    wu = _pad_axis(w_up, 2, bf)
    wd = _pad_axis(w_down, 1, bf)
    nf = wg.shape[-1] // bf
    xp = _pad_axis(x, 0, TOKEN_BLOCK)  # whole token blocks; the grid visits only real rows
    grid = (T, k, nf)

    def tile_in(t, j, n, ids_ref, *_):  # (E, d, f) stacks: expert, 0, f-tile
        return (ids_ref[t * k + j], 0, n)

    def tile_out(t, j, n, ids_ref, *_):  # (E, f, d) stack: expert, f-tile, 0
        return (ids_ref[t * k + j], n, 0)

    def token_block(t, j, n, *_):
        return (t // TOKEN_BLOCK, 0)

    out = pl.pallas_call(
        functools.partial(_decode_moe_kernel, k=k, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TOKEN_BLOCK, d), token_block),
                # the plan steers the DMA: only the selected expert's tiles move
                pl.BlockSpec((1, d, bf), tile_in),
                pl.BlockSpec((1, d, bf), tile_in),
                pl.BlockSpec((1, bf, d), tile_out),
            ],
            # f32 accumulator of a token block, revisited by its rows'
            # consecutive grid steps and flushed to HBM when the block moves on
            out_specs=pl.BlockSpec((TOKEN_BLOCK, d), token_block),
        ),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # scatter-accumulate into a shared output block: strictly sequential
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(d, bf, jnp.dtype(wg.dtype).itemsize),
        ),
        interpret=interpret,
        name="moe_decode",
    )(ids, ws, scales.astype(jnp.float32), xp, wg, wu, wd)
    return out[:T]
