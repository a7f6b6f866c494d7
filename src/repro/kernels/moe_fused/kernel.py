"""Fused MoE data plane: plan-steered gather -> grouped GEMM -> scatter in two
Pallas launches.

The unfused pipeline pays the control-flow cost three times per layer:
``dispatch_pallas`` materializes the gathered (E, C, d) slot tensor in HBM,
the grouped GEMMs read it back, and ``combine_pallas`` round-trips the
(E, C, d) expert outputs once more (one token row per grid step).  Here the
DispatchPlan's flat control words ride the scalar-prefetch path (SMEM) into
the GEMM prologue/epilogue instead:

* ``fused_gather_swiglu_pallas`` — the gather IS the GEMM prologue: for each
  (expert, slot-block) the kernel DMAs the plan-selected token rows into a
  VMEM scratch tile and immediately feeds them to the gate/up projections +
  SwiGLU, emitting hidden slots (E, C, f).  The (E, C, d) dispatch tensor is
  never materialized.
* ``fused_down_combine_pallas`` — the scatter IS the GEMM epilogue: each
  (expert, slot-block) down-projection tile is weight-scaled and
  scatter-accumulated straight into the token-major (T, d) f32 accumulator
  (the whole-output VMEM block, revisited across the sequential grid), using
  the slot->token indices and slot weights from SMEM.  The (E, C, d) expert
  output tensor is never materialized either.

This is the kernel-level analogue of the paper's temporally loosely-coupled
control handling: the control plane (router -> plan) ran earlier; the data
plane executes the pre-computed configuration with zero exposed control cost.

Capacity blocks: K (d_model for up, d_ff for down) is deliberately untiled —
MoE projection depths fit VMEM as (bm, K)/(K, bn) tiles and untiled K keeps
the accumulator single-shot (no cross-step carry).  Token count bound: the
gather source x (T+1, d) and the combine accumulator (T+1, d) live in VMEM as
whole blocks fetched/flushed once, so T*d*4B must fit VMEM alongside one
weight tile; shard tokens (see parallel/moe_parallel.py) before that bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _pad_axis(a: jnp.ndarray, axis: int, mult: int, value=0) -> jnp.ndarray:
    r = (-a.shape[axis]) % mult
    if r:
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, r)
        a = jnp.pad(a, pad, constant_values=value)
    return a


def _pad_slots(flat: jnp.ndarray, num_experts: int, capacity: int, bm: int, value):
    """(E*C,) slot-major control words -> (E*Cp,) with per-expert tail padding."""
    return _pad_axis(flat.reshape(num_experts, capacity), 1, bm, value).reshape(-1)


# ---------------------------------------------------------------------------
# launch 1: gather + gate/up projections + SwiGLU -> hidden slots (E, C, f)
# ---------------------------------------------------------------------------


def _gather_swiglu_kernel(idx_ref, x_ref, wg_ref, wu_ref, h_ref, xs_ref, *, bm: int, cap_p: int):
    e, c, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    # Gather prologue: pull the plan-selected token rows for this slot block
    # into the VMEM scratch tile.  Runs once per (e, c) — the n (f-tile) axis
    # is innermost and sequential, so the tile is reused across f blocks.
    @pl.when(n == 0)
    def _gather():
        base = e * cap_p + c * bm

        def body(r, carry):
            tok = idx_ref[base + r]  # control word from SMEM
            xs_ref[pl.ds(r, 1), :] = x_ref[pl.ds(tok, 1), :]
            return carry

        jax.lax.fori_loop(0, bm, body, 0)

    xs = xs_ref[...]
    g = jnp.dot(xs, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(xs, wu_ref[0], preferred_element_type=jnp.float32)
    h_ref[...] = (jax.nn.silu(g) * u).astype(h_ref.dtype)[None]


@functools.partial(
    jax.jit,
    static_argnames=("num_experts", "capacity", "bm", "bn", "out_dtype", "interpret"),
)
def fused_gather_swiglu_pallas(
    x_pad: jnp.ndarray,     # (T+1, d): token rows + zero pad row at index T
    flat_idx: jnp.ndarray,  # (E*C,) int32 in [0, T]; T = empty slot
    w_gate: jnp.ndarray,    # (E, d, f)
    w_up: jnp.ndarray,      # (E, d, f)
    *,
    num_experts: int,
    capacity: int,
    bm: int = 128,
    bn: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    E, C = num_experts, capacity
    Tp, d = x_pad.shape
    f = w_gate.shape[-1]
    out_dtype = out_dtype or x_pad.dtype
    bm, bn = min(bm, C), min(bn, f)

    idx_p = _pad_slots(flat_idx.astype(jnp.int32), E, C, bm, Tp - 1)
    wg = _pad_axis(w_gate, 2, bn)
    wu = _pad_axis(w_up, 2, bn)
    Cp, fp = idx_p.shape[0] // E, wg.shape[-1]
    grid = (E, Cp // bm, fp // bn)

    h = pl.pallas_call(
        functools.partial(_gather_swiglu_kernel, bm=bm, cap_p=Cp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((Tp, d), lambda e, c, n, idx_ref: (0, 0)),  # whole x, fetched once
                pl.BlockSpec((1, d, bn), lambda e, c, n, idx_ref: (e, 0, n)),
                pl.BlockSpec((1, d, bn), lambda e, c, n, idx_ref: (e, 0, n)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda e, c, n, idx_ref: (e, c, n)),
            scratch_shapes=[pltpu.VMEM((bm, d), x_pad.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, Cp, fp), out_dtype),
        compiler_params=pltpu.CompilerParams(
            # e/c may split across cores; n must stay sequential so the
            # gathered scratch tile from n == 0 is still live for n > 0
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx_p, x_pad, wg, wu)
    return h[:, :C, :f]


# ---------------------------------------------------------------------------
# launch 2: down projection + weighted scatter-combine -> tokens (T, d)
# ---------------------------------------------------------------------------


def _down_combine_kernel(idx_ref, w_ref, h_ref, wd_ref, o_ref, *, bm: int, cap_p: int):
    e, c = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (c == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    y = jnp.dot(h_ref[0], wd_ref[0], preferred_element_type=jnp.float32)  # (bm, d)

    # Scatter epilogue: each slot row accumulates into its destination token,
    # scaled by the slot's router weight (0 for empty/dropped slots, whose
    # destination is the dump row T — sliced off by the wrapper).
    base = e * cap_p + c * bm

    def body(r, carry):
        tok = idx_ref[base + r]
        w = w_ref[base + r]
        row = jax.lax.dynamic_slice_in_dim(y, r, 1, axis=0)
        o_ref[pl.ds(tok, 1), :] = o_ref[pl.ds(tok, 1), :] + w * row
        return carry

    jax.lax.fori_loop(0, bm, body, 0)


@functools.partial(
    jax.jit, static_argnames=("num_tokens", "bm", "interpret")
)
def fused_down_combine_pallas(
    h: jnp.ndarray,         # (E, C, f) hidden slots
    w_down: jnp.ndarray,    # (E, f, d)
    flat_idx: jnp.ndarray,  # (E*C,) int32 destination token per slot; T = empty
    slot_w: jnp.ndarray,    # (E*C,) f32 combine weight per slot (0 = empty)
    *,
    num_tokens: int,
    bm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    E, C, f = h.shape
    d = w_down.shape[-1]
    T = num_tokens
    bm = min(bm, C)

    h_p = _pad_axis(h, 1, bm)
    idx_p = _pad_slots(flat_idx.astype(jnp.int32), E, C, bm, T)
    w_p = _pad_slots(slot_w.astype(jnp.float32), E, C, bm, 0.0)
    Cp = h_p.shape[1]
    grid = (E, Cp // bm)

    out = pl.pallas_call(
        functools.partial(_down_combine_kernel, bm=bm, cap_p=Cp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, f), lambda e, c, idx_ref, w_ref: (e, c, 0)),
                pl.BlockSpec((1, f, d), lambda e, c, idx_ref, w_ref: (e, 0, 0)),
            ],
            # token-blocked f32 accumulator: the whole (T+1, d) output block is
            # revisited (constant index_map) across the sequential grid and
            # flushed to HBM exactly once at the end
            out_specs=pl.BlockSpec((T + 1, d), lambda e, c, idx_ref, w_ref: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T + 1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # scatter-accumulate into a shared output block: strictly sequential
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(idx_p, w_p, h_p, w_down)
    return out[:T]
