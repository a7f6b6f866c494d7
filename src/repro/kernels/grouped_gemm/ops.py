"""jit'd wrappers: grouped GEMM and the per-expert SwiGLU used as the MoE
data-plane experts_fn (drop-in for repro.models.moe.local_experts_fn)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.grouped_gemm.kernel import grouped_gemm_pallas


def grouped_gemm(
    x: jnp.ndarray, w: jnp.ndarray, *, interpret: Optional[bool] = None, **tiles
) -> jnp.ndarray:
    return grouped_gemm_pallas(x, w, interpret=resolve_interpret(interpret), **tiles)


def grouped_swiglu(
    x_slots: jnp.ndarray,  # (E, C, d)
    w_gate: jnp.ndarray,   # (E, d, f)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,   # (E, f, d)
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    dt = x_slots.dtype
    return _swiglu_slots(
        resolve_interpret(interpret), x_slots, w_gate.astype(dt), w_up.astype(dt), w_down.astype(dt)
    )


# A pallas_call has no transpose rule, so reverse-mode differentiation (MoE
# training whenever kernels run) gets its backward pass from the einsum
# twins of the three GEMMs, fed the gate/up activations the forward kept.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _swiglu_slots(interpret, x, wg, wu, wd):
    return _swiglu_fwd(interpret, x, wg, wu, wd)[0]


def _swiglu_fwd(interpret, x, wg, wu, wd):
    g = grouped_gemm_pallas(x, wg, interpret=interpret)
    u = grouped_gemm_pallas(x, wu, interpret=interpret)
    y = grouped_gemm_pallas(jax.nn.silu(g) * u, wd, interpret=interpret)
    return y, (x, wg, wu, wd, g, u)


def _swiglu_bwd(interpret, res, dy):
    x, wg, wu, wd, g, u = res
    h, h_vjp = jax.vjp(lambda g, u: jax.nn.silu(g) * u, g, u)
    dh, dwd = jax.vjp(lambda h, w: jnp.einsum("ecf,efd->ecd", h, w), h, wd)[1](dy)
    dg, du = h_vjp(dh)
    _, gate_vjp = jax.vjp(lambda x, w: jnp.einsum("ecd,edf->ecf", x, w), x, wg)
    _, up_vjp = jax.vjp(lambda x, w: jnp.einsum("ecd,edf->ecf", x, w), x, wu)
    dx_g, dwg = gate_vjp(dg)
    dx_u, dwu = up_vjp(du)
    return dx_g + dx_u, dwg, dwu, dwd


_swiglu_slots.defvjp(_swiglu_fwd, _swiglu_bwd)


def pallas_experts_fn(x_slots: jnp.ndarray, p) -> jnp.ndarray:
    """experts_fn signature used by repro.models.moe.moe_ffn."""
    return grouped_swiglu(x_slots, p["w_gate"], p["w_up"], p["w_down"])
