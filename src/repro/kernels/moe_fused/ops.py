"""jit'd wrappers for the fused MoE data plane.

``fused_moe_apply`` is the whole expert pipeline in two Pallas launches:
plan-steered gather + gate/up + SwiGLU, then down projection + weighted
scatter-combine.  No (E, C, d) tensor is ever materialized — only the
(E, C, f) hidden slots between the two launches.

Off the served path: the TPU compiler refuses the per-row gather and
scatter of these kernels (dynamic sublane offsets, ``dynamic_slice``), so
they run in the Pallas interpreter only, opted into with
``moe_ffn(fused=True)``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.plans import DispatchPlan
from repro.kernels import resolve_interpret
from repro.kernels.moe_fused.kernel import (
    fused_down_combine_pallas,
    fused_gather_swiglu_pallas,
)


def fused_gather_swiglu(
    x: jnp.ndarray,         # (T, d)
    flat_idx: jnp.ndarray,  # (E*C,)
    w_gate: jnp.ndarray,    # (E, d, f)
    w_up: jnp.ndarray,
    *,
    num_experts: int,
    capacity: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    T, d = x.shape
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    return fused_gather_swiglu_pallas(
        x_pad,
        flat_idx,
        w_gate.astype(x.dtype),
        w_up.astype(x.dtype),
        num_experts=num_experts,
        capacity=capacity,
        interpret=resolve_interpret(interpret),
    )


def fused_down_combine(
    h: jnp.ndarray,         # (E, C, f)
    w_down: jnp.ndarray,    # (E, f, d)
    flat_idx: jnp.ndarray,
    slot_w: jnp.ndarray,
    *,
    num_tokens: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    return fused_down_combine_pallas(
        h,
        w_down.astype(h.dtype),
        flat_idx,
        slot_w,
        num_tokens=num_tokens,
        interpret=resolve_interpret(interpret),
    )


def fused_moe_apply(
    x: jnp.ndarray,         # (T, d)
    flat_idx: jnp.ndarray,  # (E*C,) slot -> token (T = empty)
    slot_w: jnp.ndarray,    # (E*C,) combine weight per slot
    w_gate: jnp.ndarray,    # (E, d, f)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,    # (E, f, d)
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Full plan-steered expert pipeline, (T, d) -> (T, d), two launches."""
    E = w_gate.shape[0]
    C = flat_idx.shape[0] // E
    h = fused_gather_swiglu(
        x, flat_idx, w_gate, w_up, num_experts=E, capacity=C, interpret=interpret
    )
    y = fused_down_combine(
        h, w_down, flat_idx, slot_w, num_tokens=x.shape[0], interpret=interpret
    )
    return y.astype(x.dtype)


def fused_moe_fn(
    x: jnp.ndarray, plan: DispatchPlan, p, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Plan-level entry point used by :func:`repro.models.moe.moe_ffn` under
    ``fused=True`` (replaces dispatch -> experts_fn -> combine)."""
    return fused_moe_apply(
        x,
        plan.flat_dispatch_idx(),
        plan.flat_slot_w(),
        p["w_gate"],
        p["w_up"],
        p["w_down"],
        interpret=interpret,
    )

