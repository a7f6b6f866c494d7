"""jit'd wrappers: DispatchPlan in, kernel invocations out.

``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.plans import DispatchPlan
from repro.kernels import resolve_interpret
from repro.kernels.moe_dispatch.kernel import combine_pallas, dispatch_pallas



def dispatch(x: jnp.ndarray, plan: DispatchPlan, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Gather (T, d) tokens into (E, C, d) expert slots per the plan."""
    T, d = x.shape
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    return dispatch_pallas(
        x_pad, plan.flat_dispatch_idx(),
        num_experts=plan.num_experts, capacity=plan.capacity,
        interpret=resolve_interpret(interpret),
    )


def combine(y_slots: jnp.ndarray, plan: DispatchPlan, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Weighted scatter of (E, C, d) expert outputs back to (T, d) tokens."""
    E, C, d = y_slots.shape
    T, k = plan.combine_idx.shape
    y_pad = jnp.concatenate(
        [y_slots.reshape(E * C, d), jnp.zeros((1, d), y_slots.dtype)], axis=0
    )
    cidx, w = plan.flat_combine_words()
    out = combine_pallas(y_pad, cidx, w, top_k=k, interpret=resolve_interpret(interpret))
    return out.astype(y_slots.dtype)
