"""jit'd wrappers for the decode MoE data plane.

``decode_moe`` executes a :class:`~repro.core.plans.DecodePlan` over the
expert stacks in one plan-steered Pallas launch on TPU; off-TPU it runs the
jnp gather oracle (which is also the fastest CPU shape at tiny T — the
interpreter's per-step cost would dominate a T*k-step grid).  Pass
``interpret=True`` to force the kernel through the interpreter (parity
tests), or ``interpret=False`` to lower it for the TPU compiler off-TPU
(compile rehearsals).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.plans import DecodePlan
from repro.kernels import on_tpu, resolve_interpret
from repro.kernels.moe_decode import ref
from repro.kernels.moe_decode.kernel import decode_moe_pallas


def decode_moe(
    x: jnp.ndarray,  # (T, d)
    plan: DecodePlan,
    p,               # {"w_gate": (E,d,f), "w_up": ..., "w_down": (E,f,d)}
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Plan-steered decode expert pipeline, (T, d) -> (T, d), one launch.

    When the param dict carries pre-quantized expert stacks (``w_gate_q`` et
    al., built by ``init_moe`` under ``cfg.expert_dtype == "int8"``) the
    decode path consumes the int8 stacks + per-expert scale control words —
    the f32 stacks stay untouched for prefill/train.
    """
    quantized = "w_gate_q" in p
    if quantized:
        stacks = (p["w_gate_q"], p["w_up_q"], p["w_down_q"])
        scales = jnp.stack([p["w_gate_s"], p["w_up_s"], p["w_down_s"]]).astype(jnp.float32)
    else:
        stacks, scales = (p["w_gate"], p["w_up"], p["w_down"]), None
    if interpret is None and not on_tpu():
        y = ref.decode_moe(x, plan.expert_ids, plan.weights, *stacks, scales=scales)
    else:
        if not quantized:
            stacks = tuple(w.astype(x.dtype) for w in stacks)
        y = decode_moe_pallas(
            x, plan.expert_ids, plan.weights, *stacks, scales,
            quantized=quantized, interpret=resolve_interpret(interpret),
        )
    return y.astype(x.dtype)
